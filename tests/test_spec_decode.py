"""Speculative decoding pieces: n-gram proposal, exact rejection-sampling
verification (distribution preservation), multi-query decode attention,
and the spec decode step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.ops.attention import (
    decode_attention,
    decode_attention_chunk,
)
from areal_tpu.ops.ngram import propose_ngram
from areal_tpu.ops.sampling import sample_token, spec_accept


class TestProposeNgram:
    def test_copies_continuation_of_most_recent_match(self):
        # History: 1 2 3 9 8 | 2 3  -> trailing 2-gram (2,3) matched at
        # position 1; continuation = 9 8.
        row = [1, 2, 3, 9, 8, 2, 3]
        t = jnp.asarray([row + [0] * 5], jnp.int32)
        d = propose_ngram(t, jnp.asarray([7]), k=2, m=2)
        np.testing.assert_array_equal(np.asarray(d), [[9, 8]])

    def test_most_recent_match_wins(self):
        # (5 6) occurs twice; most recent continuation is 42.
        row = [5, 6, 7, 1, 5, 6, 42, 3, 5, 6]
        t = jnp.asarray([row], jnp.int32)
        d = propose_ngram(t, jnp.asarray([len(row)]), k=1, m=2)
        np.testing.assert_array_equal(np.asarray(d), [[42]])

    def test_fallback_repeats_last_token(self):
        t = jnp.asarray([[4, 5, 6, 7, 0, 0]], jnp.int32)
        d = propose_ngram(t, jnp.asarray([4]), k=3, m=2)
        np.testing.assert_array_equal(np.asarray(d), [[7, 7, 7]])

    def test_short_history(self):
        t = jnp.asarray([[9, 0, 0, 0]], jnp.int32)
        d = propose_ngram(t, jnp.asarray([1]), k=2, m=3)
        np.testing.assert_array_equal(np.asarray(d), [[9, 9]])

    def test_continuation_clamped_to_history(self):
        # Match near the end: continuation runs past lens -> padded with
        # the last token.
        row = [1, 2, 8, 1, 2]
        t = jnp.asarray([row + [0] * 3], jnp.int32)
        d = propose_ngram(t, jnp.asarray([5]), k=3, m=2)
        np.testing.assert_array_equal(np.asarray(d), [[8, 1, 2]])


class TestSpecAccept:
    def test_greedy_chain_matches_argmax(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.standard_normal((4, 3, 16)), jnp.float32)
        argm = np.asarray(jnp.argmax(logits, -1))
        # Drafts: row 0 all-correct, row 1 wrong at 0, row 2 wrong at 1,
        # row 3 all-correct.
        drafts = argm[:, :2].copy()
        drafts[1, 0] = (drafts[1, 0] + 1) % 16
        drafts[2, 1] = (drafts[2, 1] + 1) % 16
        emitted, logps, n_emit = spec_accept(
            logits, jnp.asarray(drafts), jax.random.PRNGKey(0), greedy=True
        )
        emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
        np.testing.assert_array_equal(n_emit, [3, 1, 2, 3])
        # Row 0: both drafts + bonus, all argmax.
        np.testing.assert_array_equal(emitted[0], argm[0])
        # Row 1: rejected at 0 -> emit argmax of position 0 only.
        assert emitted[1, 0] == argm[1, 0]
        # Row 2: accepted draft 0, closing argmax at position 1.
        np.testing.assert_array_equal(emitted[2, :2], argm[2, :2])

    def test_k0_matches_sample_token(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        key = jax.random.PRNGKey(7)
        emitted, logps, n_emit = spec_accept(
            logits[:, None, :], jnp.zeros((8, 0), jnp.int32), key
        )
        assert np.asarray(n_emit).tolist() == [1] * 8
        # Same logp convention as sample_token.
        tok = np.asarray(emitted)[:, 0]
        scaled = np.asarray(logits)
        ref_lp = scaled[np.arange(8), tok] - np.log(
            np.exp(scaled).sum(-1)
        )
        np.testing.assert_allclose(
            np.asarray(logps)[:, 0], ref_lp, rtol=1e-4, atol=1e-5
        )

    @pytest.mark.parametrize("top_p", [1.0, 0.8])
    def test_marginal_distribution_preserved(self, top_p):
        """Position-0 emissions must follow the warped model distribution
        exactly, whatever the draft is (the whole point of rejection
        sampling)."""
        V, N = 8, 40000
        rng = np.random.default_rng(2)
        logits_row = rng.standard_normal((2, V)).astype(np.float32)
        logits = jnp.asarray(np.broadcast_to(logits_row, (N, 2, V)))
        drafts = jnp.full((N, 1), 3, jnp.int32)  # a fixed, arbitrary draft

        emitted, _, _ = spec_accept(
            logits, drafts, jax.random.PRNGKey(3), top_p=top_p
        )
        first = np.asarray(emitted)[:, 0]
        counts = np.bincount(first, minlength=V) / N

        from areal_tpu.ops.sampling import apply_top_k, apply_top_p

        warped = np.asarray(
            apply_top_p(apply_top_k(jnp.asarray(logits_row[0:1]), 0), top_p)
        )[0]
        probs = np.exp(warped - warped.max())
        probs[warped < -1e9] = 0.0
        probs /= probs.sum()
        np.testing.assert_allclose(counts, probs, atol=0.012)

    def test_second_position_conditional_distribution(self):
        """Among rows whose draft-0 was accepted, position-1 emissions
        follow position-1's model distribution."""
        V, N = 6, 60000
        rng = np.random.default_rng(4)
        row = rng.standard_normal((3, V)).astype(np.float32)
        logits = jnp.asarray(np.broadcast_to(row, (N, 3, V)))
        drafts = jnp.asarray(
            np.tile(np.array([[2, 4]], np.int64), (N, 1)), jnp.int32
        )
        emitted, _, n_emit = spec_accept(
            logits, drafts, jax.random.PRNGKey(5)
        )
        emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
        reached = n_emit >= 2  # draft 0 accepted
        p0 = np.exp(row[0] - row[0].max()); p0 /= p0.sum()
        # Acceptance rate of draft 0 == p0[2].
        np.testing.assert_allclose(reached.mean(), p0[2], atol=0.01)
        second = emitted[reached, 1]
        counts = np.bincount(second, minlength=V) / reached.sum()
        p1 = np.exp(row[1] - row[1].max()); p1 /= p1.sum()
        np.testing.assert_allclose(counts, p1, atol=0.015)


class TestSpecDecodeStep:
    def test_chunk_attention_matches_sequential(self):
        rng = np.random.default_rng(5)
        B, S, nq, nkv, d, Q = 2, 16, 4, 2, 8, 3
        k_cache = jnp.asarray(rng.standard_normal((B, S, nkv, d)), jnp.float32)
        v_cache = jnp.asarray(rng.standard_normal((B, S, nkv, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((B, Q, nq, d)), jnp.float32)
        vf = jnp.zeros((B,), jnp.int32)
        vt0 = jnp.asarray([5, 9], jnp.int32)
        out = decode_attention_chunk(q, k_cache, v_cache, vf, vt0)
        for i in range(Q):
            ref = decode_attention(
                q[:, i:i+1], k_cache, v_cache, vf, vt0 + i
            )
            np.testing.assert_allclose(
                np.asarray(out[:, i:i+1]), np.asarray(ref),
                rtol=1e-5, atol=1e-5,
            )

    def test_ragged_step_q_len_q_matches_q_single_lane_steps(self):
        """A row that owns Q lanes of one `decode_step_ragged_paged` call
        (a spec-verify row, a prefill slice, a resume replay) must get the
        same logits and pool contents as Q calls in which it owns one
        lane each."""
        cfg = tiny_config()
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        B, Q, ps, mp = 2, 3, 2, 4
        rng = np.random.default_rng(6)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, Q)), jnp.int32)
        # Row b owns pages b*mp .. b*mp+mp-1; fresh rows, positions 0..Q-1.
        table = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
        pool = tfm.init_paged_kv_cache(cfg, B * mp, ps, jnp.float32)
        wide_logits, wide_pool = tfm.decode_step_ragged_paged(
            params, cfg, toks.reshape(-1),
            jnp.tile(jnp.arange(Q, dtype=jnp.int32), B), pool, table,
            jnp.repeat(jnp.arange(B, dtype=jnp.int32), Q),
        )
        wide_logits = wide_logits.reshape(B, Q, -1)
        pool2 = tfm.init_paged_kv_cache(cfg, B * mp, ps, jnp.float32)
        for t in range(Q):
            lg, pool2 = tfm.decode_step_ragged_paged(
                params, cfg, toks[:, t], jnp.full((B,), t, jnp.int32),
                pool2, table, jnp.arange(B, dtype=jnp.int32),
            )
            np.testing.assert_allclose(
                np.asarray(wide_logits[:, t]), np.asarray(lg),
                rtol=2e-4, atol=2e-4,
            )
        np.testing.assert_allclose(
            np.asarray(wide_pool.k), np.asarray(pool2.k),
            rtol=1e-5, atol=1e-5,
        )


class TestSpecGeneratorE2E:
    @pytest.fixture(scope="class")
    def setup(self):
        from areal_tpu.base.topology import ParallelConfig, make_mesh
        from areal_tpu.engines.generator import GeneratorEngine

        cfg = tiny_config()
        params = tfm.init_params(cfg, jax.random.PRNGKey(11))
        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        eng = GeneratorEngine(cfg, params, mesh, eos_token_id=7,
                              max_decode_batch=4)
        return cfg, eng

    def _sample(self, cfg, lens, seed=0):
        from areal_tpu.api.data_api import SequenceSample

        rng = np.random.default_rng(seed)
        data = np.concatenate(
            [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
        ).astype(np.int32)
        return SequenceSample(
            keys={"packed_prompts"},
            ids=[f"p{i}" for i in range(len(lens))],
            seqlens={"packed_prompts": [[l] for l in lens]},
            data={"packed_prompts": data},
        )

    @pytest.mark.parametrize("k", [1, 3])
    def test_greedy_spec_matches_plain(self, setup, k):
        from areal_tpu.api.data_api import MicroBatchSpec
        from areal_tpu.api.model_api import GenerationHyperparameters

        cfg, eng = setup
        sample = self._sample(cfg, lens=(6, 11, 4, 9, 13, 5))
        g0 = GenerationHyperparameters(n=1, max_new_tokens=12, greedy=True)
        gs = GenerationHyperparameters(
            n=1, max_new_tokens=12, greedy=True,
            spec_decode_k=k, spec_ngram=2,
        )
        plain = eng.generate(sample, MicroBatchSpec(), g0, inflight=True)
        spec = eng.generate(sample, MicroBatchSpec(), gs)
        assert (
            spec.seqlens["packed_input_ids"]
            == plain.seqlens["packed_input_ids"]
        )
        np.testing.assert_array_equal(
            np.asarray(spec.data["packed_input_ids"]),
            np.asarray(plain.data["packed_input_ids"]),
        )
        np.testing.assert_allclose(
            np.asarray(spec.data["packed_logprobs"]),
            np.asarray(plain.data["packed_logprobs"]),
            rtol=5e-4, atol=5e-4,
        )

    @pytest.mark.slow
    def test_sampled_spec_valid_outputs(self, setup):
        """Sampled spec decoding: outputs are well-formed (logprobs match a
        recompute through the model) even with refills and mixed lengths."""
        from areal_tpu.api.data_api import MicroBatchSpec
        from areal_tpu.api.model_api import GenerationHyperparameters

        cfg, eng = setup
        sample = self._sample(cfg, lens=(5, 9, 6, 12, 8, 4, 10, 7), seed=3)
        g = GenerationHyperparameters(
            n=2, max_new_tokens=10, temperature=1.0,
            spec_decode_k=2, spec_ngram=2,
        )
        out = eng.generate(sample, MicroBatchSpec(), g, seed=5)
        lens = out.seqlens["packed_input_ids"]
        assert len(lens) == 8 and all(len(row) == 2 for row in lens)
        toks = np.asarray(out.data["packed_input_ids"])
        lps = np.asarray(out.data["packed_logprobs"])
        noe = np.asarray(out.data["seq_no_eos_mask"])
        assert np.isfinite(lps).all()
        # Recompute behavior logprobs with the model: for each sequence,
        # forward and gather log p(tok_t | prefix) on generated positions.
        t_off = lp_off = 0
        pl_iter = iter([l for row in sample.seqlens["packed_prompts"]
                        for l in row for _ in range(2)])
        for row_lens in lens:
            for L in row_lens:
                pl = next(pl_iter)
                seq = toks[t_off:t_off + L]
                row_lp = lps[lp_off:lp_off + L - 1]
                t = jnp.asarray(seq[None, :], jnp.int32)
                logits = tfm.forward(
                    eng.params, cfg, t, jnp.ones_like(t)
                )[0]
                logq = jax.nn.log_softmax(
                    np.asarray(logits, np.float32), axis=-1
                )
                for j in range(pl, L):
                    want = float(logq[j - 1, seq[j]])
                    got = float(row_lp[j - 1])
                    assert abs(want - got) < 5e-3, (j, want, got)
                # EOS bookkeeping consistent.
                t_off += L
                lp_off += L - 1
        assert set(np.unique(noe)).issubset({0.0, 1.0})


def test_spec_decoding_on_sharded_mesh():
    """Spec decoding under a d2 mesh (batch-sharded inflight pool) matches
    the single-device greedy output."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    rng = np.random.default_rng(4)
    lens = (6, 9, 5, 11)
    data = np.concatenate(
        [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
    ).astype(np.int32)
    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(lens))],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={"packed_prompts": data},
    )
    g = GenerationHyperparameters(
        n=1, max_new_tokens=10, greedy=True, spec_decode_k=2, spec_ngram=2
    )

    def run(layout, n_dev):
        eng = GeneratorEngine(
            cfg, params,
            make_mesh(ParallelConfig.from_str(layout), jax.devices()[:n_dev]),
            eos_token_id=7, max_decode_batch=4,
        )
        return eng.generate(sample, MicroBatchSpec(), g)

    want = run("d1", 1)
    got = run("d2", 2)
    np.testing.assert_array_equal(
        np.asarray(got.data["packed_input_ids"]),
        np.asarray(want.data["packed_input_ids"]),
    )


def test_spec_budget_smaller_than_draft_window():
    """max_new_tokens < K+1: the host truncates the overshoot and the
    output still matches plain greedy decoding."""
    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    eng = GeneratorEngine(
        cfg, params,
        make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1]),
        eos_token_id=7, max_decode_batch=4,
    )
    rng = np.random.default_rng(2)
    lens = (6, 9)
    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=["a", "b"],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={"packed_prompts": np.concatenate(
            [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
        ).astype(np.int32)},
    )
    g_spec = GenerationHyperparameters(
        n=1, max_new_tokens=2, greedy=True, spec_decode_k=4, spec_ngram=2
    )
    g_plain = GenerationHyperparameters(n=1, max_new_tokens=2, greedy=True)
    spec = eng.generate(sample, MicroBatchSpec(), g_spec)
    plain = eng.generate(sample, MicroBatchSpec(), g_plain, inflight=True)
    assert (
        spec.seqlens["packed_input_ids"] == plain.seqlens["packed_input_ids"]
    )
    np.testing.assert_array_equal(
        np.asarray(spec.data["packed_input_ids"]),
        np.asarray(plain.data["packed_input_ids"]),
    )


def test_spec_decode_with_int8_cache(rng):
    """Speculative decoding over an int8 KV cache completes and produces
    well-formed groups; distribution-exactness holds w.r.t. the
    quantized-cache model (drafts and verification share the cache), so
    outputs are finite and EOS semantics intact."""
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=7, max_decode_batch=2,
        kv_cache_dtype="int8",
    )
    lens = (5, 9, 4)
    data = np.concatenate(
        [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
    ).astype(np.int32)
    from areal_tpu.api.data_api import SequenceSample

    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(lens))],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={"packed_prompts": data},
    )
    g = GenerationHyperparameters(
        n=1, max_new_tokens=8, spec_decode_k=3, greedy=True
    )
    out = eng.generate(sample, MicroBatchSpec(), g)
    assert out.bs == 3
    assert np.isfinite(np.asarray(out.data["packed_logprobs"])).all()
    lens_out = [sum(r) for r in out.seqlens["packed_input_ids"]]
    assert all(
        l0 < lo <= l0 + 8 for l0, lo in zip(lens, lens_out)
    ), (lens, lens_out)
