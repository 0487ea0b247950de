"""MiniCPM-SALA (openbmb/MiniCPM-SALA, `minicpm_sala`) at toy size on the
CPU, seeded random weights, fp32: block-sparse attention by SELECTION
(InfLLM-V2: compressed keys, block scores, forced and top-k blocks; no
positions; a sigmoid output gate) beside Lightning linear attention (rope,
a constant decay a head, an output norm and gate), muP scales — against the
plain reference of `benchmark/references/minicpm_sala.py` (selection as an
explicit mask, the recurrence token by token), through the train forward
over packed rows, its gradients, and prefill + decode through the cache
(K/V, compressed keys, the fp32 state).  A toy `dense_len` / `block_size` /
`topk` makes selection run at a few hundred tokens; a case lies on each side
of `dense_len`.  Logits and log-probabilities are compared, never sampled
tokens.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base import monitor
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.models import lightning
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import LIGHTNING, MLP, SPARSE, ModelConfig, tiny_config
from areal_tpu.models.hf import registry
from areal_tpu.ops import block_sparse
from areal_tpu.parallel import sharding
from benchmark import files, peaks_sala
from benchmark import run as bench_run
from benchmark.references import minicpm_sala as reference

TOL = dict(rtol=5e-4, atol=5e-4)
CONFIG = "minicpm-sala-l4-v8.json"
FAMILY = registry.HF_FAMILIES["minicpm_sala"]
# Selection at a few hundred tokens: kernels of 8 every 4, blocks of 16, 6
# blocks a query (block 0, the 2 ending at its own, 3 by score).
SPARSE_TOY = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
                  init_blocks=1, window_size=32, dense_len=128)


def _toy_hf(**changes):
    """The benchmark configuration's keys at its `toy` sizes."""
    config, _ = bench_run.toy(
        files.load_json("configs", CONFIG),
        files.load_json("traffic", "rollout8-ctx9k-14k-256.json"))
    return dict(config, sparse_config=SPARSE_TOY, **changes)


def _cfg(**changes) -> ModelConfig:
    cfg = FAMILY.config_from_hf(_toy_hf())
    return dataclasses.replace(cfg, param_dtype="float32", **changes)


_forward = jax.jit(
    lambda p, c, t, s: tfm.forward(p, c, t, s, use_flash=False),
    static_argnums=1)
_prefill = jax.jit(
    lambda p, c, t, s, cache: tfm.prefill(p, c, t, s, cache, use_flash=False),
    static_argnums=1)
_decode = jax.jit(
    lambda p, c, tok, pos, cache, slot, vf: tfm.decode_step(
        p, c, tok, pos, cache, slot, vf, with_counts=True),
    static_argnums=1)


@pytest.fixture(scope="module")
def cfg() -> ModelConfig:
    return _cfg()


def _params(cfg, seed=5):
    """Random weights with NON-trivial norm scales (a head norm left out
    cannot pass)."""
    p = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    names = ("ln1", "ln2", "q_norm", "k_norm", "lt_q_norm", "lt_k_norm",
             "lt_norm")
    for k, name in zip(
            jax.random.split(jax.random.PRNGKey(seed + 1), len(names)), names):
        leaf = p["blocks"][name]
        p["blocks"][name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
    return p


@pytest.fixture(scope="module")
def params(cfg):
    return _params(cfg)


def _sequences(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _packed(seqs, pads=7):
    """One packed row: the sequences back to back, then pads."""
    tokens = np.concatenate(seqs + [np.zeros(pads, np.int32)])
    segs = np.concatenate(
        [np.full(len(s), i + 1, np.int32) for i, s in enumerate(seqs)]
        + [np.zeros(pads, np.int32)])
    return jnp.asarray(tokens)[None], jnp.asarray(segs)[None]


# ------------------------------------------------------------ config, reader


def test_the_config_file_holds_the_published_keys_and_the_cut():
    config = files.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "MiniCPM-SALA")
    bench = config["benchmark"]
    assert bench["source"] == row["source_url"]
    reduced = {"num_hidden_layers", "mixer_types", "vocab_size"}
    assert set(bench["reduced"]) == reduced
    for key, value in row["config"].items():
        if key not in reduced:
            assert config[key] == value, key
    assert config["mixer_types"] == row["config"]["mixer_types"][:4]
    assert config["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 3
    assert config["num_hidden_layers"] == 4
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]  # the floor
    assert config["vocab_size"] == -(-row["config"]["vocab_size"] // 8)
    for what in ("sparse_config", "lightning_decay", "lightning_output_norm",
                 "lightning_activation", "gate_width", "tensor_names",
                 "state_precision"):
        assert what in bench["assumed"], what


def test_the_leaf_count_of_the_configuration():
    """The published widths, by shapes alone: 1,184.6 M parameters."""
    big = bench_run.model_config(files.load_json("configs", CONFIG))
    shapes = jax.eval_shape(
        lambda: tfm.init_params(big, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    count = files.load_json("configs", CONFIG)["benchmark"]["leaf_count"]
    assert sum(int(np.prod(x.shape)) for x in leaves) == count["parameters"]
    assert len(leaves) == count["leaves"]
    blocks = shapes["blocks"]
    assert blocks["wq"].shape == (1, 4096, 4096)
    assert blocks["wk"].shape == (1, 4096, 256)
    assert blocks["lt_wq"].shape == (3, 4096, 4096)
    assert blocks["wg"].shape == (4, 4096, 16384)
    assert shapes["embed"].shape == shapes["lm_head"].shape[::-1] == (9181, 4096)
    # The residual multiplier keeps the PUBLISHED depth.
    assert big.residual_multiplier == pytest.approx(1.4 / 32**0.5)
    assert (big.embedding_multiplier, big.logits_scaling) == (12.0, 16.0)
    assert big.plan.unit == ((SPARSE, MLP),) + ((LIGHTNING, MLP),) * 3


def test_the_traffic_draws_its_ids_from_the_vocabulary_slice():
    """A sliced vocabulary is a smaller vocabulary: the cell's prompts
    are bytes under the benchmark's tokenizer, inside rows 0-9,180, and
    EOS (unreachable) is the first id past the slice."""
    from benchmark.tokenizer import ByteTokenizer

    config = files.load_json("configs", CONFIG)
    cell, _, traffic = files.load_cell("sala-docrl8-longctx")
    rows = bench_run.traffic_rows(cell, traffic, seed=1)
    tok = ByteTokenizer(eos_token_id=config["vocab_size"])
    ids = np.concatenate([np.asarray(tok.encode(r["prompt"])) for r in rows])
    assert 0 <= ids.min() and ids.max() < config["vocab_size"] == 9181
    assert sorted(len(r["prompt"]) for r in rows) == [10496, 13056]


def test_config_both_ways(cfg):
    assert FAMILY.config_from_hf(FAMILY.config_to_hf(cfg)) == dataclasses.replace(
        cfg, param_dtype="bfloat16")
    assert registry.infer_model_type(cfg) == "minicpm_sala"


@pytest.mark.parametrize("key,value", [
    ("attn_use_rope", True), ("lightning_use_rope", False),
    ("use_output_norm", False), ("attn_use_output_gate", False),
    ("qk_norm", False), ("lightning_nkv", 2), ("attention_bias", True),
])
def test_what_is_not_modelled_raises(key, value):
    with pytest.raises(NotImplementedError, match=key):
        FAMILY.config_from_hf(_toy_hf(**{key: value}))


def test_state_dict_round_trip_by_the_assumed_names(cfg, params):
    sd = FAMILY.params_to_sd(cfg, params)
    assert sd["model.layers.0.self_attn.o_gate.weight"].shape == (
        cfg.q_dim, cfg.hidden_dim)
    assert sd["model.layers.1.self_attn.o_norm.weight"].shape == (
        cfg.lightning_head_dim,)
    back = FAMILY.params_from_sd(cfg, sd, dtype=jnp.float32)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------- forward and gradients


@pytest.mark.parametrize("lens,why", [
    ((300,), "one sequence past dense_len: selection in every query"),
    ((100,), "one sequence under dense_len: plain causal attention"),
    ((200, 60, 150), "three segments: selection and state restart, the "
                     "middle one dense"),
])
def test_train_forward_over_packed_rows_matches_the_reference(
        cfg, params, lens, why):
    seqs = _sequences(cfg, lens)
    tokens, segs = _packed(seqs)
    got = _forward(params, cfg, tokens, segs)
    off = 0
    for s in seqs:
        np.testing.assert_allclose(
            got[0, off: off + len(s)], reference.logits(params, cfg, s),
            err_msg=why, **TOL)
        off += len(s)


def test_selection_is_not_the_dense_layer(cfg, params):
    """Past `dense_len` the logits are NOT plain causal attention's."""
    seq = _sequences(cfg, (300,), seed=4)[0]
    tokens, segs = _packed([seq], pads=0)
    dense = dataclasses.replace(cfg, sparse_dense_len=10**6)
    got = _forward(params, cfg, tokens, segs)
    other = _forward(params, dense, tokens, segs)
    bs, topk = cfg.sparse_block_size, cfg.sparse_topk
    # Identical while a query has no more blocks than it may choose ...
    np.testing.assert_allclose(got[0, : bs * topk], other[0, : bs * topk], **TOL)
    # ... and another function after.
    assert float(jnp.abs(got[0, bs * topk:] - other[0, bs * topk:]).max()) > 1e-3


@pytest.mark.parametrize("chunk", [64, 128])
def test_the_chunks_of_queries_do_not_show(chunk):
    """Two segments in a row, selection in both: chunks of queries of any
    size against the row in one chunk; the kernels are counted a segment."""
    sz = block_sparse.Sizes(
        kernel=8, stride=4, block=16, topk=5, init_blocks=1, window=32,
        dense_len=64)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    s, hq, hk, d = 208, 4, 2, 16
    q = jax.random.normal(ks[0], (1, s, hq, d))
    k = jax.random.normal(ks[1], (1, s, hk, d))
    v = jax.random.normal(ks[2], (1, s, hk, d))
    seg = jnp.concatenate(
        [jnp.ones((1, 120), jnp.int32), jnp.full((1, 88), 2, jnp.int32)], 1)
    got, kc, knum = jax.jit(
        lambda q, k, v, seg: block_sparse.packed_attention(
            q, k, v, seg, sz, chunk=chunk))(q, k, v, seg)
    want, _, _ = jax.jit(
        lambda q, k, v, seg: block_sparse.packed_attention(
            q, k, v, seg, sz, chunk=208))(q, k, v, seg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Kernels of the first segment: (120 - 8) / 4 + 1 = 29, the second's 21.
    assert int((knum >= 0).sum()) == 29 + 21


def test_loss_and_gradients_match_the_reference(cfg, params):
    seq = _sequences(cfg, (200,), seed=2)[0]
    toks = jnp.asarray(seq)

    def score(logits):
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return jnp.sum(jnp.take_along_axis(lp, toks[1:, None], axis=-1))

    def system(p):
        return score(tfm.forward(
            p, cfg, toks[None], jnp.ones((1, len(seq)), jnp.int32),
            remat="full")[0])

    loss, got = jax.jit(jax.value_and_grad(system))(params)
    ref_loss, want = jax.jit(jax.value_and_grad(
        lambda p: score(reference.logits(p, cfg, seq))))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-4)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)


# ---------------------------------- the selected attention on the flash kernels


def _row_of_384(cfg, seed=12):
    """A dense short sequence, a sparse one from index 60 — its blocks of
    16 keys straddle every tile's edge — and a third, in a row of three
    tiles of 128."""
    seqs = _sequences(cfg, (60, 200, 117), seed=seed)
    return _packed(seqs, pads=7)


@pytest.mark.parametrize("program", ["forward", "prefill"])
def test_the_kernel_form_is_the_mask_form(cfg, params, program):
    """`use_flash=True` (the flash kernels under the block choice,
    interpreted here) against `use_flash=False` (dense under the mask)."""
    tokens, segs = _row_of_384(cfg)
    if program == "forward":
        # a pad's logits are no one's (the kernels write zeros there)
        run = lambda flash: jax.jit(  # noqa: E731
            lambda p: tfm.forward(
                p, cfg, tokens, segs, use_flash=flash)[:, :-7]
        )(params)
    else:
        # one sequence a row, right-aligned, as the static program lays
        # prompts out: the logits of the last token and what the cache keeps
        seq = _sequences(cfg, (300,), seed=13)[0]
        tok = jnp.asarray(np.concatenate([np.zeros(84, np.int32), seq]))[None]
        seg = (jnp.arange(384) >= 84).astype(jnp.int32)[None]
        cache = tfm.init_kv_cache(cfg, 1, 448, dtype=jnp.float32)
        run = lambda flash: jax.jit(  # noqa: E731
            lambda p: tfm.prefill(p, cfg, tok, seg, cache, use_flash=flash)
        )(params)
    for got, want in zip(jax.tree.leaves(run(True)), jax.tree.leaves(run(False))):
        np.testing.assert_allclose(got, want, **TOL)


def test_the_kernel_forms_gradient_of_a_two_layer_plan(params):
    """Selection then Lightning attention, each branch rematerialised on
    its own (`_REMAT_BY_BRANCH`): the flash `custom_vjp` under the choice
    against the `jnp` form's `jax.grad`, every leaf."""
    two = FAMILY.config_from_hf(_toy_hf(
        num_hidden_layers=2, mixer_types=["minicpm4", "lightning-attn"]))
    two = dataclasses.replace(two, param_dtype="float32")
    assert two.plan.unit == ((SPARSE, MLP), (LIGHTNING, MLP))
    p = _params(two, seed=7)
    tokens, segs = _row_of_384(two)

    def score(p, flash):
        logits = tfm.forward(
            p, two, tokens, segs, remat="full", use_flash=flash)
        lp = jax.nn.log_softmax(logits[0, :-1], axis=-1)
        picked = jnp.take_along_axis(lp, tokens[0, 1:, None], axis=-1)[:, 0]
        return jnp.sum(picked * (segs[0, 1:] > 0))

    grad = jax.jit(jax.value_and_grad(score), static_argnums=1)
    loss, got = grad(p, True)
    want_loss, want = grad(p, False)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-4)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=2e-3,
            err_msg=name)


def test_by_itself_the_kernel_form_serves_the_gradient_program_alone(
        cfg, monkeypatch):
    """`use_flash=None` on a TPU backend: the flash kernels under the
    choice where the stack is differentiated under a remat policy, the
    `jnp` form in `forward` and prefill (their programs are the ones the
    reference check runs at set-up); True forces the kernels anywhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((1, 384), jnp.int32)

    def traced(f):
        return str(jax.make_jaxpr(f)(shapes, ints, ints))

    def loss(p, tok, seg):
        return jnp.sum(tfm.hidden_states(p, cfg, tok, seg, remat="full")[0])

    def fill(p, tok, seg, **kw):
        cache = tfm.init_kv_cache(cfg, 1, 448, dtype=jnp.float32)
        return tfm.prefill(p, cfg, tok, seg, cache, **kw)

    grad = traced(jax.grad(loss))
    assert all(k in grad for k in ("flash_fwd", "flash_dq", "flash_dkv"))
    assert "flash_fwd" not in traced(lambda *a: tfm.forward(a[0], cfg, *a[1:]))
    assert "flash_fwd" not in traced(fill)
    assert "flash_fwd" in traced(lambda *a: fill(*a, use_flash=True))


def test_the_kernel_form_refuses_a_row_of_no_whole_tiles():
    sz = block_sparse.Sizes(
        kernel=8, stride=4, block=16, topk=5, init_blocks=1, window=32,
        dense_len=64)
    q = jnp.zeros((1, 208, 4, 16))
    with pytest.raises(ValueError, match="no row of 208 tokens"):
        block_sparse.packed_attention(
            q, q[:, :, :2], q[:, :, :2], jnp.ones((1, 208), jnp.int32), sz,
            use_flash=True)
    assert not block_sparse.kernel_fits(208, sz)
    assert block_sparse.kernel_fits(13312, block_sparse.Sizes.of(
        bench_run.model_config(files.load_json("configs", CONFIG))))


def test_the_fused_logprob_head_divides_by_logits_scaling(cfg, params):
    seq = _sequences(cfg, (140,), seed=3)[0]
    tokens, segs = _packed([seq], pads=0)
    x, _ = tfm.hidden_states(params, cfg, tokens, segs, use_flash=False)
    got = tfm.per_token_output(params, cfg, x, tokens, segs)
    want, _ = reference._next_token_logprobs(
        params, cfg, reference._padded(seq), len(seq))
    np.testing.assert_allclose(got[0, : len(seq) - 1], want[: len(seq) - 1], **TOL)


@pytest.mark.parametrize("multiplier", [
    "embedding_multiplier", "residual_multiplier", "logits_scaling"])
def test_each_multiplier_is_applied(cfg, params, multiplier):
    seq = _sequences(cfg, (40,), seed=6)[0]
    tokens, segs = _packed([seq], pads=0)
    plain = dataclasses.replace(cfg, **{multiplier: 1.0})
    assert float(jnp.abs(
        _forward(params, cfg, tokens, segs)
        - _forward(params, plain, tokens, segs)).max()) > 1e-3


def test_the_chunked_recurrence_is_the_token_by_token_one():
    """Lightning attention alone: chunks with restarts against the step."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    s, h, d = 300, 4, 16
    q, k, v = (jax.random.normal(kk, (1, s, h, d)) for kk in ks)
    seg = jnp.concatenate(
        [jnp.ones((1, 170), jnp.int32), jnp.full((1, 130), 2, jnp.int32)], 1)
    y, state = jax.jit(lightning.lightning_chunked)(q, k, v, seg)
    st = jnp.zeros((1, h, d, d))
    step = jax.jit(lightning.lightning_step_jnp)
    for t in range(s):
        if t == 170:
            st = jnp.zeros_like(st)
        st, y_t = step(st, q[:, t], k[:, t], v[:, t])
        np.testing.assert_allclose(y[:, t], y_t, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, st, rtol=2e-4, atol=2e-4)
    # Head 0 forgets within two tokens, the last over 2 ** 8.
    lam = np.exp(np.asarray(lightning.decay_log(32)))
    assert lam[0] == pytest.approx(np.exp(-2 ** -0.25))
    assert lam[-1] == pytest.approx(np.exp(-2.0 ** -8))


# ------------------------------------------------- prefill, decode, the cache


@pytest.mark.parametrize("n_prompt,n_new,why", [
    (300, 40, "past dense_len: decode reads by selection"),
    (60, 60, "under dense_len to the last token: the dense form in every "
             "step (a row that CROSSES it decodes densely up to it where the "
             "trainer selects over the whole sequence: no cell has one)"),
])
def test_prefill_then_decode_through_the_cache_matches_the_reference(
        cfg, params, n_prompt, n_new, why):
    """Right-aligned prompts of two lengths in one batch, as the static
    program lays them out."""
    seq = _sequences(cfg, (n_prompt + n_new,), seed=8)[0]
    other = _sequences(cfg, (n_prompt - 17 + n_new,), seed=9)[0]
    prompts = [seq[:n_prompt], other[: n_prompt - 17]]
    sp, s_total = 320, 384
    tok = np.zeros((2, sp), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for r, p in enumerate(prompts):
        tok[r, sp - len(p):] = p
    seg = (np.arange(sp)[None] >= (sp - lens)[:, None]).astype(np.int32)
    cache = tfm.init_kv_cache(cfg, 2, s_total, dtype=jnp.float32)
    assert cache.ck.shape == (1, 2, s_total // 4, 2, 16)
    assert cache.state.shape == (3, 2, 4, 16, 16) and cache.conv is None
    logits, cache = _prefill(params, cfg, jnp.asarray(tok), jnp.asarray(seg), cache)
    want = [reference.logits(params, cfg, s) for s in (seq, other)]
    for r, n in enumerate(lens):
        np.testing.assert_allclose(logits[r], want[r][n - 1], err_msg=why, **TOL)
    valid_from = jnp.asarray(sp - lens)
    dense_rows = 0
    for i in range(n_new):
        new = jnp.asarray([seq[n_prompt + i], other[n_prompt - 17 + i]])
        logits, cache, counts = _decode(
            params, cfg, new, jnp.asarray(lens + i), cache, sp + i, valid_from)
        dense_rows += float(counts["sparse"][0, 2])
        for r, n in enumerate(lens):
            np.testing.assert_allclose(
                logits[r], want[r][n + i], err_msg=f"{why}, token {i}", **TOL)
    # What it leaves: the compressed keys and the state are the reference's.
    n = len(seq)
    _, left = reference._next_token_logprobs(
        params, cfg, reference._padded(seq), n, None, (n,))
    ck = np.asarray(left["ck"][0])
    np.testing.assert_allclose(cache.ck[0, 0, : len(ck)], ck, **TOL)
    np.testing.assert_allclose(cache.state[:, 0], left["state"][:, 0], **TOL)
    if n_prompt >= cfg.sparse_dense_len:
        assert dense_rows == 0
        read, cached = (float(x) for x in counts["sparse"][0, :2])
        assert cached == sum(lens) + 2 * n_new
        assert read < 0.6 * cached  # 6 blocks of 16 + a key per 4 tokens
    else:
        assert dense_rows > 0


def _engine(cfg, params, **kw):
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    return GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size, max_decode_batch=4,
        donation_safe_swap=False, **kw)


def test_the_static_program_counts_what_it_reads(cfg, params):
    """Prefill in waves and the decode loop of the generator itself, held
    to the reference by `check_generator`'s own readings."""
    seq = _sequences(cfg, (330,), seed=11)[0]
    reference._CHECKED.clear()
    readings, problems = reference.check_generator(params, cfg, seq)
    assert not problems, problems
    assert readings["sparse_dense_rows"] == 0
    assert readings["block_flips"] == 0.0
    assert 0.2 < readings["sparse_read_share"] < 0.6
    assert readings["state_bf16_residual_min"] > 1e-3
    eng = _engine(cfg, params)
    g = GenerationHyperparameters(n=1, max_new_tokens=4)
    eng.static_rollout([seq[:200]] * 2, g, jax.random.PRNGKey(0))
    stats = eng.last_pool_stats
    assert stats["compressed_cache_bytes"] > 0
    assert stats["lightning_state_bytes"] == 3 * 2 * 4 * 16 * 16 * 4
    assert stats["sparse_keys_cached"] == 2 * (201 + 202 + 203 + 204)


def test_a_state_kept_in_bfloat16_is_refused(cfg, params, monkeypatch):
    """The limit that refuses a lower precision: the decode step's state
    rounded to bfloat16 fails `state_bf16_residual_min`."""
    real = lightning.lightning_step_jnp

    def rounded(state, q, k, v):
        state, y = real(state, q, k, v)
        return jax.lax.reduce_precision(state, 8, 7), y

    monkeypatch.setattr(lightning, "lightning_step_jnp", rounded)
    seq = _sequences(cfg, (330,), seed=11)[0]
    _, problems = reference.check_generator(params, cfg, seq)
    assert any("state_bf16_residual_min" in p for p in problems), problems


# ----------------------------------------------------------------- refusals


def _sample(cfg):
    prompts = _sequences(cfg, (12, 9), seed=1)
    return SequenceSample(
        keys={"packed_prompts"}, ids=["a", "b"],
        seqlens={"packed_prompts": [[len(p)] for p in prompts]},
        data={"packed_prompts": np.concatenate(prompts)})


@pytest.mark.parametrize("how", ["inflight", "speculation"])
def test_the_serving_plane_is_refused_by_name(cfg, params, how):
    eng = _engine(cfg, params)
    g = GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True)
    with pytest.raises(tfm.HybridLayoutError, match="static decode program"):
        if how == "inflight":
            eng.generate(_sample(cfg), MicroBatchSpec(), g, inflight=True)
        else:
            eng.generate(
                _sample(cfg), MicroBatchSpec(),
                dataclasses.replace(g, spec_decode_k=2))
    refusal = tfm.plan_refusal(cfg, serving=True)
    assert "selection" in str(refusal) and "Lightning" in str(refusal)


@pytest.mark.parametrize("mode", ["m2", "p2", "s2"])
def test_layouts_the_plan_cannot_run_are_refused_by_name(cfg, mode):
    pc = ParallelConfig.from_str(mode)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    with pytest.raises(tfm.HybridLayoutError, match="minicpm_sala"):
        sharding.attn_dispatch(mesh, cfg)
    sharding.attn_dispatch(mesh, tiny_config())  # every other model: fine


@pytest.mark.parametrize("change,match", [
    (dict(window_pattern="BLLF"), "stand beside each other alone"),
    (dict(sparse_kernel_size=24), "two strides"),
    (dict(sparse_topk=2), "forced blocks within"),
    (dict(lightning_head_dim=32), "one rotary table"),
])
def test_what_the_plan_cannot_be_is_refused_by_name(cfg, change, match):
    with pytest.raises(NotImplementedError, match=match):
        dataclasses.replace(cfg, **change)


# ------------------------------------------------------------------- counts


def test_flops_count_the_selected_keys(cfg):
    h, d = cfg.hidden_dim, cfg.head_dim
    attn = 2 * h * cfg.q_dim + 2 * h * cfg.kv_dim + cfg.q_dim * h
    light = 5 * h * cfg.lightning_dim
    mlp = 3 * h * cfg.intermediate_dim
    assert peaks_sala.matmul_params(cfg) == (
        attn + 3 * light + 4 * mlp + h * cfg.vocab_size)
    assert monitor.matmul_params(cfg) == peaks_sala.matmul_params(cfg) + (
        3 * 2 * cfg.lightning_dim * cfg.lightning_head_dim)
    # A sequence under dense_len counts every causal key, a long one its
    # chosen blocks: 6 x 16 = 96 keys a token once it has them.
    assert peaks_sala.selected_keys(cfg, 99, 100) == 100
    assert peaks_sala.selected_keys(cfg, 299, 300) == 96
    assert peaks_sala.visible_kernels(cfg, 299, 300) == (299 - 7) // 4 + 1
    long, short = (peaks_sala.flops_forward(cfg, [n]) for n in (300, 100))
    dense = dataclasses.replace(cfg, sparse_dense_len=10**6)
    assert long < peaks_sala.flops_forward(dense, [300])
    assert short == peaks_sala.flops_forward(dense, [100])
    # The program's own estimate caps the keys the same way.
    hd = cfg.n_q_heads * cfg.head_dim
    assert monitor.flops_forward(cfg, 3000, 3000.0**2) == pytest.approx(
        2.0 * monitor.matmul_params(cfg) * 3000
        + 4.0 * hd * 3000 * 96 + 2.0 * hd * 3000.0**2 / 4)


# ---------------------------------------------- the cell's window, rehearsed

# `sala-docrl8-longctx` rehearsed on the CPU, one process for both cases: to the
# end of its window (`benchmark/tests/fixed_work_cases.py`) and held to
# `correct`.  Why they are collected here: `tests/benchmark_windows.py`.
from tests.benchmark_windows import correct_case, window_case  # noqa: E402

test_the_window_closes_on_the_cells_count_or_on_the_clock = window_case(
    __name__)
test_cpu_rehearsal_of_the_cell_is_correct = correct_case(__name__)
