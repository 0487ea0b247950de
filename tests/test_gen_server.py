"""Decoupled generation service (reference: backend/sglang.py — HTTP
serving with per-request logprobs + update_weights_from_disk):
server/client roundtrip, cross-request batching, weight hot-swap, the
remote_generator backend, and token auth."""

import urllib.error

import jax
import numpy as np
import pytest

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
    LLMAPIClient,
)
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.engines.generator import GeneratorEngine
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import tiny_config
from areal_tpu.system.gen_server import GenerationServer, RemoteGeneratorEngine

EOS = 7


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return tfm.init_params(cfg, jax.random.PRNGKey(11))


@pytest.fixture(scope="module")
def engine(cfg, params):
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    return GeneratorEngine(cfg, params, mesh, eos_token_id=EOS)


@pytest.fixture()
def server(engine):
    srv = GenerationServer(engine, max_wait_ms=2.0)
    yield srv
    srv.close()


def _prompt_sample(rng, cfg, lens):
    data = np.concatenate(
        [rng.integers(8, cfg.vocab_size, size=l) for l in lens]
    ).astype(np.int32)
    return SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(lens))],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={"packed_prompts": data},
    )


def test_generate_roundtrip_greedy_parity(server, engine, cfg):
    rng = np.random.default_rng(0)
    sample = _prompt_sample(rng, cfg, lens=(6, 9))
    g = GenerationHyperparameters(n=1, max_new_tokens=6, greedy=True)

    client = LLMAPIClient(server.url)
    assert client.health()["status"] == "ok"
    prompts = np.asarray(sample.data["packed_prompts"])
    bounds = sample.cu_seqlens("packed_prompts")
    outs = client.generate_batch(
        [
            APIGenerateInput(
                qid=sample.ids[i],
                prompt_ids=[int(t) for t in prompts[bounds[i]:bounds[i+1]]],
                gconfig=g,
            )
            for i in range(sample.bs)
        ]
    )

    ref = engine.generate(sample, MicroBatchSpec(), g)
    per_id = {s.ids[0]: s for s in ref.unpack()}
    for o in outs:
        want = np.asarray(per_id[o.qid].data["packed_input_ids"])
        got = np.asarray(o.prompt_ids + o.output_ids[0], np.int32)
        np.testing.assert_array_equal(got, want)
        # Logprobs align with the generated span.
        assert len(o.output_logprobs[0]) == len(o.output_ids[0])


def test_update_weights_changes_output_and_version(tmp_path, server, cfg):
    from areal_tpu.models.hf import registry as hf

    client = LLMAPIClient(server.url)
    g = GenerationHyperparameters(n=1, max_new_tokens=8, greedy=True)
    inp = APIGenerateInput(
        qid="q", prompt_ids=list(range(10, 20)), gconfig=g
    )
    before = client.generate(inp)

    params2 = tfm.init_params(cfg, jax.random.PRNGKey(99))
    hf.save_hf_checkpoint(str(tmp_path), cfg, params2, model_type="qwen2")
    v = client.update_weights_from_disk(str(tmp_path))
    assert v == server.version > 0

    after = client.generate(inp)
    assert after.version == v
    assert before.output_ids != after.output_ids  # new weights, new argmax


def test_remote_generator_engine_parity(server, engine, cfg):
    """The remote_generator backend returns the SAME rollout sample as the
    local engine (greedy)."""
    rng = np.random.default_rng(3)
    sample = _prompt_sample(rng, cfg, lens=(5, 8, 11))
    g = GenerationHyperparameters(n=2, max_new_tokens=5, greedy=True)

    remote = RemoteGeneratorEngine(cfg, server.url)
    got = remote.generate(sample, MicroBatchSpec(), g)
    want = engine.generate(sample, MicroBatchSpec(), g)
    assert got.seqlens["packed_input_ids"] == want.seqlens["packed_input_ids"]
    np.testing.assert_array_equal(
        np.asarray(got.data["packed_input_ids"]),
        np.asarray(want.data["packed_input_ids"]),
    )
    np.testing.assert_allclose(
        np.asarray(got.data["packed_logprobs"]),
        np.asarray(want.data["packed_logprobs"]),
        rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_array_equal(
        np.asarray(got.data["seq_no_eos_mask"]),
        np.asarray(want.data["seq_no_eos_mask"]),
    )


def test_token_auth(engine, monkeypatch):
    srv = GenerationServer(engine, token="sekrit")
    try:
        bad = LLMAPIClient(srv.url, token="wrong")
        with pytest.raises(RuntimeError, match="bad token"):
            bad.generate(
                APIGenerateInput(
                    qid="q", prompt_ids=[10, 11, 12],
                    gconfig=GenerationHyperparameters(
                        n=1, max_new_tokens=2, greedy=True
                    ),
                )
            )
        ok = LLMAPIClient(srv.url, token="sekrit")
        out = ok.generate(
            APIGenerateInput(
                qid="q", prompt_ids=[10, 11, 12],
                gconfig=GenerationHyperparameters(
                    n=1, max_new_tokens=2, greedy=True
                ),
            )
        )
        assert len(out.output_ids[0]) >= 1
    finally:
        srv.close()


def test_ppo_e2e_with_remote_gen_server(tmp_path):
    """Full decoupled trial: actor_gen is a weightless client of a running
    GenerationServer; rollouts come over HTTP, and the post-train weight
    sync ships a checkpoint to the server (update_weights_from_disk)."""
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import OptimizerConfig
    from areal_tpu.experiments.common import (
        PPOMathConfig,
        build_ppo_math,
        run_experiment,
    )
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    tok = fixtures.make_tokenizer()
    cfg = tiny_config()
    # The server must start from the same weights the actor worker will
    # build (seed=1 below) so step-1 generation is on-policy.
    srv_params = tfm.init_params(cfg, jax.random.PRNGKey(1))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    srv_engine = GeneratorEngine(
        cfg, srv_params, mesh, eos_token_id=tok.eos_token_id
    )
    server = GenerationServer(srv_engine, max_wait_ms=2.0)
    try:
        rows = fixtures.build_math_rows(8, seed=4)
        pcfg = PPOMathConfig(
            actor=ModelAbstraction("random", {"config": cfg}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface_args={
                "id2info": {r["query_id"]: r for r in rows}
            },
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
            ppo_kwargs={"n_minibatches": 2},
            optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
            gen_server_url=server.url,
            batch_size=4,
            seed=1,
            ctrl=ExperimentSaveEvalControl(benchmark_steps=2),
            fileroot=str(tmp_path),
        )
        _, stats = run_experiment(build_ppo_math(pcfg, tok), tokenizer=tok)
        assert len(stats) == 2
        # On-policy step 1: generation served remotely from identical
        # weights -> importance ratio ~ 1.
        assert abs(stats[0]["actor_train/importance_weight"] - 1.0) < 5e-2
        # The post-train sync bumped the server's weight version.
        assert server.version >= 1
    finally:
        server.close()


def test_multi_server_dp_ranks(cfg):
    """Multiple serving ranks (reference: one SGLang server per DP rank):
    requests round-robin across servers, weight updates broadcast to all,
    and greedy outputs match the single-server path."""
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    fresh = tfm.init_params(cfg, jax.random.PRNGKey(21))
    eng1 = GeneratorEngine(cfg, fresh, mesh, eos_token_id=EOS)
    eng2 = GeneratorEngine(cfg, fresh, mesh, eos_token_id=EOS)
    s1 = GenerationServer(eng1, max_wait_ms=2.0)
    s2 = GenerationServer(eng2, max_wait_ms=2.0)
    try:
        rng = np.random.default_rng(9)
        sample = _prompt_sample(rng, cfg, lens=(5, 8, 11, 6))
        g = GenerationHyperparameters(n=1, max_new_tokens=5, greedy=True)
        multi = RemoteGeneratorEngine(cfg, [s1.url, s2.url])
        single = RemoteGeneratorEngine(cfg, s1.url)
        got = multi.generate(sample, MicroBatchSpec(), g)
        want = single.generate(sample, MicroBatchSpec(), g)
        np.testing.assert_array_equal(
            np.asarray(got.data["packed_input_ids"]),
            np.asarray(want.data["packed_input_ids"]),
        )
        # set_params broadcasts the checkpoint to every serving rank.
        multi.set_params(tfm.init_params(cfg, jax.random.PRNGKey(123)))
        assert s1.version == 1 and s2.version == 1
    finally:
        s1.close()
        s2.close()


class TestZMQTransport:
    """The pipelined ZMQ plane shares the HTTP path's collector: parity,
    pipelining, auth, and weight updates over one DEALER connection."""

    @pytest.fixture()
    def zserver(self, engine):
        srv = GenerationServer(engine, max_wait_ms=2.0, zmq_port=0)
        yield srv
        srv.close()

    def test_zmq_matches_http_greedy(self, zserver, cfg):
        from areal_tpu.system.gen_server import ZMQGenClient

        rng = np.random.default_rng(1)
        sample = _prompt_sample(rng, cfg, lens=(6, 9, 5))
        g = GenerationHyperparameters(n=1, max_new_tokens=6, greedy=True)
        prompts = np.asarray(sample.data["packed_prompts"])
        bounds = sample.cu_seqlens("packed_prompts")
        inps = [
            APIGenerateInput(
                qid=sample.ids[i],
                prompt_ids=[int(t) for t in prompts[bounds[i]:bounds[i+1]]],
                gconfig=g,
            )
            for i in range(sample.bs)
        ]
        zc = ZMQGenClient(zserver.zmq_url)
        assert zc.health()["status"] == "ok"
        # All requests pipeline over ONE connection; replies correlate.
        z_outs = {o.qid: o for o in zc.generate_batch(inps)}
        h_outs = {
            o.qid: o for o in LLMAPIClient(zserver.url).generate_batch(inps)
        }
        for qid in z_outs:
            np.testing.assert_array_equal(
                np.asarray(z_outs[qid].output_ids[0]),
                np.asarray(h_outs[qid].output_ids[0]),
            )
            np.testing.assert_allclose(
                np.asarray(z_outs[qid].output_logprobs[0]),
                np.asarray(h_outs[qid].output_logprobs[0]),
                rtol=1e-5, atol=1e-6,
            )

    def test_zmq_update_weights(self, zserver, cfg, tmp_path):
        from areal_tpu.models.hf import registry as hf
        from areal_tpu.system.gen_server import ZMQGenClient

        new_params = tfm.init_params(cfg, jax.random.PRNGKey(123))
        ckpt = tmp_path / "ck"
        hf.save_hf_checkpoint(str(ckpt), cfg, new_params, model_type="qwen2")
        zc = ZMQGenClient(zserver.zmq_url)
        v0 = zc.health()["version"]
        assert zc.update_weights_from_disk(str(ckpt)) == v0 + 1
        assert zc.health()["version"] == v0 + 1

    def test_zmq_bad_token_rejected(self, engine):
        from areal_tpu.system.gen_server import ZMQGenClient

        srv = GenerationServer(
            engine, max_wait_ms=2.0, zmq_port=0, token="sekret"
        )
        try:
            zc = ZMQGenClient(srv.zmq_url, token="wrong", timeout_s=10.0)
            with pytest.raises(RuntimeError, match="bad token"):
                zc.health()
            ok = ZMQGenClient(srv.zmq_url, token="sekret")
            assert ok.health()["status"] == "ok"
        finally:
            srv.close()

    def test_remote_engine_routes_zmq_urls(self, zserver, cfg):
        from areal_tpu.system.gen_server import (
            RemoteGeneratorEngine,
            ZMQGenClient,
        )

        eng = RemoteGeneratorEngine(cfg, zserver.zmq_url)
        assert isinstance(eng.clients[0], ZMQGenClient)
        rng = np.random.default_rng(2)
        sample = _prompt_sample(rng, cfg, lens=(5, 7))
        g = GenerationHyperparameters(n=2, max_new_tokens=4, greedy=True)
        out = eng.generate(sample, MicroBatchSpec(), g)
        assert all(len(x) == 2 for x in out.seqlens["packed_input_ids"])

    def test_zmq_malformed_request_fails_fast(self, zserver):
        """A malformed field must come back as a rid-correlated error
        immediately — not leave the client blocked until its timeout."""
        import time as _time

        from areal_tpu.system.gen_server import ZMQGenClient

        zc = ZMQGenClient(zserver.zmq_url, timeout_s=30.0)
        t0 = _time.monotonic()
        with pytest.raises(RuntimeError, match="bad request"):
            zc._call_many([
                {"cmd": "generate", "qid": "x", "prompt_ids": ["nan"],
                 "gconfig": {}},
            ])
        assert _time.monotonic() - t0 < 5.0

    def test_concurrent_callers_share_one_connection(self, zserver, cfg):
        """Multiple threads generating through ONE client must pipeline
        (per-rid futures), each getting ITS OWN prompt's continuation —
        the serialize-under-lock design this replaces would still pass
        functionally, so also check wall overlap via the server's
        cross-request batching: all replies arrive."""
        import threading as _t

        from areal_tpu.system.gen_server import ZMQGenClient

        zc = ZMQGenClient(zserver.zmq_url, timeout_s=120.0)
        g = GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True)
        results = {}

        def run(i):
            o = zc.generate(APIGenerateInput(
                qid=f"c{i}", prompt_ids=[9, 10, 11 + i], gconfig=g,
            ))
            results[i] = o

        ts = [_t.Thread(target=run, args=(i,)) for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert len(results) == 6
        for i, o in results.items():
            assert o.prompt_ids == [9, 10, 11 + i]
            assert len(o.output_ids[0]) > 0


class TestEpisodeServing:
    """Agent-serving episode surface: start/extend/release over HTTP and
    ZMQ, observation-only prefills on the parked slot, and the typed
    SlotGoneError a continuation on a reclaimed slot gets."""

    @pytest.fixture(scope="class")
    def ep_env(self, cfg):
        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        params = tfm.init_params(cfg, jax.random.PRNGKey(13))
        # EOS outside the vocab so greedy decode never terminates early;
        # turns end on the probe-derived stop sequence instead.
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
            kv_page_size=8, prefill_chunk_tokens=4,
            max_decode_batch=2,
        )
        srv = GenerationServer(eng, max_wait_ms=2.0, zmq_port=0)
        client = LLMAPIClient(srv.url)
        rng = np.random.default_rng(7)
        prompt = [int(x) for x in rng.integers(8, cfg.vocab_size, size=10)]
        # Probe the greedy continuation, then pick a stop sequence the
        # model is guaranteed to emit (same trick as the --agents leg).
        probe = client.generate(APIGenerateInput(
            qid="probe", prompt_ids=prompt,
            gconfig=GenerationHyperparameters(
                n=1, max_new_tokens=8, greedy=True
            ),
        ))
        toks = [int(t) for t in probe.output_ids[0]]
        g = GenerationHyperparameters(
            n=1, max_new_tokens=8, greedy=True, stop=(tuple(toks[2:4]),),
        )
        yield srv, client, prompt, toks, g
        srv.close()

    @staticmethod
    def _metric(name):
        from areal_tpu.base import metrics

        total = 0.0
        for line in metrics.default_registry().expose().splitlines():
            if line.startswith(f"{name} ") or line.startswith(f"{name}{{"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    def test_http_episode_lifecycle(self, ep_env):
        _, client, prompt, toks, g = ep_env
        t1 = client.episode_start("ep-h", prompt, g, token_budget=64)
        assert t1["stop_reason"] == "stop"
        # Stop tokens stay IN the turn: the parser needs the full call.
        assert t1["tokens"] == toks[:4]
        obs = [int(x) for x in np.asarray(prompt[:3]) + 1]
        t2 = client.episode_extend("ep-h", obs)
        # The tentpole property: turn 2 prefilled ONLY the observation —
        # the transcript stayed hot on the slot's KV pages.
        assert t2["prefill_tokens"] == len(obs)
        assert t2["transcript_len"] == (
            len(prompt) + len(t1["tokens"]) + len(obs) + len(t2["tokens"])
        )
        assert client.episode_release("ep-h")["released"] is True

    def test_http_continuation_on_reclaimed_slot_is_typed(self, ep_env):
        from areal_tpu.api.model_api import SlotGoneError

        _, client, prompt, _, g = ep_env
        client.episode_start("ep-gone", prompt, g, token_budget=64)
        client.episode_release("ep-gone")
        lost0 = self._metric("areal_gen_episode_slot_lost_total")
        with pytest.raises(SlotGoneError) as ei:
            client.episode_extend("ep-gone", [9, 10])
        assert ei.value.episode_id == "ep-gone"
        assert ei.value.reason
        assert self._metric(
            "areal_gen_episode_slot_lost_total"
        ) == lost0 + 1

    def test_zmq_episode_matches_http(self, ep_env):
        from areal_tpu.api.model_api import SlotGoneError
        from areal_tpu.system.gen_server import ZMQGenClient

        srv, _, prompt, toks, g = ep_env
        zc = ZMQGenClient(srv.zmq_url)
        try:
            t1 = zc.episode_start("ep-z", prompt, g, token_budget=64)
            assert t1["tokens"] == toks[:4]
            zc.episode_release("ep-z")
            with pytest.raises(SlotGoneError):
                zc.episode_extend("ep-z", [9, 10])
        finally:
            zc.close()

    def test_generate_honors_stop_sequences(self, ep_env):
        _, client, prompt, toks, g = ep_env
        out = client.generate(APIGenerateInput(
            qid="stop-q", prompt_ids=prompt, gconfig=g,
        ))
        assert out.output_ids[0] == toks[:4]


class TestAsyncServing:
    """Async-RL serving surface: enriched /health load signals,
    pause/resume at a chunk boundary, and the interruptible in-memory
    weight push that resumes in-flight decodes on their KV pages."""

    def test_health_reports_load_signals(self, server):
        h = LLMAPIClient(server.url).health()
        assert h["status"] == "ok"
        for key in ("version", "queue_depth", "live_slots",
                    "kv_utilization", "capacity", "paused"):
            assert key in h, key
        assert h["paused"] is False
        assert h["capacity"] >= 1

    def test_pause_parks_generation_until_resume(self, server):
        import threading as _t

        client = LLMAPIClient(server.url)
        client.pause()
        assert client.health()["paused"] is True
        g = GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True)
        box = {}

        def run():
            box["out"] = client.generate(APIGenerateInput(
                qid="p", prompt_ids=[10, 11, 12], gconfig=g,
            ))

        th = _t.Thread(target=run)
        th.start()
        # Parked: the request must NOT complete while paused.
        th.join(timeout=0.3)
        assert th.is_alive() and "out" not in box
        client.resume()
        th.join(timeout=60)
        assert not th.is_alive()
        assert len(box["out"].output_ids[0]) >= 1
        assert client.health()["paused"] is False

    def test_update_weights_inmem_bumps_version(self, cfg, params):
        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        eng = GeneratorEngine(cfg, params, mesh, eos_token_id=EOS)
        srv = GenerationServer(eng, max_wait_ms=2.0)
        try:
            client = LLMAPIClient(srv.url)
            g = GenerationHyperparameters(n=1, max_new_tokens=8, greedy=True)
            inp = APIGenerateInput(
                qid="q", prompt_ids=list(range(10, 20)), gconfig=g
            )
            before = client.generate(inp)
            assert before.version == before.version_start == 0

            v = srv.update_weights_inmem(
                tfm.init_params(cfg, jax.random.PRNGKey(99))
            )
            assert v == srv.version == 1
            after = client.generate(inp)
            # A request submitted after the push starts AND ends on v1.
            assert after.version == after.version_start == 1
            assert before.output_ids != after.output_ids
            assert client.health()["paused"] is False
        finally:
            srv.close()

    def test_remote_engine_inmem_sync_pause_wraps_push(self, cfg, params):
        """inmem_sync=True: set_params pauses every serving rank, pushes
        the checkpoint, and resumes — the server ends live and versioned."""
        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        eng = GeneratorEngine(cfg, params, mesh, eos_token_id=EOS)
        srv = GenerationServer(eng, max_wait_ms=2.0)
        try:
            remote = RemoteGeneratorEngine(cfg, srv.url, inmem_sync=True)
            remote.set_params(tfm.init_params(cfg, jax.random.PRNGKey(5)))
            assert srv.version == 1
            h = LLMAPIClient(srv.url).health()
            assert h["paused"] is False and h["version"] == 1
        finally:
            srv.close()

    def test_inmem_push_interrupts_and_resumes_inflight(self, cfg):
        """The tentpole behavior: a weight push lands MID-DECODE, the
        in-flight requests halt at a chunk boundary, the swap happens,
        and they resume on their existing KV pages — finishing under the
        new version while keeping their original head version stamp."""
        import threading as _t

        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        params = tfm.init_params(cfg, jax.random.PRNGKey(11))
        # Force the interruptible inflight path: more concurrent requests
        # than max_decode_batch (static/dense paths drain instead).
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=EOS, max_decode_batch=2
        )
        # The batcher takes what arrives within `max_wait_ms` of the first
        # request.  At 20 ms a loaded host split the four posts below into
        # batches of two, which fit `max_decode_batch` and ran the static
        # path: no chunk was ever drained and "decode never started" (one
        # of PR 62's whole runs).  A second is all four, loaded or not.
        srv = GenerationServer(eng, max_wait_ms=1000.0)
        try:
            client = LLMAPIClient(srv.url)
            g = GenerationHyperparameters(
                n=1, max_new_tokens=96, greedy=True
            )
            inps = [
                APIGenerateInput(
                    qid=f"q{i}", prompt_ids=[10 + i, 11, 12, 13],
                    gconfig=g,
                )
                for i in range(4)
            ]
            box = {}

            def run():
                box["outs"] = client.generate_batch(inps)

            # The push has to land MID-decode.  Polling `live_slots` and
            # then pushing raced the three chunks of a toy decode (under
            # six test workers the decode could finish first: no request
            # spanned two versions).  Instead the serving loop holds at the
            # end of its first chunk until the interrupt the push sets has
            # arrived: a wait on the engine's own event, not a sleep.
            first_chunk = _t.Event()
            drain = eng._drain_chunk_outputs

            def drain_then_hold(*a, **k):
                out = drain(*a, **k)
                if not first_chunk.is_set():
                    first_chunk.set()
                    assert eng._interrupt_evt.wait(timeout=60)
                return out

            eng._drain_chunk_outputs = drain_then_hold
            th = _t.Thread(target=run)
            th.start()
            assert first_chunk.wait(timeout=60), "decode never started"
            assert client.health()["live_slots"] > 0
            v = srv.update_weights_inmem(
                tfm.init_params(cfg, jax.random.PRNGKey(99))
            )
            assert v == 1
            th.join(timeout=120)
            assert not th.is_alive()
            outs = box["outs"]
            assert len(outs) == 4
            # Interrupted requests: head version 0, finished under v1.
            spanned = [
                o for o in outs
                if o.version_start == 0 and o.version == 1
            ]
            assert spanned, [
                (o.qid, o.version_start, o.version) for o in outs
            ]
            # ...and they were resumed (tail-replay on existing pages),
            # not restarted from scratch.
            assert eng.resume_replays >= 1
            for o in outs:
                assert len(o.output_ids[0]) == len(o.output_logprobs[0])
                assert len(o.output_ids[0]) >= 1
        finally:
            srv.close()


class TestLineagePropagation:
    """Causal-lineage propagation over both transports: a trace_id
    minted at the (simulated) dispatcher must ride the HTTP header /
    ZMQ frame into the server and come back out of the merged shards
    as per-turn and per-request lineage stamps."""

    def test_http_episode_turns_carry_trace_id(self, tmp_path, cfg):
        from areal_tpu.base import tracer

        mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
        params = tfm.init_params(cfg, jax.random.PRNGKey(13))
        eng = GeneratorEngine(
            cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
            kv_page_size=8, prefill_chunk_tokens=4,
            max_decode_batch=2,
        )
        srv = GenerationServer(eng, max_wait_ms=2.0)
        try:
            tracer.configure(
                role="gen_server", rank=0, dir=str(tmp_path),
                enabled=True, force=True,
            )
            client = LLMAPIClient(srv.url)
            rng = np.random.default_rng(7)
            prompt = [
                int(x) for x in rng.integers(8, cfg.vocab_size, size=10)
            ]
            # Probe the greedy continuation for a guaranteed stop seq.
            probe = client.generate(APIGenerateInput(
                qid="probe", prompt_ids=prompt,
                gconfig=GenerationHyperparameters(
                    n=1, max_new_tokens=8, greedy=True
                ),
            ))
            toks = [int(t) for t in probe.output_ids[0]]
            g = GenerationHyperparameters(
                n=1, max_new_tokens=8, greedy=True,
                stop=(tuple(toks[2:4]),),
            )
            tid = tracer.new_trace_id()
            tracer.lineage("dispatch", tid, root=True, qid="ep-lin")
            # trace_id rides the X-Areal-Trace header on start; the
            # server's episode->trace store then resolves it for the
            # extend, which does NOT carry the header.
            client.episode_start(
                "ep-lin", prompt, g, token_budget=64, trace_id=tid
            )
            obs = [int(x) for x in np.asarray(prompt[:3]) + 1]
            client.episode_extend("ep-lin", obs)
            client.episode_release("ep-lin")
            tracer.flush()
            trace = tracer.merge_shards(str(tmp_path))
            assert tracer.validate_trace(trace) == []
            turns = [
                e for e in trace["traceEvents"]
                if e.get("ph") == "i" and e.get("cat") == "lineage"
                and e["args"].get("stage") == "turn"
            ]
            assert len(turns) >= 2  # start + extend both stamped
            assert all(e["args"]["trace_id"] == tid for e in turns)
            ops = {e["args"].get("op") for e in turns}
            assert {"start", "extend"} <= ops
        finally:
            tracer._reset_for_tests()
            srv.close()

    def test_zmq_generate_carries_trace_id(self, tmp_path, engine):
        from areal_tpu.base import tracer
        from areal_tpu.system.gen_server import ZMQGenClient

        srv = GenerationServer(engine, max_wait_ms=2.0, zmq_port=0)
        try:
            tracer.configure(
                role="gen_server", rank=0, dir=str(tmp_path),
                enabled=True, force=True,
            )
            tid = tracer.new_trace_id()
            tracer.lineage("dispatch", tid, root=True, qid="z-lin")
            zc = ZMQGenClient(srv.zmq_url)
            out = zc.generate(APIGenerateInput(
                qid="z-lin", prompt_ids=[9, 10, 11],
                gconfig=GenerationHyperparameters(
                    n=1, max_new_tokens=4, greedy=True
                ),
                trace_id=tid,
            ))
            assert out.output_ids[0]
            tracer.flush()
            trace = tracer.merge_shards(str(tmp_path))
            assert tracer.validate_trace(trace) == []
            stages = {
                e["args"]["stage"]
                for e in trace["traceEvents"]
                if e.get("ph") == "i" and e.get("cat") == "lineage"
                and e["args"].get("trace_id") == tid
            }
            # The same id the ZMQ frame carried in came out as the
            # server-side serving stamps.
            assert {"dispatch", "first_token", "generated"} <= stages
            req = next(
                e for e in trace["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "request:z-lin"
            )
            assert req["args"]["trace_id"] == tid
        finally:
            tracer._reset_for_tests()
            srv.close()
