#!/usr/bin/env python
"""Trace smoke check: run a tiny traced CPU generate, merge the shards,
and fail loudly when the trace is empty or schema-invalid.

    python scripts/check_trace.py [--dir /tmp/trace_check] [--lineage]

Exercises the same wiring an AREAL_TRACE=1 trial uses — engine compute
spans, pool/slot gauges, shard flush, merge_shards, validate_trace —
then prints the stall-attribution report.  Exit 0 iff the trace is
valid and contains span + counter events.  CI-friendly: CPU-only,
tiny random model, a few seconds end to end.

``--lineage`` runs the causal-lineage leg instead: a 2-episode rollout
through a real HTTP generation server (trace ids minted at dispatch,
carried in the X-Areal-Trace header, stamped per turn / at grading /
at replay admission / at train consumption), then asserts every
trajectory joins into a complete dispatch -> trained timeline with
zero orphan trace ids, and prints ``trace_report --lineage``.
"""

import argparse
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Paranoid page allocator: validate every allocator transition.
os.environ.setdefault("AREAL_PAGING_CHECK", "1")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def check_lineage(trace_dir: str) -> int:
    """Causal-lineage leg: two multi-turn episodes dispatched through
    the rollout controller against a live HTTP generation server, every
    trajectory graded and consumed, and the merged shards must join
    each one into a complete dispatch -> trained timeline."""
    import asyncio

    import jax
    import numpy as np

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.apps import trace_report
    from areal_tpu.base import name_resolve, tracer
    from areal_tpu.base.name_resolve import MemoryNameResolveRepository
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.interfaces.reward_service import grade_item
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.episode import (
        ToolCall,
        ToolExecutor,
        make_episode_runner,
    )
    from areal_tpu.system.fleet import fleet_discovery
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.replay import ReplayBuffer
    from areal_tpu.system.rollout import RolloutController

    tracer.configure(
        role="check", rank=0, dir=trace_dir, enabled=True, force=True
    )
    name_resolve.set_default(MemoryNameResolveRepository())

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    # Unreachable EOS + even-token stop sequences: deterministic turn
    # boundaries for the random tiny model (same convention as the
    # agent-serving leg of check_async).
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
        kv_page_size=8, prefill_chunk_tokens=4,
        max_decode_batch=2,
    )
    srv = GenerationServer(engine, max_wait_ms=20.0, zmq_port=None)
    srv.announce("lineage_check", "t0", ttl=30.0)

    g = GenerationHyperparameters(
        n=1, max_new_tokens=16, greedy=True,
        stop=tuple((t,) for t in range(0, cfg.vocab_size, 2)),
    )

    def parse_calc(toks):
        a, b = (list(toks) * 2)[-2:]
        return ToolCall("calculator", f"{a % 9} + {b % 9}")

    def encode_obs(call, text, ok):
        return [8 + (ord(c) % 16) for c in text][:4] or [8]

    runner = make_episode_runner(
        ToolExecutor(timeout_s=10.0), parse_calc, encode_obs, g,
        max_turns=2,
    )
    replay = ReplayBuffer(capacity=4, max_head_offpolicyness=8)
    ctl = RolloutController(
        replay=replay,
        gconfig=g,
        discovery=fleet_discovery("lineage_check", "t0"),
        max_concurrency=2,
        autosize_inflight=False,
        episode_runner=runner,
    )
    rng = np.random.default_rng(3)
    prompts = [
        (f"ep{i}", [int(t) for t in rng.integers(8, cfg.vocab_size, size=8)])
        for i in range(2)
    ]
    try:
        stat = asyncio.run(ctl.run(prompts))
    finally:
        srv.close()
    if stat.accepted != len(prompts):
        print(
            f"FAIL: {stat.accepted}/{len(prompts)} episodes accepted "
            f"(failed={stat.failed} rejected={stat.rejected})"
        )
        return 1

    # Train-consume each trajectory, then grade it through the verifier
    # registry so the timeline carries a ``graded`` stamp too (in this
    # repo rewards are computed at train time, after consumption).
    trajs = []
    while True:
        try:
            trajs.extend(replay.get_batch(1, timeout=0))
        except TimeoutError:
            break
    for t in trajs:
        grade_item({
            "task": "judge",
            "text": "final answer: yes",
            "payload": {"reference": "yes"},
            "trace_id": t.trace_id,
        })

    tracer.flush()
    trace = tracer.merge_shards(
        trace_dir, out_path=os.path.join(trace_dir, "trace.json")
    )
    errors = tracer.validate_trace(trace)
    if errors:
        print("FAIL: lineage trace schema problems:")
        for e in errors:
            print(f"  - {e}")
        return 1

    summary = trace_report.lineage_summary(trace)
    rows = trace_report.lineage_rows(trace)
    rc = 0
    if summary["orphans"]:
        print(f"FAIL: orphan trace ids (no dispatch root): "
              f"{summary['orphans']}")
        rc = 1
    if summary["n"] != len(prompts):
        print(f"FAIL: expected {len(prompts)} lineage roots, "
              f"got {summary['n']}")
        rc = 1
    if summary["complete"] != len(trajs):
        print(
            f"FAIL: only {summary['complete']} of {len(trajs)} consumed "
            f"trajectories join dispatch -> trained"
        )
        rc = 1
    want = {"dispatch", "turn", "admitted", "trained", "graded"}
    for r in rows:
        missing = want - set(r["stages"])
        if missing:
            print(
                f"FAIL: {r['trace_id']} ({r['qid']}) timeline missing "
                f"stages {sorted(missing)}; has {sorted(r['stages'])}"
            )
            rc = 1
    if rc:
        return rc

    print(
        f"OK: {summary['complete']}/{summary['n']} trajectories join "
        f"dispatch -> trained, 0 orphans -> {trace_dir}/trace.json"
    )
    print()
    print(trace_report.format_lineage(trace))
    return 0


def main() -> int:
    p = argparse.ArgumentParser(prog="check_trace")
    p.add_argument(
        "--dir", default=None, help="trace dir (default: fresh tempdir)"
    )
    p.add_argument(
        "--lineage", action="store_true",
        help="run the causal-lineage join leg instead of the span smoke",
    )
    args = p.parse_args()
    trace_dir = args.dir or tempfile.mkdtemp(prefix="areal_tpu_trace_check_")
    if args.lineage:
        return check_lineage(trace_dir)

    import jax
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.apps import trace_report
    from areal_tpu.base import tracer
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    tracer.configure(
        role="check", rank=0, dir=trace_dir, enabled=True, force=True
    )

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    # Small decode pool so 4 requests take the inflight path (where the
    # kv_pool/gen_slots gauges are emitted).
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=7, max_decode_batch=2
    )
    rng = np.random.default_rng(0)
    lens = [5, 7, 6, 5]
    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(lens))],
        seqlens={"packed_prompts": [[l] for l in lens]},
        data={
            "packed_prompts": np.concatenate(
                [
                    rng.integers(8, cfg.vocab_size, size=l)
                    for l in lens
                ]
            ).astype(np.int32)
        },
    )
    with tracer.span("step", step=1):
        out = engine.generate(
            sample,
            MicroBatchSpec(),
            GenerationHyperparameters(n=1, max_new_tokens=4, greedy=True),
        )
    assert out.bs == len(lens)
    shard = tracer.flush()
    if not shard or not os.path.exists(shard):
        print("FAIL: tracer.flush() produced no shard file")
        return 1

    trace = tracer.merge_shards(
        trace_dir, out_path=os.path.join(trace_dir, "trace.json")
    )
    errors = tracer.validate_trace(trace)
    if errors:
        print("FAIL: trace schema problems:")
        for e in errors:
            print(f"  - {e}")
        return 1
    evs = trace["traceEvents"]
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    # The serving plane folds admission prefill into the decode chunk;
    # "serving_chunk" is the single compute span both phases share.
    missing = {"generate", "serving_chunk"} - spans
    if missing:
        print(f"FAIL: expected spans missing from trace: {sorted(missing)}")
        return 1
    if not {"kv_pool", "gen_slots"} <= counters:
        print(f"FAIL: expected counter tracks missing, got {sorted(counters)}")
        return 1

    print(
        f"OK: {len(evs)} events ({len(spans)} span names, "
        f"{len(counters)} counter tracks) -> {trace_dir}/trace.json"
    )
    print()
    print(trace_report.format_report(trace))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
