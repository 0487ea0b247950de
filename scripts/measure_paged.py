"""Serving-plane counts on the CPU test cluster (8 virtual devices, the
tests' fake-cluster configuration, tests/conftest.py).  Counts only — a
CPU run gives no time or rate of the device.

  - sweep: group-size sweep (n in {1,4,8}) of one long prompt at a
    FIXED kv_pool_pages, kv_share_prefix on vs off: with copy-on-write
    prefix sharing the group's prompt pages are mapped once, so the
    same pool holds >= 3x as many concurrently live rows
    (peak_live_slots) at group size 8.
  - ragged: packed-stream lane accounting for the ragged serving
    chunk.  Three legs (plain K=0, spec K=2, int8) run the same
    workload; each reports the lane counters (lanes_dispatched /
    lanes_live / lanes_slack / dead_live_lanes) plus the lane count a
    masked [n_slots, W] slab would have paid.  The ragged_compare
    invariants: dead-lane compute is exactly 0, one compiled program,
    zero standalone prefills, the packed stream is strictly narrower
    than the slab, and greedy spec output is token-identical to greedy
    plain (the argmax chain does not care how tokens were grouped into
    drafts).

Runs with AREAL_PAGING_CHECK=1 so every allocator transition is
invariant-checked while the numbers are gathered.

Usage (from the repo root; takes a few minutes):
    python scripts/measure_paged.py [--mode all] [--max-new 192]
                                    [--out FILE]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Measure under the paranoid allocator: every reserve/share/release is
# invariant-checked, so a perf number can never come from a refcount bug.
os.environ.setdefault("AREAL_PAGING_CHECK", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

EOS = 7
PROMPT_LENS = (37, 120, 64, 230, 91, 333, 180, 45, 260, 150, 77, 410)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-new", type=int, default=192)
    ap.add_argument("--page-size", type=int, default=128)
    ap.add_argument("--mode", default="all",
                    choices=("sweep", "ragged", "all"))
    ap.add_argument("--out", default=None,
                    help="also append JSON lines to this file")
    args = ap.parse_args()

    import jax

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    assert len(jax.devices()) == 8, (
        f"expected the 8-virtual-device CPU cluster, got "
        f"{len(jax.devices())} devices"
    )
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(11))
    mesh = make_mesh(ParallelConfig.from_str("d8"), jax.devices())

    rng = np.random.default_rng(42)
    data = np.concatenate(
        [rng.integers(8, cfg.vocab_size, size=l) for l in PROMPT_LENS]
    ).astype(np.int32)
    sample = SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(len(PROMPT_LENS))],
        seqlens={"packed_prompts": [[l] for l in PROMPT_LENS]},
        data={"packed_prompts": data},
    )
    lines = []

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        lines.append(line)

    ok = True

    def run_ragged():
        """Ragged packed-stream lane accounting: plain / spec / int8
        legs through the serving chunk, plus the invariant
        leg the regression gate pins (dead-lane compute exactly 0)."""
        rnew = min(args.max_new, 192)

        def ragged_leg(name, spec_k, kv_dtype):
            gg = GenerationHyperparameters(
                n=1, max_new_tokens=rnew, min_new_tokens=rnew,
                greedy=True, spec_decode_k=spec_k,
            )
            eng = GeneratorEngine(
                cfg, params, mesh, eos_token_id=EOS, max_decode_batch=8,
                kv_page_size=args.page_size,
                kv_cache_dtype=kv_dtype,
            )
            t0 = time.time()
            out = eng.generate(sample, MicroBatchSpec(), gg, inflight=True)
            dt = time.time() - t0
            gen_tokens = int(
                sum(t for r in out.seqlens["packed_input_ids"] for t in r)
            ) - sum(PROMPT_LENS)
            # The lane count a masked [n_slots, W] slab would pay per
            # inner step, reconstructed the way the engine sizes its
            # session.
            n_slots = min(
                max(eng.batch_shard, eng.max_decode_batch),
                len(PROMPT_LENS),
            )
            while n_slots % eng.batch_shard:
                n_slots += 1
            slab = n_slots * max(eng.prefill_chunk_tokens, spec_k + 1)
            emit({
                "leg": f"ragged_{name}",
                "prompts": len(PROMPT_LENS),
                "max_new_tokens": rnew,
                "spec_decode_k": spec_k,
                "kv_cache_dtype": kv_dtype,
                "gen_tokens": gen_tokens,
                "wall_seconds": round(dt, 2),
                "gen_tokens_per_sec": round(gen_tokens / dt, 1),
                "decode_compiles": eng.decode_compiles,
                "prefill_dispatches": eng.prefill_dispatches,
                "lane_budget": eng.serving_lane_budget,
                "masked_slab_lanes": slab,
                "lanes_dispatched": eng.lanes_dispatched,
                "lanes_live": eng.lanes_live,
                "lanes_slack": eng.lanes_slack,
                "dead_live_lanes": eng.dead_live_lanes,
                "lane_occupancy": round(
                    eng.lanes_live / max(1, eng.lanes_dispatched), 4
                ),
            })
            return out, eng, slab

        out_p, eng_p, slab_p = ragged_leg("plain", 0, "auto")
        out_s, eng_s, slab_s = ragged_leg("spec", 2, "auto")
        out_8, eng_8, slab_8 = ragged_leg("int8", 0, "int8")
        legs = ((eng_p, slab_p), (eng_s, slab_s), (eng_8, slab_8))
        toks_equal = bool(
            np.array_equal(
                np.asarray(out_p.data["packed_input_ids"]),
                np.asarray(out_s.data["packed_input_ids"]),
            )
        )
        emit({
            "leg": "ragged_compare",
            "greedy_spec_tokens_identical": toks_equal,
            "dead_lane_compute_zero": all(
                e.dead_live_lanes == 0 for e, _ in legs
            ),
            "decode_compiles_once": all(
                e.decode_compiles == 1 for e, _ in legs
            ),
            "zero_standalone_prefills": all(
                e.prefill_dispatches == 0 for e, _ in legs
            ),
            "lane_partition_holds": all(
                e.lanes_live + e.lanes_slack == e.lanes_dispatched
                for e, _ in legs
            ),
            "packed_narrower_than_slab": all(
                e.serving_lane_budget < s for e, s in legs
            ),
        })
        return (
            toks_equal
            and all(e.dead_live_lanes == 0 for e, _ in legs)
            and all(e.decode_compiles == 1 for e, _ in legs)
            and all(e.prefill_dispatches == 0 for e, _ in legs)
            and all(
                e.lanes_live + e.lanes_slack == e.lanes_dispatched
                for e, _ in legs
            )
            and all(e.serving_lane_budget < s for e, s in legs)
        )

    def run_sweep():
        """Group-size sweep at a FIXED pool: prefix sharing multiplies
        how many rows the same pages keep concurrently live."""
        ps, plen, mnew, pool = 64, 385, 16, 14
        toks = rng.integers(8, cfg.vocab_size, size=plen).astype(np.int32)
        peak = {}
        for n in (1, 4, 8):
            for share in (False, True):
                s1 = SequenceSample(
                    keys={"packed_prompts"},
                    ids=["p0"],
                    seqlens={"packed_prompts": [[plen]]},
                    data={"packed_prompts": toks},
                )
                eng = GeneratorEngine(
                    cfg, params, mesh, eos_token_id=EOS,
                    max_decode_batch=8, kv_page_size=ps,
                    kv_pool_pages=pool, prefill_chunk_tokens=8,
                    kv_share_prefix=share,
                )
                gg = GenerationHyperparameters(
                    n=n, max_new_tokens=mnew, min_new_tokens=mnew,
                    greedy=True,
                )
                t0 = time.time()
                out = eng.generate(s1, MicroBatchSpec(), gg, inflight=True)
                dt = time.time() - t0
                assert out is not None
                st = eng.last_pool_stats
                peak[(n, share)] = int(st.get("peak_live_slots", 0))
                emit({
                    "leg": "sweep",
                    "group_n": n,
                    "kv_share_prefix": share,
                    "kv_pool_pages": pool,
                    "page_size": ps,
                    "prompt_len": plen,
                    "max_new_tokens": mnew,
                    "wall_seconds": round(dt, 2),
                    "decode_compiles": eng.decode_compiles,
                    "peak_live_slots": st.get("peak_live_slots"),
                    "shared_mappings": st.get("shared_mappings"),
                    "prefix_hits": st.get("prefix_hits"),
                    "cow_copies": st.get("cow_copies"),
                    "peak_pages_used": st.get("peak_pages_used"),
                })
        ratio = peak[(8, True)] / max(1, peak[(8, False)])
        emit({
            "leg": "sweep_compare",
            "peak_live_no_share_n8": peak[(8, False)],
            "peak_live_share_n8": peak[(8, True)],
            "capacity_multiplier_n8": round(ratio, 2),
            "capacity_3x_or_better": ratio >= 3.0,
        })
        return ratio >= 3.0

    if args.mode in ("sweep", "all"):
        ok = run_sweep() and ok
    if args.mode in ("ragged", "all"):
        ok = run_ragged() and ok

    if args.out:
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
