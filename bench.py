"""End-to-end RL-step throughput benchmark on one TPU chip (fails at
once where `jax.default_backend()` is not "tpu").

Runs full PPO iterations — group generation (n=4), reward assignment, GRPO
actor update, weight hot-swap into the generator — on one chip with the
1.5B-class qwen2 architecture (the flagship `entry()` config) and ≥1k new
tokens per response, and reports samples/sec/chip with an MFU and
per-stage (gen/train/sync) breakdown.

Baseline constant: AReaL's published 1.5B "boba" convergence (250 steps of
512 prompts × 16 responses in ~240 h on 8×H800, README.md:38-43) works out
to 250*512*16 / (240*3600*8) ≈ 0.30 samples/sec/chip end-to-end.  Honest
caveats, encoded in `baseline_note`: the reference decodes up to 27,648 new
tokens per sample where this bench caps at 1,024 (long tails dominate its
wall-clock), and one H800 ≈ 2× the bf16 peak of this v5e chip.  The
derivation becomes controlled when multi-chip 7B runs land.

Trainer memory: bf16 master weights + Adam moments (TrainEngine
master_dtype) — 1.5B fp32 optimizer state alone (18.6 GB) exceeds this
chip's 16 GB HBM; fp32 masters return on multi-chip meshes where ZeRO
shards them.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC_CHIP = 0.30


def main(size: str = "1.5b"):
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"[bench] needs a TPU: jax.default_backend() is "
            f"{jax.default_backend()!r} (a CPU timing is not a device metric)"
        )

    from areal_tpu.base import compilation_cache

    compilation_cache.enable()

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import (
        FinetuneSpec,
        GenerationHyperparameters,
        Model,
        OptimizerConfig,
    )
    from areal_tpu.base import monitor
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import qwen2_config

    n_prompts, group, prompt_len, max_new = (
        int(os.environ.get("AREAL_BENCH_PROMPTS", 8)), 4, 128, 1024
    )
    n_iters = 3
    mode = os.environ.get("AREAL_BENCH_MODE", "")
    if mode == "longctx":
        # Reference-scale decode budget (ppo-7B-distill-gpus-128.yaml
        # decodes up to 27,648 new tokens with max_tokens_per_mb=30720):
        # fewer samples, >=16k new tokens each, KV window growing through
        # the inflight generator's buckets.  int8 KV cache by default —
        # at 16k+ the cache is the capacity bound (bf16 at batch 8 x 16k
        # is ~3.7 GB next to 9.3 GB of engine state), and halving it is
        # what lets the decode batch reach 8 on this chip.
        n_prompts = int(os.environ.get("AREAL_BENCH_PROMPTS", 4))
        group, max_new, n_iters = 2, 16384, 1
        os.environ.setdefault("AREAL_BENCH_MB_TOKENS", "32768")
        os.environ.setdefault("AREAL_BENCH_KV_DTYPE", "int8")

    mesh = make_mesh(ParallelConfig(), jax.devices()[:1])
    cfg = qwen2_config(size, param_dtype="bfloat16")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))

    class _Tok:
        eos_token_id = 151643
        pad_token_id = 151643

        def decode(self, ids, **kw):
            return ""

    tok = _Tok()
    # Engine order matters for HBM: TrainEngine first (bf16 master shares
    # the freshly-initialized bf16 arrays), then the generator from the
    # SAME master tree — bf16->bf16 astype and same-sharding device_put are
    # no-ops, so one weight copy serves both engines (the hot-swap rebinds
    # it after each optimizer step).
    train_engine = TrainEngine(
        cfg,
        params,
        mesh,
        optimizer_config=OptimizerConfig(lr=2e-5, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 64, 64),
        master_dtype=jnp.bfloat16,
        # Sweepable without edits: AREAL_BENCH_REMAT=full|dots_small|dots|none.
        remat_policy=os.environ.get("AREAL_BENCH_REMAT", "full"),
    )
    del params
    gen_engine = GeneratorEngine(
        cfg, train_engine.get_params(), mesh,
        eos_token_id=tok.eos_token_id,
        max_decode_batch=int(os.environ.get("AREAL_BENCH_DECODE_BATCH", 32)),
        # Synchronous colocated loop: generation never overlaps the
        # donating optimizer step, so the generator may alias the train
        # master's buffers instead of copying them — without this the
        # extra 3.1 GB param copy pushes 1.5B past this chip's 16 GB HBM.
        donation_safe_swap=False,
        # "int8" halves KV HBM per token — the capacity lever for the
        # >=16k longctx mode (a bf16 cache at batch 32 x 16k does not
        # fit this chip at all).
        kv_cache_dtype=os.environ.get("AREAL_BENCH_KV_DTYPE", "auto"),
        # Paged-vs-dense decode leg: AREAL_BENCH_PAGED=0 forces the dense
        # grow-by-doubling window, 1 forces the page pool; unset defers
        # to the engine default (paged unless AREAL_PAGED_KV=0).
        kv_paged=(
            None
            if os.environ.get("AREAL_BENCH_PAGED") is None
            else os.environ["AREAL_BENCH_PAGED"] != "0"
        ),
        kv_page_size=int(os.environ.get("AREAL_BENCH_KV_PAGE_SIZE", 128)),
        kv_pool_pages=int(os.environ.get("AREAL_BENCH_KV_POOL_PAGES", 0)),
    )
    actor = Model("actor", engine=train_engine, tokenizer=tok, config=cfg)
    gen = Model("actor_gen", engine=gen_engine, tokenizer=tok, config=cfg)

    rng = np.random.default_rng(0)
    prompts = SequenceSample(
        keys={"packed_prompts"},
        ids=[f"p{i}" for i in range(n_prompts)],
        seqlens={"packed_prompts": [[prompt_len]] * n_prompts},
        data={
            "packed_prompts": rng.integers(
                0, cfg.vocab_size, size=n_prompts * prompt_len
            ).astype(np.int32)
        },
    )
    g = GenerationHyperparameters(
        n=group, max_new_tokens=max_new, temperature=1.0, top_p=1.0
    )
    actor_if = PPOActorInterface(
        gconfig=g, n_minibatches=2, disable_value=True, kl_ctl=0.0,
        adv_norm=True,
    )
    # Token-budget micro-batches: the fused logprob head avoids the dense
    # [B,S,V] logits, leaving attention/MLP activations as the peak term.
    # Sweepable: AREAL_BENCH_MB_TOKENS.
    # Default 8192: picked in an earlier round's sweep whose artifacts are
    # gone; not re-measured on today's code.
    mb = MicroBatchSpec(
        max_tokens_per_mb=int(os.environ.get("AREAL_BENCH_MB_TOKENS", 8192))
    )

    timers = {"gen": 0.0, "train": 0.0, "sync": 0.0}
    flops = {"gen": 0.0, "train": 0.0}
    # KV-memory accounting for the dense-vs-paged comparison (counters
    # reset per generate call; sum them over the recorded iters).
    kv = {"copy_bytes": 0, "compiles": 0, "live": 0, "alloc": 0}

    def one_step(seed, record=False):
        t0 = time.time()
        rollout = actor_if.generate(gen, prompts, mb)
        t1 = time.time()
        scores = rng.choice([-5.0, 5.0], size=n_prompts * group).astype(
            np.float32
        )
        rollout.update_(
            SequenceSample(
                keys={"rewards"},
                ids=list(rollout.ids),
                seqlens={"rewards": [[1] * group] * n_prompts},
                data={"rewards": scores},
            )
        )
        # The generator's aliased weights are dead until the post-step
        # swap; releasing them lets the optimizer donate params in place.
        gen_engine.release_params()
        stats = actor_if.train_step(actor, rollout, mb)
        t2 = time.time()
        # Weight sync train -> generator (colocated hot-swap).
        gen_engine.set_params(train_engine.get_params())
        jax.block_until_ready(gen_engine.params)
        t3 = time.time()
        if record:
            timers["gen"] += t1 - t0
            timers["train"] += t2 - t1
            timers["sync"] += t3 - t2
            out_lens = [
                int(sum(row))
                for row in rollout.seqlens["packed_input_ids"]
            ]
            p_exp = [prompt_len] * len(out_lens)
            g_lens = [t - prompt_len for t in out_lens]
            flops["gen"] += monitor.flops_generate(cfg, p_exp, g_lens)
            kv["copy_bytes"] += gen_engine.cache_copy_bytes
            kv["compiles"] += gen_engine.decode_compiles
            st = gen_engine.last_pool_stats
            kv["live"] += st.get("live_tokens", 0)
            kv["alloc"] += st.get("allocated_tokens", 0)
            tokens = sum(out_lens)
            flops["train"] += monitor.flops_train(
                cfg, tokens, float(sum(t * t for t in out_lens))
            )
        return rollout, stats

    # Warmup (compiles).
    t0 = time.time()
    one_step(0)
    warmup_s = time.time() - t0

    t0 = time.time()
    total_samples = 0
    total_gen_tokens = 0
    for i in range(n_iters):
        rollout, stats = one_step(i + 1, record=True)
        total_samples += n_prompts * group
        total_gen_tokens += int(
            sum(t for row in rollout.seqlens["packed_input_ids"] for t in row)
        ) - n_prompts * group * prompt_len
    dt = time.time() - t0

    samples_per_sec = total_samples / dt
    n_dev = 1
    mfu_gen = monitor.mfu(flops["gen"], timers["gen"], n_dev)
    mfu_train = monitor.mfu(flops["train"], timers["train"], n_dev)
    mfu_e2e = monitor.mfu(flops["gen"] + flops["train"], dt, n_dev)
    print(
        json.dumps(
            {
                "metric": (
                    f"ppo_samples_per_sec_chip_{size}"
                    + (f"_{mode}" if mode else "")
                ),
                "value": round(samples_per_sec, 4),
                "unit": "samples/s/chip",
                "device": {
                    "platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices()),
                },
                "vs_baseline": round(
                    samples_per_sec / BASELINE_SAMPLES_PER_SEC_CHIP, 3
                ),
                # Decode throughput = generated tokens over time spent
                # GENERATING (dividing by whole-step time, as an earlier
                # revision did, understates decode ~3x and made it look
                # 6x off roofline when it is ~1.5x off).
                "gen_tokens_per_sec": round(
                    total_gen_tokens / max(timers["gen"], 1e-9), 1
                ),
                "gen_tokens_per_sec_e2e": round(total_gen_tokens / dt, 1),
                "step_seconds": round(dt / n_iters, 2),
                "gen_seconds": round(timers["gen"] / n_iters, 2),
                "train_seconds": round(timers["train"] / n_iters, 2),
                "sync_seconds": round(timers["sync"] / n_iters, 3),
                "mfu_gen": round(mfu_gen, 4) if mfu_gen else None,
                "mfu_train": round(mfu_train, 4) if mfu_train else None,
                "mfu_e2e": round(mfu_e2e, 4) if mfu_e2e else None,
                "warmup_seconds": round(warmup_s, 1),
                # Paged-KV contract metrics: a paged run must show
                # decode_compiles == n_iters (one per generate call) and
                # cache_copy_bytes == 0; the dense leg pays both at every
                # window-bucket crossing.  kv_pool_utilization = live
                # tokens / allocated cache tokens, chunk-averaged.
                "kv_paged": bool(gen_engine.kv_paged),
                "decode_compiles": kv["compiles"],
                "cache_copy_bytes": kv["copy_bytes"],
                "kv_pool_utilization": round(
                    kv["live"] / max(kv["alloc"], 1), 4
                ),
                # Fraction of the padded [rows, row_len] train grid that
                # is real tokens — the padding waste MFU silently pays.
                "pack_efficiency": round(
                    getattr(train_engine, "last_pack_stats", {}).get(
                        "pack_efficiency", 0.0
                    ),
                    3,
                ),
                "config": (
                    f"qwen2-{size} bf16, {n_prompts} prompts x{group} group, "
                    f"{prompt_len} prompt + <={max_new} new tokens, GRPO, "
                    "bf16 master+Adam"
                ),
                "baseline_note": (
                    "0.30 samples/s/chip = boba 1.5B e2e on 8xH800 at up to "
                    "27648 new tokens (250 steps x 512 prompts x 16 resp / "
                    "240h / 8 chips, reference README.md:38-43); this row "
                    f"decodes up to {max_new} new tokens/sample — a "
                    "like-for-like decode budget (within 1.7x of the "
                    "reference's 27,648 cap; its median response is far "
                    "below the cap) — on ONE v5e chip with ~0.5x an "
                    "H800's bf16 peak; vs_baseline divides by the same "
                    "0.30 constant"
                    if mode == "longctx"
                    else
                    "0.30 samples/s/chip = boba 1.5B e2e on 8xH800 at up "
                    "to 27648 new tokens; this bench caps decode at "
                    f"{max_new} tokens (long tails dominate the "
                    "reference's wall-clock) and one H800 has ~2x this "
                    "chip's bf16 peak — see the longctx row for the "
                    "like-for-like comparison"
                ),
            }
        )
    )


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "1.5b")
