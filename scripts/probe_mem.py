"""Probe resident device memory stage by stage (live jax.Arrays).

Builds the 1.5B train and generation engines by hand and prints the
live-array total after each stage — separates "resident set too big" from "XLA transient peak
too big" when diagnosing single-chip OOMs.  (The production path —
build_ppo_math + run_experiment_inproc — is chip_smoke.py's `static` phase,
which prints live and peak bytes per chip.)
"""

import os
import sys
import time

import numpy as np


def live_gb(tag):
    import jax

    arrs = jax.live_arrays()
    total = sum(a.nbytes for a in arrs) / 1e9
    big = sorted(
        ((a.nbytes / 1e9, str(a.shape), str(a.dtype)) for a in arrs),
        reverse=True,
    )[:6]
    print(f"[mem] {tag}: {total:.2f} GB live in {len(arrs)} arrays")
    for gb, shape, dt in big:
        if gb > 0.05:
            print(f"       {gb:6.2f} GB  {shape} {dt}")
    sys.stdout.flush()
    return total


def main(size="1.5b"):
    import jax
    import jax.numpy as jnp

    from areal_tpu.base import compilation_cache

    compilation_cache.enable()

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import (
        FinetuneSpec,
        GenerationHyperparameters,
        Model,
        OptimizerConfig,
    )
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import qwen2_config

    mesh = make_mesh(ParallelConfig(), jax.devices()[:1])
    cfg = qwen2_config(size, param_dtype="bfloat16")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    live_gb("init_params")

    class _Tok:
        eos_token_id = 151643
        pad_token_id = 151643

        def decode(self, ids, **kw):
            return ""

    tok = _Tok()
    train_engine = TrainEngine(
        cfg,
        params,
        mesh,
        optimizer_config=OptimizerConfig(lr=2e-5, warmup_steps_proportion=0.0),
        ftspec=FinetuneSpec(1, 64, 64),
        master_dtype=jnp.bfloat16,
        remat_policy=os.environ.get("AREAL_BENCH_REMAT", "full"),
    )
    del params
    live_gb("train_engine (params + Adam)")
    gen_engine = GeneratorEngine(
        cfg, train_engine.get_params(), mesh,
        eos_token_id=tok.eos_token_id, max_decode_batch=32,
    )
    live_gb("gen_engine (should alias)")
    actor = Model("actor", engine=train_engine, tokenizer=tok, config=cfg)
    gen = Model("actor_gen", engine=gen_engine, tokenizer=tok, config=cfg)

    n_prompts, group, prompt_len, max_new = 8, 4, 128, int(
        os.environ.get("PROBE_MAX_NEW", 1024)
    )
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
    mb = MicroBatchSpec(
        max_tokens_per_mb=int(os.environ.get("AREAL_BENCH_MB_TOKENS", 4096))
    )

    t0 = time.time()
    rollout = actor_if.generate(gen, prompts, mb)
    print(f"[mem] generate took {time.time() - t0:.1f}s")
    live_gb("after generate")

    scores = rng.choice([-5.0, 5.0], size=n_prompts * group).astype(np.float32)
    rollout.update_(
        SequenceSample(
            keys={"rewards"},
            ids=list(rollout.ids),
            seqlens={"rewards": [[1] * group] * n_prompts},
            data={"rewards": scores},
        )
    )
    try:
        t0 = time.time()
        stats = actor_if.train_step(actor, rollout, mb)
        print(f"[mem] train_step took {time.time() - t0:.1f}s")
        live_gb("after train_step")
        print("[mem] OK — no OOM")
    except Exception as e:
        print(f"[mem] train_step FAILED: {type(e).__name__}: {e}")
        live_gb("at failure")
        raise


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "1.5b")
