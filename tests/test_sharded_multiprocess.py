"""Sharded data plane across REAL process boundaries.

The single-process suite (test_sharded_data.py) can only exercise the
layout-parity form of the plane: one process cannot host a
process-spanning mesh, so `jax.make_array_from_process_local_data` never
sees genuinely divergent host buffers there.  This file closes that gap
the way the reference's multi-process tests do (realhf/base/testing.py
LocalMultiProcessTest spawns gloo workers): the parent spawns TWO
`jax.distributed` CPU processes (4 virtual devices each) forming one
8-device mesh whose batch axis spans them, and each member's HOST arrays
are divergent — real values only for its own rows, zeros elsewhere —
exactly what the master ships under shard_keys (system/master.py
_dispatch_mfc, reference: realhf/system/data_manager.py:144-416).

Parity asserted across four independent computations:
  sharded rank0 == sharded rank1 == full-data run == numpy oracle
for (a) TrainEngine.masked_moments (the in-mesh global-stats reduction
PPO relies on under sharding) and (b) a full train_batch step's
loss/grad_norm (grads flow through the placed arrays, so any mis-shipped
row diverges them).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SEQLEN = 8
_N_IDS = 4


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _full_data(vocab):
    rng = np.random.default_rng(7)
    toks = rng.integers(0, vocab, size=_N_IDS * _SEQLEN).astype(np.int32)
    x = rng.normal(size=_N_IDS * _SEQLEN).astype(np.float32)
    adv = rng.normal(size=_N_IDS * _SEQLEN).astype(np.float32)
    mask = (rng.random(_N_IDS * _SEQLEN) < 0.75).astype(np.float32)
    mask[::_SEQLEN] = 1.0  # every sequence keeps at least one loss token
    return toks, x, adv, mask


def _ppo_child(rank: int, mode: str, outfile: str):
    """Full PPO interface across the 2-process mesh: adaptive KL +
    KL-in-reward + batch adv_norm (everything the old guard refused),
    with the per-token inputs zero-filled for the other member's rows
    under mode='ppo_sharded'.  Stats must equal the full-data run."""
    import jax
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import (
        FinetuneSpec,
        GenerationHyperparameters,
        Model,
        OptimizerConfig,
    )
    from areal_tpu.base.topology import (
        ParallelConfig,
        local_batch_shard,
        make_mesh,
    )
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.interfaces.ppo import PPOActorInterface
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    mesh = make_mesh(ParallelConfig(data=8))
    shard_rank, n_shards = local_batch_shard(mesh)
    assert n_shards == 2

    cfg = tiny_config()
    rng = np.random.default_rng(23)
    n_ids, group = 4, 2
    seqlens = [[12, 14] for _ in range(n_ids)]
    flat = [l for row in seqlens for l in row]
    total = sum(flat)
    n_seqs = n_ids * group
    pmask = np.zeros(total, bool)
    off = 0
    for l in flat:
        pmask[off : off + 4] = True
        off += l
    data = {
        "packed_input_ids": rng.integers(1, 64, total).astype(np.int32),
        "prompt_mask": pmask,
        "packed_logprobs": rng.normal(-1, 0.2, total - n_seqs).astype(
            np.float32
        ),
        "packed_ref_logprobs": rng.normal(-1.1, 0.2, total - n_seqs).astype(
            np.float32
        ),
        "rewards": rng.choice([-1.0, 1.0], n_seqs).astype(np.float32),
        "seq_no_eos_mask": np.zeros(n_seqs, np.float32),
    }
    owner = [i % 2 for i in range(n_ids)]
    sample = SequenceSample(
        keys=set(data),
        ids=[f"q{i}" for i in range(n_ids)],
        seqlens={
            "packed_input_ids": [list(r) for r in seqlens],
            "prompt_mask": [list(r) for r in seqlens],
            "packed_logprobs": [[l - 1 for l in r] for r in seqlens],
            "packed_ref_logprobs": [[l - 1 for l in r] for r in seqlens],
            "rewards": [[1] * group] * n_ids,
            "seq_no_eos_mask": [[1] * group] * n_ids,
        },
        data=data,
        metadata={"shard_of": [[o, 2] for o in owner]},
    )
    if mode == "ppo_sharded":
        from tests.fixtures import zero_fill_unowned

        zero_fill_unowned(
            sample, shard_rank, 2,
            ("packed_input_ids", "packed_logprobs", "packed_ref_logprobs"),
        )

    engine = TrainEngine(
        cfg,
        tfm.init_params(cfg, jax.random.PRNGKey(0)),
        mesh,
        optimizer_config=OptimizerConfig(
            lr=1e-4, warmup_steps_proportion=0.0
        ),
        ftspec=FinetuneSpec(1, 8, 8),
    )
    actor = Model("actor", engine=engine, tokenizer=None, config=cfg)
    iface = PPOActorInterface(
        gconfig=GenerationHyperparameters(n=group, max_new_tokens=8),
        n_minibatches=1,
        kl_ctl=0.1,
        kl_adaptive=True,
        adaptive_kl_target=4.0,
        adaptive_kl_horizon=100.0,
        adv_norm=True,
        disable_value=True,
    )
    stats = iface.train_step(actor, sample, MicroBatchSpec())
    out = {
        "loss": stats["actor_loss"],
        "ref_kl": stats["ref_kl"],
        "adv_abs": stats["advantage_abs"],
        "kl_after": iface._kl().value,
        "rank": shard_rank,
    }
    # EVERY rank writes: the adaptive controller must advance in
    # lockstep across members, and only comparing both proves it.
    import json as _json

    with open(f"{outfile}.rank{rank}", "w") as f:
        _json.dump(out, f)
    if rank == 0:
        with open(outfile, "w") as f:
            _json.dump(out, f)


def _child_main(rank: int, port: int, mode: str, outfile: str):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    sys.path.insert(0, _REPO)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2,
        process_id=rank,
    )
    import jax.numpy as jnp

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import (
        ParallelConfig,
        local_batch_shard,
        make_mesh,
    )
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config

    assert jax.device_count() == 8 and jax.process_count() == 2
    if mode.startswith("ppo_"):
        _ppo_child(rank, mode, outfile)
        jax.distributed.shutdown()
        return
    mesh = make_mesh(ParallelConfig(data=8))
    shard_rank, n_shards = local_batch_shard(mesh)
    assert n_shards == 2, "batch axis must span the two processes"

    cfg = tiny_config()
    toks, x, adv, mask = _full_data(cfg.vocab_size)
    owner = [i % 2 for i in range(_N_IDS)]
    if mode == "sharded":
        # Divergent host data: zero every row this member does not own —
        # byte-for-byte what the worker's zero-fill assembly produces.
        for i in range(_N_IDS):
            if owner[i] != shard_rank:
                sl = slice(i * _SEQLEN, (i + 1) * _SEQLEN)
                toks[sl], x[sl], adv[sl] = 0, 0.0, 0.0
    seqlens = [[_SEQLEN]] * _N_IDS
    sample = SequenceSample(
        keys={"packed_input_ids", "x", "adv", "loss_mask"},
        ids=[f"id{i}" for i in range(_N_IDS)],
        seqlens={
            k: [list(s) for s in seqlens]
            for k in ("packed_input_ids", "x", "adv", "loss_mask")
        },
        data={
            "packed_input_ids": toks,
            "x": x,
            "adv": adv,
            "loss_mask": mask,
        },
        metadata={"shard_of": [[o, 2] for o in owner]},
    )

    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    engine = TrainEngine(cfg, params, mesh)

    mom = engine.masked_moments(
        sample, MicroBatchSpec(), ("x",), mask_key="loss_mask"
    )

    def loss_fn(out, batch):
        m = batch["loss_mask"] > 0
        loss = jnp.where(m, out * batch["adv"], 0.0).sum()
        return loss, {"loss_sum": loss}

    stats = engine.train_batch(
        sample.select_keys({"packed_input_ids", "adv", "loss_mask"}),
        MicroBatchSpec(),
        loss_fn=loss_fn,
        loss_weight_fn=lambda a: float((a["loss_mask"] > 0).sum()),
        extra_keys=("adv", "loss_mask"),
    )

    out = {
        "count": mom["count"],
        "x": [float(v) for v in mom["x"]],
        "loss": stats["loss"],
        "grad_norm": stats["grad_norm"],
    }
    if rank == 0:
        with open(outfile, "w") as f:
            json.dump(out, f)
    jax.distributed.shutdown()


def _run_trial(mode: str, tmp_path) -> dict:
    port = _free_port()
    outfile = str(tmp_path / f"{mode}.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("PYTEST_CURRENT_TEST", None)
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--child", str(r), str(port), mode, outfile,
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for r in range(2)
    ]
    logs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        logs.append(out.decode(errors="replace"))
        if p.returncode != 0:
            raise AssertionError(
                f"{mode} child failed (rc={p.returncode}):\n"
                + "\n---\n".join(logs)
            )
    with open(outfile) as f:
        return json.load(f)


def test_sharded_dispatch_across_processes(tmp_path):
    sharded = _run_trial("sharded", tmp_path)
    full = _run_trial("full", tmp_path)

    # Numpy oracle from the full data.
    from areal_tpu.models.config import tiny_config

    _, x, _, mask = _full_data(tiny_config().vocab_size)
    m = mask > 0
    assert sharded["count"] == pytest.approx(float(m.sum()))
    want = [
        float(x[m].sum()),
        float((x[m] ** 2).sum()),
        float(np.abs(x[m]).sum()),
    ]
    assert sharded["x"] == pytest.approx(want, rel=1e-5)

    # Divergent-host run must agree exactly with the full-data run: the
    # placed global arrays are identical, so loss and grad norm are too.
    assert sharded["x"] == pytest.approx(full["x"], rel=1e-6)
    assert sharded["loss"] == pytest.approx(full["loss"], rel=1e-5)
    assert sharded["grad_norm"] == pytest.approx(
        full["grad_norm"], rel=1e-5
    )


def test_full_ppo_interface_across_processes(tmp_path):
    """The round-5 headline guarantee, proven across REAL process
    boundaries: full PPO (adaptive KL + KL-in-reward + batch adv_norm)
    under shard-exact dispatch produces the same loss, ref-KL, |adv|,
    and controller trajectory as the full-data run."""
    sharded = _run_trial("ppo_sharded", tmp_path)
    full = _run_trial("ppo_full", tmp_path)
    for key in ("loss", "ref_kl", "adv_abs", "kl_after"):
        assert sharded[key] == pytest.approx(full[key], rel=2e-4), (
            key, sharded, full
        )
    # Cross-rank lockstep: both members measured the same global stats
    # and advanced the adaptive controller identically.
    import json as _json

    r0 = _json.load(open(tmp_path / "ppo_sharded.json.rank0"))
    r1 = _json.load(open(tmp_path / "ppo_sharded.json.rank1"))
    assert r0["rank"] != r1["rank"]
    for key in ("ref_kl", "kl_after", "loss", "adv_abs"):
        assert r0[key] == pytest.approx(r1[key], rel=1e-6), (key, r0, r1)


if __name__ == "__main__" and "--child" in sys.argv:
    i = sys.argv.index("--child")
    _child_main(
        int(sys.argv[i + 1]),
        int(sys.argv[i + 2]),
        sys.argv[i + 3],
        sys.argv[i + 4],
    )
