"""The quickest proof that today's tree still starts on the chip.

    python chip_smoke.py            # needs a TPU; one process; exit 0 = ok

Drives the RL step — generate -> grade -> actor update -> weight
hand-back — through the functions the CLI calls
(`experiments.common.build_ppo_math` + `apps.main.run_experiment_inproc`,
i.e. `apps/quickstart.py ppo-math`) at the full width and depth of
qwen2-1.5B with random weights from a seed, and checks what comes out.
Phases, all in this one process (a chip belongs to one process):

  kernels    every Pallas kernel reachable on a chip, compiled by Mosaic
             (not interpreted) at the 1.5B head geometry, against its
             XLA/dense counterpart on the device
  static     the trainer, 8 prompts x 4: static decode program
  serving    the same plan with fewer decode slots than requests, so
             generate() goes inflight -> paged -> ragged serving chunk
  multichip  with >= 4 chips: the serving plan again with train f2 on
             chips 0-1 and gen m2 on chips 2-3, weights re-laid-out f2 ->
             m2 at every hand-back (printed as skipped with fewer)

Lengths are short (prompts ~17 tokens, <= 256 new): width is what meets
the tiling and HBM limits; lengths are the benchmark's business.

The grader is the real math verifier on the `tests/fixtures.py` rows.  A
random model never answers right, so every reward is -5 and GRPO's
group-normalised advantage would be identically zero — no gradient,
nothing to hand back.  The actor therefore runs with a zero value
baseline and no advantage normalisation (`disable_value=False,
adv_norm=False`, no critic), which turns the verifier's -5 into a real
policy-gradient signal; the device programs are the same either way (the
advantages are an input array).

Without a TPU this exits non-zero and prints no result.
`--cpu-rehearsal` runs the same phases at toy size on the CPU to debug
the script before chip time is spent; it says `platform=cpu` and is never
what the no-argument run falls back to.  Any failed check raises.

Last line of stdout: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import gc
import json
import os
import sys
import tempfile
import time

PHASES = ("kernels", "static", "serving", "multichip")


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    log(f"  ok: {what}")


# --------------------------------------------------------------------------
# Device memory
# --------------------------------------------------------------------------


def bytes_per_device(arrays):
    """Bytes the given jax.Arrays hold on each local device.  A buffer
    counts once however many Arrays view it (the colocated generator
    aliases the trainer's weights; `addressable_shards` itself leaves
    per-shard views behind that `jax.live_arrays()` then lists too)."""
    import jax

    out = {d.id: 0 for d in jax.local_devices()}
    seen = set()
    for a in arrays:
        if a.is_deleted():
            continue
        for s in a.addressable_shards:
            ptr = s.data.unsafe_buffer_pointer()
            if ptr not in seen:
                seen.add(ptr)
                out[s.device.id] += s.data.nbytes
    return out


def live_bytes_per_device():
    import jax

    return bytes_per_device(jax.live_arrays())


def check_within_hbm(tag):
    import jax

    live = live_bytes_per_device()
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        limit = stats.get("bytes_limit")
        peak = stats.get("peak_bytes_in_use")
        log(
            f"  {tag}: device {d.id} live {live[d.id] / 1e9:.2f} GB"
            + (f", peak {peak / 1e9:.2f} GB" if peak is not None else "")
            + (f" of {limit / 1e9:.2f} GB" if limit else "")
        )
        if limit:
            check(
                live[d.id] <= limit and (peak is None or peak <= limit),
                f"device {d.id} live and peak bytes within HBM",
            )
    return live


def release_device_memory(tag, budget_bytes=16 << 20):
    """A finished phase must leave the chip to the next one."""
    while gc.collect():  # engine <-> jit-closure cycles take a few passes
        pass
    live = live_bytes_per_device()
    check(
        max(live.values()) <= budget_bytes,
        f"{tag}: engines released ({max(live.values()) / 1e6:.0f} MB live)",
    )


# --------------------------------------------------------------------------
# Phase: kernels
# --------------------------------------------------------------------------


def _max_err(a, b):
    import jax.numpy as jnp

    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


# The flash kernels' calls in the benchmark's cells: name -> (rows, row
# length, q heads, kv heads, head_dim, the sequences packed into each row).
FLASH_CELLS = {
    "long-prompt train row [12,8192,128]":
        (1, 8192, 12, 2, 128, [2598, 2598, 1263, 740, 384, 384]),
    "long-prompt train rows [24,8192,128]":
        (2, 8192, 12, 2, 128, [2598, 1263, 1263, 740, 740, 384, 384]),
    "serving train row [12,8192,128]": (1, 8192, 12, 2, 128, [210] * 39),
    "prefill [192,2560,128]": (16, 2560, 12, 2, 128, [2534]),
    "qwen3-next train row [16,8192,256]": (1, 8192, 16, 2, 256, [640] * 12),
    "7B per-chip rows [28,2048,128]": (1, 2048, 28, 4, 128, [400] * 5),
    "a row in two chunks [12,65536,128]":
        (1, 65536, 12, 2, 128, [30000, 20000, 9000, 4000]),
    "lfm2 train row, heads of 64 [32,8192,64]":
        (1, 8192, 32, 8, 64, [4608, 2763, 821]),
}


def _flash_head_64(fa, attention, dt, tol, on_tpu):
    """The three flash kernels at heads of 64 — half a lane tile, lfm2_moe's
    and no other configuration's — against the dense mask, ragged packed
    rows, 32 query heads over 8 key/value heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    s = 1024 if on_tpu else 256
    rng = np.random.default_rng(64)
    q, k, v, do = (
        jnp.asarray(rng.standard_normal((2, s, h, 64)), dt)
        for h in (32, 8, 8, 32)
    )
    ids = np.zeros((2, s), np.int32)
    ids[0, : s // 2], ids[0, s // 2: s - 40] = 1, 2
    ids[1, :60], ids[1, 60: s - 100], ids[1, s - 100: s - 10] = 1, 2, 3
    seg = jnp.asarray(ids)
    real = (seg > 0)[..., None, None]

    def pulled(attend):
        def f(q, k, v):
            return jnp.where(real, attend(q, k, v, seg), 0).astype(jnp.float32)

        out, pull = jax.vjp(f, q, k, v)
        return (out,) + pull(do.astype(jnp.float32))

    want = jax.jit(lambda: pulled(attention.packed_attention_reference))()
    got = jax.jit(lambda: pulled(fa.flash_attention))()
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
        check(err <= tol * max(scale, 1.0),
              f"flash at head_dim 64, {name} == dense mask "
              f"(max err {err:.2e} of {scale:.2e})")


def _flash_cell_shapes(fa, dt, on_tpu, reps=5):
    """The kernels at the cells' sizes (about 1/16 the lengths in rehearsal):
    under the live schedule they give the bits they give when every tile
    of the square is visited, and a call's time goes to the log."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    blk = fa.DEFAULT_BLOCK_Q
    for name, (b, s, hq, hkv, d, lens) in FLASH_CELLS.items():
        if not on_tpu:
            s, lens = max(s // 16 // blk, 1) * blk, [n // 16 for n in lens]
        rng = np.random.default_rng(s + hq)
        q, k, v, do = (
            jnp.asarray(rng.standard_normal((b * h, s, d)), dt)
            for h in (hq, hkv, hkv, hq)
        )
        ids = np.repeat(np.arange(len(lens)) + 1, lens)[:s]
        seg = jnp.asarray(np.tile(np.pad(ids, (0, s - len(ids))), (b, 1)))
        scale = d ** -0.5

        @jax.jit
        def fwd(sched):
            return fa._fwd(q, k, v, seg, sched, hq, scale, blk, blk, True)

        @jax.jit
        def fwd_bwd(sched):
            o, lse = fa._fwd(q, k, v, seg, sched, hq, scale, blk, blk, True)
            res = (q, k, v, o, lse, seg, sched)
            return (o, lse) + fa._bwd(scale, blk, blk, True, res, do)

        live = jax.jit(fa.live_schedule, static_argnums=(1, 2, 3))(
            seg, blk, blk, True
        )
        n = s // blk
        got = fwd_bwd(live)
        want = fwd_bwd(fa.all_tiles_schedule(b, n, n))
        check(
            all(bool(jnp.array_equal(a, c)) for a, c in zip(got, want)),
            f"flash {name}: o, lse, dq, dk, dv under the live schedule == "
            f"under all tiles, bit for bit "
            f"({int(jnp.sum(live.k_hi - live.k_lo + 1))} of {b * n * n} "
            f"tiles a head)",
        )
        ms = {}
        for what, fn in (("fwd", fwd), ("fwd+bwd", fwd_bwd)):
            jax.block_until_ready(fn(live))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(live)
            jax.block_until_ready(out)
            ms[what] = (time.perf_counter() - t0) / reps * 1e3
        log(f"  flash {name}: fwd {ms['fwd']:.3f} ms, fwd + dq + dkv "
            f"{ms['fwd+bwd']:.3f} ms a call (host clock, {reps} calls"
            + ("" if on_tpu else "; interpreted on the cpu, no device time")
            + ")")


def phase_kernels(geom, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.ops import attention
    from areal_tpu.ops.pallas import flash_attention as fa
    from areal_tpu.ops.pallas import paged_attention as pa

    check(
        fa._interpret() is (not on_tpu) and pa._interpret() is (not on_tpu),
        "Pallas kernels " + ("compiled by Mosaic" if on_tpu else
                             "interpreted (cpu rehearsal)"),
    )
    n_q, n_kv, d = geom["n_q"], geom["n_kv"], geom["d"]
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    tol = 3e-2 if on_tpu else 2e-4
    rng = np.random.default_rng(0)

    # ---- flash: forward, backward, shard_mapped form vs the dense oracle
    b, s = 2, geom["flash_s"]
    q = jnp.asarray(rng.standard_normal((b, s, n_q, d)), dt)
    k = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dt)
    v = jnp.asarray(rng.standard_normal((b, s, n_kv, d)), dt)
    w = jnp.asarray(rng.standard_normal((b, s, n_q, d)), jnp.float32)
    # Packed rows: three sequences + padding, one sequence + padding.
    seg = np.zeros((b, s), np.int32)
    seg[0, : s // 4], seg[0, s // 4 : s // 2 + 7] = 1, 2
    seg[0, s // 2 + 7 : s - 9] = 3
    seg[1, : s - 40] = 1
    seg = jnp.asarray(seg)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, seg).astype(jnp.float32) * w
        )

    ref = jax.jit(attention.packed_attention_reference)
    flash = jax.jit(fa.flash_attention)
    o_ref, o_fl = ref(q, k, v, seg), flash(q, k, v, seg)
    check(bool(jnp.isfinite(o_fl.astype(jnp.float32)).all()),
          "flash forward finite")
    err = _max_err(o_ref, o_fl)
    check(err <= tol, f"flash forward == dense reference (max err {err:.2e})")
    g_ref = jax.jit(jax.grad(loss(attention.packed_attention_reference),
                             argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2)))(
        q, k, v
    )
    for name, a, c in zip(("dq", "dk", "dv"), g_ref, g_fl):
        scale = float(jnp.max(jnp.abs(a.astype(jnp.float32)))) or 1.0
        err = _max_err(a, c) / scale
        check(err <= tol, f"flash backward {name} == reference "
                          f"(max rel err {err:.2e})")
    n_dev = len(jax.devices())
    layout = "m2" if n_dev >= 2 else "d1"
    pc = ParallelConfig.from_str(layout)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    o_sh = jax.jit(
        lambda q, k, v: attention.packed_attention(
            q, k, v, seg, use_flash=mesh
        )
    )(q, k, v)
    err = _max_err(o_ref, o_sh)
    check(err <= tol,
          f"shard_mapped flash ({layout}) == reference (max err {err:.2e})")

    _flash_head_64(fa, attention, dt, tol, on_tpu)
    _flash_cell_shapes(fa, dt, on_tpu)

    _paged_cell_shape(attention, geom, dt, tol, on_tpu)

    _latent_cell_shape(attention, dt, tol, on_tpu)

    _kv_cell_shape(attention, dt, tol, on_tpu)

    _ssm_cell_shape(dt, tol, on_tpu)

    _gdn_chunk_cell_shape(on_tpu)


def _gdn_chunk_cell_shape(on_tpu, reps=5):
    """The Gated DeltaNet's chunked delta rule in training at
    `q3next-rollout64-512`'s micro-batch (one packed row of 8,192 tokens,
    32 value heads over 16 key heads of 128, eleven segments of 642 tokens
    and pads; 256 tokens and 4 heads in rehearsal): the Pallas sweep
    `ops/pallas/delta_chunk.gdn_chunk` and its own backward against the
    `jnp` form `linear_attention.gated_delta_chunked` and its `jax.grad` —
    o and the five gradients within what bf16 operands move (both forms
    round every product's operands to bf16 on the chip, in another order)
    — and a call's time, both forms, to the log."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import delta_chunk_bench as bench

    s, hk, hv, d, seg_len = (8192, 16, 32, 128, 642) if on_tpu else (
        256, 2, 4, 128, 100)
    ops, seg, w = bench.operands(s, hk, hv, d, seg_len)
    got = {}
    for kind in ("jnp", "kernel"):
        fwd, fwd_bwd = bench.variant_fn(kind, hv // hk)
        got[kind] = jax.block_until_ready(fwd_bwd(ops, seg, w))
        if on_tpu:
            log(f"gdn_chunk cell shape, {kind} form: forward "
                f"{bench.ms_per_call(fwd, (ops, seg, w), reps):.2f} ms, "
                f"forward + backward "
                f"{bench.ms_per_call(fwd_bwd, (ops, seg, w), reps):.2f} ms")
    tol = 2e-2 if on_tpu else 3e-2  # the kernel's operands are bf16 here too
    for name, a, c in zip(bench.NAMES, got["jnp"], got["kernel"]):
        scale = float(jnp.max(jnp.abs(a))) or 1.0
        err = _max_err(a, c) / scale
        check(err <= tol, f"gdn_chunk {name} == jnp form "
                          f"(max err {err:.2e} of the largest entry)")


def _ssm_cell_shape(dt, tol, on_tpu, reps=10):
    """The Mamba-2 decode step (`models/mamba.ssm_step`) at
    `nemo3n-rollout64-512`'s shape (64 rows, 4 layers of 64 heads x 64
    channels with an fp32 state of 128, conv over 6,144 channels; 2 layers
    of 4 heads x 16 with a state of 16 in rehearsal): a few tokens stepped
    one at a time end on the state and the outputs of the chunked scan over
    the same tokens (`ssm_forward`), and the time of one sweep over the
    layers goes to the log beside what the bandwidth allows.  The sweep
    runs over a state MADE INSIDE the program, as the decode loop's is (a
    cache handed in from outside keeps an entry parameter's layout and is
    copied in front of the step: PR 38's threefold misreading)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import mamba
    from areal_tpu.models.config import ModelConfig
    from benchmark import peaks_ssm

    sizes = dict(hidden_dim=2688, ssm_n_heads=64, ssm_head_dim=64,
                 ssm_n_groups=8, ssm_state_dim=128) if on_tpu else dict(
        hidden_dim=64, ssm_n_heads=4, ssm_head_dim=16, ssm_n_groups=2,
        ssm_state_dim=16, ssm_chunk=4)
    n_layers, b, t = (4, 64, 6) if on_tpu else (2, 4, 6)
    cfg = ModelConfig(
        n_layers=n_layers, n_q_heads=2, n_kv_heads=2, head_dim=16,
        intermediate_dim=16, vocab_size=16, rms_norm_eps=1e-5,
        layer_pattern="M" * n_layers,
        param_dtype="bfloat16" if on_tpu else "float32", **sizes)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape) * fan_in**-0.5).astype(dt)

    blocks = mamba.init_ssm(cfg, jax.random.PRNGKey(3), n_layers, dense)
    h = jax.random.normal(
        jax.random.PRNGKey(4), (b, t, cfg.hidden_dim)).astype(dt)

    @jax.jit
    def both(blocks, h):
        blk = {n: w[0] for n, w in blocks.items()}
        want, state, _ = mamba.ssm_forward(
            h, blk, cfg, jnp.ones((b, t), jnp.int32), with_state=True)
        states = jnp.zeros(
            (n_layers, b, cfg.ssm_n_heads, cfg.ssm_head_dim,
             cfg.ssm_state_dim), jnp.float32)
        tails = jnp.zeros(
            (n_layers, b, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim), dt)
        got = []
        for i in range(t):
            y, states, tails = mamba.ssm_step(
                h[:, i: i + 1], blk, cfg, states, tails, 0)
            got.append(y[:, 0])
        return want, jnp.stack(got, 1), state, states[0]

    want, got, state, stepped = both(blocks, h)
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
    err = _max_err(want, got) / scale
    check(err <= tol, f"mamba decode steps == chunked scan (max rel err {err:.2e})")
    err = _max_err(state, stepped) / (float(jnp.max(jnp.abs(state))) or 1.0)
    check(err <= tol, f"mamba state after the steps == the scan's (max rel err {err:.2e})")

    def timed(sweeps):
        @jax.jit
        def run(blocks, x, key):
            states = jax.random.normal(
                key, (n_layers, b, cfg.ssm_n_heads, cfg.ssm_head_dim,
                      cfg.ssm_state_dim), jnp.float32)
            tails = jnp.zeros(
                (n_layers, b, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim), dt)

            def layer(i, carry):
                y, states, tails = carry
                li = i % n_layers
                blk = {n: jax.lax.dynamic_index_in_dim(w, li, 0, False)
                       for n, w in blocks.items()}
                out, states, tails = mamba.ssm_step(
                    y, blk, cfg, states, tails, li)
                return 0.5 * (y + out), states, tails

            y, states, _ = jax.lax.fori_loop(
                0, sweeps * n_layers, layer, (x, states, tails))
            return y, states[0, 0, 0, 0, 0]
        return run

    wall, key = {}, jax.random.PRNGKey(5)
    for sweeps in (1, 1 + reps):
        fn = timed(sweeps)
        jax.block_until_ready(fn(blocks, h[:, :1], key))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(blocks, h[:, :1], key))
        wall[sweeps] = time.perf_counter() - t0
    ms = (wall[1 + reps] - wall[1]) / reps * 1e3
    floor_ms = peaks_ssm.ssm_decode_bytes(cfg, b) / 819e9 * 1e3
    log(f"  mamba decode step: {n_layers} layers x {b} rows, state "
        f"[{cfg.ssm_n_heads}, {cfg.ssm_head_dim}, {cfg.ssm_state_dim}] fp32: "
        f"{ms:.3f} ms a sweep where a v5e's 819 GB/s allow {floor_ms:.3f} "
        f"(host clock, {reps} sweeps over a state made in the program"
        + ("" if on_tpu else "; on the cpu, no device time") + ")")


def _kv_cell_shape(attention, dt, tol, on_tpu):
    """The kernel `kv_decode` against `decode_attention` on a STACKED k/v
    cache at `q1p5b-decode-static`'s shape (8 rows, 1,280 slots of 2 key
    heads under 12 query heads, 28 layers; 2 layers of 256 slots in
    rehearsal) and at `olmoe-decode-tail`'s 16 ungrouped heads: every
    layer, windows that start late and end early, an empty window's exact
    zeros.  Its time is `scripts/kv_decode_bench.py`'s to read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.pallas.kv_decode import kv_decode

    b, d = 8, 128
    rng = np.random.default_rng(11)
    for g, rep, n_layers, s_max in (
            ((2, 6, 28, 1280), (16, 1, 3, 1280)) if on_tpu
            else ((2, 6, 2, 256), (16, 1, 2, 256))):
        k, v = (jnp.asarray(
            rng.standard_normal((n_layers, b, s_max, g, d)), dt)
            for _ in range(2))
        q = jnp.asarray(rng.standard_normal((b, 1, g * rep, d)), dt)
        lo = rng.integers(0, 160, size=b)
        hi = rng.integers(200, s_max + 1, size=b)
        lo[0], hi[0], lo[1], hi[1] = 0, s_max, 9, 9  # whole window; empty
        lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)

        def sweep(q, k, v, kernel):
            def layer(li, acc):
                if kernel:
                    out = kv_decode(q, k, v, li, lo, hi)
                else:
                    out = attention.decode_attention(
                        q, jax.lax.dynamic_index_in_dim(k, li, 0, False),
                        jax.lax.dynamic_index_in_dim(v, li, 0, False), lo, hi)
                return acc + out.astype(jnp.float32)
            return jax.lax.fori_loop(
                0, n_layers, layer, jnp.zeros(q.shape, jnp.float32))

        sweep = jax.jit(sweep, static_argnums=3)
        got, want = sweep(q, k, v, True), sweep(q, k, v, False)
        err = _max_err(want, got)
        check(err <= tol * n_layers,
              f"kv decode kernel ({n_layers} layers x {g} key heads x {rep} "
              f"of a stacked cache summed) == XLA form (max err {err:.2e})")
        check(float(jnp.max(jnp.abs(got[1]))) == 0.0,
              "kv decode kernel: an empty window's exact zeros")


def _latent_cell_shape(attention, dt, tol, on_tpu, reps=10):
    """The latent decode kernel against the XLA form on a STACKED latent
    cache at `glm47f-rollout64-1k`'s shape (64 rows, a 1,280-slot window of
    576-wide rows, 7 layers, 20 heads; 2 layers of 256 slots in rehearsal):
    every layer, windows that start late and end early, an empty window's
    exact zeros; and the time of one sweep over the layers in each form
    goes to the log."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, h, c, r = 64, 20, 512, 64
    n_layers, s_max = (7, 1280) if on_tpu else (2, 256)
    rng = np.random.default_rng(7)
    cache = jnp.asarray(
        rng.standard_normal((n_layers, b, s_max, c + r)), dt)
    q = jnp.asarray(rng.standard_normal((b, h, c + r)), dt)
    lo = rng.integers(0, 160, size=b)
    hi = rng.integers(256, s_max + 1, size=b)
    lo[0], hi[0], lo[1], hi[1] = 0, s_max, 9, 9  # whole window; empty
    lo, hi = jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)

    def sweep(use_kernel):
        @jax.jit
        def run(q, cache):
            def layer(li, acc):
                return acc + attention.latent_decode_attention(
                    q, cache, li, lo, hi, c, (c + r) ** -0.5,
                    use_kernel=use_kernel,
                ).astype(jnp.float32)
            return jax.lax.fori_loop(
                0, n_layers, layer, jnp.zeros((b, h, c), jnp.float32)
            )
        return run

    def timed(use_kernel, sweeps):
        """`sweeps` sweeps over a cache made INSIDE the program, as the
        decode loop's is: a cache passed in keeps the layout of an entry
        parameter and XLA copies all 660 MB of it in front of the kernel
        (`copy bf16[7,64,1280,576]` in a deviceless v5e compile: most of
        the 3.4 ms a sweep the first form of this log read, PR 38)."""
        @jax.jit
        def run(q, key):
            rows = jax.random.normal(key, cache.shape, dt)

            def layer(i, acc):
                return acc + attention.latent_decode_attention(
                    q, rows, i % n_layers, lo, hi, c, (c + r) ** -0.5,
                    use_kernel=use_kernel,
                ).astype(jnp.float32)
            return jax.lax.fori_loop(
                0, sweeps * n_layers, layer,
                jnp.zeros((b, h, c), jnp.float32),
            )
        return run

    outs, ms = {}, {}
    key = jax.random.PRNGKey(7)
    for form, use_kernel in (("xla", False), ("kernel", True)):
        outs[form] = jax.block_until_ready(sweep(use_kernel)(q, cache))
        # One sweep's time: the difference of 1 + reps sweeps and 1, so the
        # cache's making and the dispatch cancel.
        wall = {}
        for sweeps in (1, 1 + reps):
            fn = timed(use_kernel, sweeps)
            jax.block_until_ready(fn(q, key))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, key))
            wall[sweeps] = time.perf_counter() - t0
        ms[form] = (wall[1 + reps] - wall[1]) / reps * 1e3
    err = _max_err(outs["xla"], outs["kernel"])
    check(err <= tol * n_layers,
          f"latent decode kernel ({n_layers} layers of a stacked cache "
          f"summed) == XLA form (max err {err:.2e})")
    check(float(jnp.max(jnp.abs(outs["kernel"][1]))) == 0.0,
          "latent decode kernel: an empty window's exact zeros")
    log(f"  latent decode: {n_layers} layers x {b} rows x {s_max} slots, "
        f"kernel {ms['kernel']:.3f} ms, XLA form {ms['xla']:.3f} ms a sweep "
        f"(host clock, {reps} sweeps over a cache made in the program"
        + ("" if on_tpu else "; interpreted on the cpu, no device time")
        + ")")


def _paged_cell_shape(attention, geom, dt, tol, on_tpu, reps=5):
    """The paged attention kernel against the XLA gather form on a STACKED
    pool at the serving cell's shape (96 lanes, 3 table columns, 192 pages,
    28 layers; 4 layers in rehearsal): bf16 and int8 pools, every layer,
    dead lanes exact zeros; and the time of one sweep over the layers in
    each form goes to the log."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops.quant import kv_quant

    n_q, n_kv, d, ps = geom["n_q"], geom["n_kv"], geom["d"], geom["page"]
    t, mp, n_pool = 96, 3, 192
    n_layers = 28 if on_tpu else 4
    rng = np.random.default_rng(5)
    # 40 decode rows, 4 prefilling rows of 8 lanes (windows 1 apart, one
    # table row), 24 slack lanes: what an inner step of the cell looks like.
    windows = list(rng.integers(1, mp * ps + 1, size=40))
    windows[0], windows[1] = 1, mp * ps  # shortest and longest
    for _ in range(4):
        w0 = int(rng.integers(8, mp * ps - 8))
        windows += [(w0 + i, i > 0) for i in range(8)]
    perm, nxt = rng.permutation(n_pool - 1), 0
    pt = np.full((t, mp), n_pool, np.int32)  # past the window: unmapped
    vt = np.zeros((t,), np.int32)
    for i, w in enumerate(windows):
        w, follows = w if isinstance(w, tuple) else (int(w), False)
        vt[i] = w
        if follows:
            pt[i] = pt[i - 1]
            continue
        n = min(mp, -(-(w + 7) // ps))  # a prefilling row's last lane
        pt[i, :n], nxt = perm[nxt:nxt + n], nxt + n
    pt, vt = jnp.asarray(pt), jnp.asarray(vt)
    qs = jnp.asarray(rng.standard_normal((t, n_q, d)), dt)
    shape = (n_layers, n_pool, ps, n_kv, d)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    (k8, ks), (v8, vs) = kv_quant(kp), kv_quant(vp)
    # The pool's layout: a token's heads in one row, scales head-major.
    kp, vp, k8, v8 = (a.reshape(*shape[:3], -1) for a in (kp, vp, k8, v8))
    ks, vs = jnp.swapaxes(ks, 2, 3), jnp.swapaxes(vs, 2, 3)

    def sweep(use_kernel):
        @jax.jit
        def run(q, *pool):
            def layer(li, acc):
                return acc + attention.ragged_paged_attention(
                    q, pool[0], pool[1], li, pt, vt, *pool[2:],
                    use_kernel=use_kernel,
                ).astype(jnp.float32)
            return jax.lax.fori_loop(
                0, n_layers, layer, jnp.zeros(q.shape, jnp.float32)
            )
        return run

    for name, pool in (
        ("bf16" if on_tpu else "fp32", (kp.astype(dt), vp.astype(dt))),
        ("int8", (k8, v8, ks, vs)),
    ):
        outs, ms = {}, {}
        for form, fn in (("xla", sweep(False)), ("kernel", sweep(True))):
            outs[form] = jax.block_until_ready(fn(qs, *pool))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(qs, *pool)
            jax.block_until_ready(out)
            ms[form] = (time.perf_counter() - t0) / reps * 1e3
        err = _max_err(outs["xla"], outs["kernel"])
        check(err <= tol * n_layers,
              f"paged attention kernel ({name} stacked pool, {n_layers} "
              f"layers summed) == XLA gather form (max err {err:.2e})")
        check(float(jnp.max(jnp.abs(outs["kernel"][-24:]))) == 0.0,
              f"paged attention kernel ({name} pool): dead lanes exact zeros")
        log(f"  paged attention {name}: {n_layers} layers, kernel "
            f"{ms['kernel']:.3f} ms, XLA gather form {ms['xla']:.3f} ms a "
            f"sweep (host clock, {reps} sweeps"
            + ("" if on_tpu else "; interpreted on the cpu, no device time")
            + ")")


# --------------------------------------------------------------------------
# Phases: the trainer
# --------------------------------------------------------------------------


def _ppo_plan(name, model_cfg, size, fileroot, **overrides):
    from tests import fixtures

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction, MicroBatchSpec
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.experiments.common import PPOMathConfig, build_ppo_math
    from areal_tpu.system.master import ExperimentSaveEvalControl

    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(size["n_prompts"], seed=5)
    cfg = PPOMathConfig(
        experiment_name="chip_smoke",
        trial_name=name,
        actor=ModelAbstraction("random", {"config": model_cfg}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows, "max_length": 128},
        ),
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(
            n=size["group"], max_new_tokens=size["max_new"], temperature=1.0
        ),
        # lr: the first Adam step moves every weight by ~lr, and a bf16
        # master at |w| ~ 0.03 has half an ulp of 6e-5 — 2e-5 (the
        # default) would round most of the update away.
        optimizer=OptimizerConfig(lr=1e-4, warmup_steps_proportion=0.0),
        ppo_kwargs={
            "n_minibatches": 2, "kl_ctl": 0.0,
            # See the module docstring: the verifier's constant -5 must
            # reach the gradient.
            "disable_value": False, "adv_norm": False,
        },
        mb_spec=MicroBatchSpec(max_tokens_per_mb=size["mb_tokens"]),
        batch_size=size["n_prompts"],
        # One batch per epoch: an epoch is a step.
        total_train_epochs=size["steps"],
        ctrl=ExperimentSaveEvalControl(benchmark_steps=size["steps"]),
        fileroot=fileroot,
        train_backend_args={"master_dtype": "bfloat16"},
        **overrides,
    )
    return build_ppo_math(cfg, tok), tok


def _engines(master):
    """(train engine, generator engine, generator Model) of the trial."""
    found = {}
    for w in master.pool.workers:
        for key, m in w.models.items():
            found[key.split("@")[0]] = m  # "actor@0" -> "actor"
    return found["actor"].engine, found["actor_gen"].engine, found["actor_gen"]


def _on_mesh_only(engine, what):
    import jax

    mesh_ids = {d.id for d in engine.mesh.devices.flat}
    where = set()
    for leaf in jax.tree.leaves(engine.params):
        where |= {d.id for d in leaf.sharding.device_set}
    check(where <= mesh_ids,
          f"{what} weights live on chips {sorted(where)} within their mesh "
          f"{sorted(mesh_ids)}")
    return mesh_ids


def phase_trainer(name, model_cfg, size, serving=False, multichip=False):
    import jax
    import numpy as np

    from areal_tpu.apps.main import run_experiment_inproc

    overrides = {}
    if serving:
        # Fewer decode slots than requests.
        overrides["gen_backend_args"] = {
            "max_decode_batch": size["serving_slots"]
        }
    if multichip:
        from areal_tpu.base.topology import ParallelConfig

        overrides.update(
            actor_parallel=ParallelConfig.from_str("f2"),
            gen_parallel=ParallelConfig.from_str("m2"),
            placement={"actor_gen": 1, "reward": 1},
            worker_device_offsets={1: 2},
        )
    seen = {}

    def inspect(master, stage):
        train, gen, gen_model = _engines(master)
        if stage == "built":
            train_ids = _on_mesh_only(train, "trainer")
            gen_ids = _on_mesh_only(gen, "generator")
            live = check_within_hbm(f"{name} built")
            if multichip:
                check(train_ids == {0, 1} and gen_ids == {2, 3},
                      "train f2 on chips 0-1, gen m2 on chips 2-3")
            # Nothing was left on a chip by accident while building: each
            # chip holds its own engines' state and (but for scalars like
            # RNG keys) nothing else.
            own = bytes_per_device(jax.tree.leaves(
                (train.params, train.opt_state, gen.params)
            ))
            stray = {i: live[i] - own[i] for i in live}
            check(max(abs(v) for v in stray.values()) <= 16 << 20,
                  "after build every chip holds exactly its engines' params "
                  f"and optimizer state (stray MB: "
                  f"{ {i: round(v / 1e6, 1) for i, v in stray.items()} })")
            p_b = bytes_per_device(jax.tree.leaves(train.params))
            o_b = bytes_per_device(jax.tree.leaves(train.opt_state))
            check(all(2 * p_b[i] <= o_b[i] <= 2 * p_b[i] + 1024
                      for i in train_ids),
                  "Adam moments are sharded like their params (ZeRO-1): "
                  f"{ {i: round(o_b[i] / 1e9, 2) for i in sorted(train_ids)} }"
                  " GB of optimizer state per trainer chip")
            bq = train.params["blocks"]["bq"]
            check(float(abs(np.asarray(bq, np.float32)).max()) == 0.0,
                  "qkv bias is zero at init (the 'params changed' witness)")
            return
        # ---- stage "done": weights, hand-back, counters, memory
        seen["gen_version"] = int(gen_model.version)
        t_bq = np.asarray(train.params["blocks"]["bq"], np.float32)
        g_bq = np.asarray(gen.params["blocks"]["bq"], np.float32)
        check(np.isfinite(t_bq).all() and float(abs(t_bq).max()) > 0.0,
              f"trainer params changed (max |bq| {abs(t_bq).max():.2e}, "
              "zero at init)")
        check(np.array_equal(t_bq, g_bq),
              "generator holds the trainer's new qkv bias after hand-back")
        for leaf in ("embed", "final_ln"):
            a = np.asarray(train.params[leaf][:64], np.float32)
            b = np.asarray(gen.params[leaf][:64], np.float32)
            check(np.array_equal(a, b),
                  f"generator {leaf}[:64] == trainer's after hand-back")
        _on_mesh_only(train, "trainer")
        _on_mesh_only(gen, "generator")
        check_within_hbm(f"{name} done")
        seen["gen"] = {
            k: int(getattr(gen, k))
            for k in ("decode_compiles", "prefill_dispatches",
                      "lanes_dispatched", "lanes_live", "dead_live_lanes",
                      "cache_copy_bytes", "serving_lane_budget")
        }
        # Every generation program the engine built over the whole trial.
        seen["programs"] = sorted(
            sig[0] if isinstance(sig[0], str) else "static"
            for sig in gen._gen_fns
        )

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as fileroot:
        plan, tok = _ppo_plan(name, model_cfg, size, fileroot, **overrides)
        stats = run_experiment_inproc(plan, tokenizer=tok, inspect=inspect)
    wall = time.time() - t0

    n_seqs = size["n_prompts"] * size["group"]
    check(len(stats) == size["steps"], f"{size['steps']} steps ran")
    for i, st in enumerate(stats):
        loss = st["actor_train/actor_loss"]
        check(np.isfinite(loss) and loss != 0.0,
              f"step {i}: actor loss finite and non-zero ({loss:.4g})")
        check(np.isfinite(st["actor_train/grad_norm"])
              and st["actor_train/grad_norm"] > 0
              and st["actor_train/update_norm"] > 0
              and st["actor_train/quarantined"] == 0,
              f"step {i}: grad norm {st['actor_train/grad_norm']:.4g} and "
              f"update norm {st['actor_train/update_norm']:.4g} finite and "
              "> 0, step not quarantined")
        # The trainer re-scores the generator's tokens under the same
        # weights: a ratio far from 1 means the two disagree about them.
        iw = st["actor_train/importance_weight"]
        check(0.8 < iw < 1.25,
              f"step {i}: trainer/generator importance weight {iw:.4f} ~ 1")
        # Graded by the math verifier: a random model is always wrong.
        check(st["actor_train/task_reward"] == -5.0,
              f"step {i}: rewards came from the verifier (all wrong: -5)")
        n_tok = st["actor_train/n_response_tokens"]
        check(0 < n_tok <= n_seqs * size["max_new"],
              f"step {i}: {int(n_tok)} response tokens generated "
              f"(<= {n_seqs} x {size['max_new']})")
        no_eos = st["actor_train/no_eos_ratio"]
        check(0.0 <= no_eos <= 1.0, f"step {i}: no-EOS ratio {no_eos:.2f}")
        if no_eos == 1.0:
            check(n_tok == n_seqs * size["max_new"],
                  f"step {i}: no sequence hit EOS, so all ran to the cap")
        for mfc in ("actor_gen", "actor_train"):
            for key in ("perf/time_s", "perf/tflops"):
                check(f"{mfc}/{key}" in st, f"step {i}: {mfc}/{key} in stats")
            if jax.default_backend() != "cpu":
                u = st.get(f"{mfc}/perf/mfu")
                check(u is not None and 0.0 < u < 1.0,
                      f"step {i}: {mfc}/perf/mfu = {u}")
    check(seen["gen_version"] == size["steps"],
          f"generator version counter == {size['steps']} steps")
    g = seen["gen"]
    log(f"  generation programs built over the trial: {seen['programs']}")
    if serving:
        check(g["lanes_dispatched"] > 0
              and g["serving_lane_budget"] > 0,
              f"generate() took the paged ragged serving chunk "
              f"({g['lanes_dispatched']} lanes dispatched, "
              f"{g['lanes_live']} live, budget {g['serving_lane_budget']})")
        check(g["prefill_dispatches"] == 0,
              "prefill rode the serving chunk (prefill_dispatches == 0)")
        check(g["dead_live_lanes"] == 0, "dead_live_lanes == 0")
        check(g["cache_copy_bytes"] == 0, "paged pool: no cache grow copies")
        check(seen["programs"].count("serving_chunk") == 1
              and g["decode_compiles"] == 0,
              "one serving chunk program for the whole trial, none compiled "
              "in the last (warm) step")
        check(not {"prefill_pages", "paged_inflight", "inflight",
                   "prefill_slots"} & set(seen["programs"]),
              "no two-program admit / dense inflight program was built")
    else:
        check(g["lanes_dispatched"] == 0 and g["prefill_dispatches"] == 0
              and "serving_chunk" not in seen["programs"],
              "generate() took the static decode program")
    step_s = [st["time/step_s"] for st in stats]
    log(
        f"phase {name}: wall {wall:.1f}s = set-up {wall - sum(step_s[1:]):.1f}s"
        f" (build + first step incl. compile {step_s[0]:.1f}s) + steady "
        f"steps {[round(x, 2) for x in step_s[1:]]} s; last step gen "
        f"{stats[-1]['actor_gen/perf/time_s']:.2f}s train "
        f"{stats[-1]['actor_train/perf/time_s']:.2f}s"
        + (f" cross-mesh weight hand-back send "
           f"{stats[-1]['transfer/param_send_s']:.2f}s recv "
           f"{stats[-1]['transfer/param_recv_s']:.2f}s "
           f"({stats[-1]['transfer/param_bytes'] / 1e9:.2f} GB through the "
           "host)" if "transfer/param_bytes" in stats[-1] else "")
    )
    release_device_memory(name)


# --------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy-size run on the CPU to debug this script; not a chip run",
    )
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help=f"comma-separated subset of {PHASES} (all by default)",
    )
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")

    import jax

    backend = jax.default_backend()
    if args.cpu_rehearsal:
        if backend != "cpu":
            raise SystemExit(
                f"chip_smoke: --cpu-rehearsal wants JAX_PLATFORMS=cpu, "
                f"found backend {backend!r}"
            )
    elif backend != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax.default_backend() is "
            f"{backend!r} (devices: {jax.devices()}).  Nothing was run."
        )
    on_tpu = backend == "tpu"

    from areal_tpu.base import compilation_cache
    from areal_tpu.models.config import qwen2_config, tiny_config

    cache_dir = compilation_cache.enable()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    log(
        f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']} jax={jax.__version__} "
        f"cache_dir={cache_dir} "
        f"(entries at start: {len(os.listdir(cache_dir))})"
    )
    if on_tpu:
        model_cfg = qwen2_config("1.5b", param_dtype="bfloat16")
        size = dict(n_prompts=8, group=4, max_new=256, mb_tokens=8192,
                    steps=3, serving_slots=8)
    else:
        log("CPU REHEARSAL at toy size — not evidence about the chip")
        model_cfg = tiny_config(param_dtype="float32")
        size = dict(n_prompts=4, group=2, max_new=12, mb_tokens=4096,
                    steps=2, serving_slots=2)
    geom = dict(
        n_q=model_cfg.n_q_heads, n_kv=model_cfg.n_kv_heads,
        d=model_cfg.head_dim, flash_s=256 if on_tpu else 128,
        page=128 if on_tpu else 8,
    )
    log(
        f"model: {model_cfg.n_layers} layers, hidden {model_cfg.hidden_dim}, "
        f"{model_cfg.n_q_heads}/{model_cfg.n_kv_heads} heads x "
        f"{model_cfg.head_dim}, vocab {model_cfg.vocab_size}; "
        f"{size['n_prompts']} prompts x {size['group']}, <= "
        f"{size['max_new']} new tokens, {size['steps']} steps per trainer "
        "phase (the first is warm-up)"
    )

    walls = {}
    for phase in phases:
        t0 = time.time()
        log(f"phase {phase}: start")
        if phase == "kernels":
            phase_kernels(geom, on_tpu)
        elif phase == "static":
            phase_trainer("static", model_cfg, size)
        elif phase == "serving":
            phase_trainer("serving", model_cfg, size, serving=True)
        elif phase == "multichip":
            if device["count"] < 4:
                log(f"multichip: skipped ({device['count']} chips)")
                continue
            # The serving plane again: the page pool under a model axis.
            phase_trainer("multichip", model_cfg, dict(size, steps=2),
                          serving=True, multichip=True)
        walls[phase] = round(time.time() - t0, 1)
        log(f"phase {phase}: passed in {walls[phase]}s")
    log(
        f"all phases passed: {walls}; cache entries at end: "
        f"{len(os.listdir(cache_dir))}"
    )
    sys.stdout.flush()
    if not on_tpu:
        log("rehearsal finished; no result line (platform=cpu)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
