"""MiniCPM-SALA's controls, on the chip at the published widths
(`benchmark/configs/minicpm-sala-l4-v8.json`).

(1) THE THREE FORMS of the selected attention over one packed row of the
cell's longest sequence (13,312 tokens, 32 / 2 heads of 128), each timed
alone, forward and forward + backward: the flash kernels under the block
choice (`block_sparse.packed_attention(use_flash=True)`, what the programs
run on a TPU), dense under the mask in chunks of queries (`use_flash=False`,
what they run elsewhere and the kernels' oracle) and the chosen blocks
gathered with a key head's 16 query heads as the matmul's rows
(`gathered_attention` below, the published kernel's form, which ran at a
thirteenth of the mask form's speed and lives here alone); beside them the
selection alone (compressed keys, scores, top-k) and the repo's flash kernel
over the same row with NO selection (what a dense layer would cost).
`tiles`: the share of tiles the cell's own choice leaves empty
(`empty_tiles`).

(2) THE SECOND READINGS of the tolerances: the plain reference with its
Lightning state rounded to bfloat16 at every step against the reference
proper over one sequence (mean and max |log-prob difference|), and the
static program itself (`references.minicpm_sala.check_generator`) as it is
— the first readings — and with the decode step's state rounded to
bfloat16, which the state limit has to REFUSE.

    chiprun -- python3 scripts/sala_controls.py [forms|tiles|tolerances|all] [n]

Writes chiprun_out/sala_controls.json; one line a reading.  A reading is
evidence only from a TPU run."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import lightning  # noqa: E402
from areal_tpu.models import transformer as tfm  # noqa: E402
from areal_tpu.ops import block_sparse  # noqa: E402
from areal_tpu.ops.attention import packed_attention  # noqa: E402
from benchmark import files  # noqa: E402
from benchmark.references import minicpm_sala as ref  # noqa: E402
from benchmark.run import model_config  # noqa: E402


def _ms(fn, *args, reps=3):
    """Milliseconds a call, the median of `reps` after one to compile."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(times))


def gathered_attention(q, k, v, segment_ids, sz):
    """The form that was measured and not taken: `block_sparse.
    packed_attention`'s selection, then each query's chosen blocks GATHERED
    and attended with a key head's query heads as the matmul's rows (it
    selects in every sequence, whatever `dense_len`).  One row at a time,
    chunks of `QUERY_CHUNK` queries, a `jax.checkpoint` a chunk."""
    bs, chunk = sz.block, block_sparse.QUERY_CHUNK

    def row(q, k, v, seg):
        s, hq, d = q.shape
        n_kv = k.shape[1]
        pos, start, _ = block_sparse._segments(seg)
        kc, knum, kseg = block_sparse.compress_row(k, pos, seg, sz)
        n_blocks = kc.shape[0] // sz.pool + 2
        n = min(sz.topk, n_blocks)
        parts = tuple(x.reshape(s // chunk, chunk, *x.shape[1:]) for x in (
            q, seg, pos, start, jnp.arange(s, dtype=jnp.int32)))

        @jax.checkpoint
        def attend(k, v, xs):
            qc, segc, posc, startc, idxc = xs
            chosen = block_sparse._select_chunk(
                jax.lax.stop_gradient(qc), kc, knum, kseg, segc, posc,
                startc, sz)  # [T, Hkv, NBg] over global blocks
            c0 = (startc + sz.kernel - 1) // sz.stride
            first = startc - (c0 // sz.pool) * bs  # global block 0's index
            order = jnp.argsort(~chosen, axis=-1, stable=True)[..., :n]
            live = jnp.take_along_axis(chosen, order, axis=-1)
            starts = first[:, None, None] + order * bs
            rows = starts[..., None] + jnp.arange(bs)  # [T, Hkv, n, bs]
            keep = live[..., None] & (rows <= idxc[:, None, None, None])
            rows = jnp.clip(rows, 0, s - 1).reshape(chunk, n_kv, n * bs)
            head = jnp.arange(n_kv)[None, :, None]
            kg, vg = k[rows, head], v[rows, head]  # [T, Hkv, n * bs, d]
            logits = jnp.einsum(
                "tgrd,tgsd->tgrs", qc.reshape(chunk, n_kv, hq // n_kv, d), kg,
                preferred_element_type=jnp.float32) * d**-0.5
            logits = jnp.where(
                keep.reshape(chunk, n_kv, 1, n * bs), logits,
                block_sparse.NEG_INF)
            out = jnp.einsum(
                "tgrs,tgsd->tgrd",
                jax.nn.softmax(logits, axis=-1).astype(v.dtype), vg,
                preferred_element_type=jnp.float32)
            return out.reshape(chunk, hq, d).astype(q.dtype)

        return jax.lax.map(
            lambda xs: attend(k, v, xs), parts).reshape(s, hq, d)

    return jax.vmap(row)(q, k, v, segment_ids)


def forms(cfg, n):
    """The selected attention alone, one row of `n` tokens."""
    sz = block_sparse.Sizes.of(cfg)
    ks = jax.random.split(jax.random.PRNGKey(55), 3)
    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    q = jax.random.normal(ks[0], (1, n, cfg.n_q_heads, cfg.head_dim), dtype)
    k = jax.random.normal(ks[1], (1, n, cfg.n_kv_heads, cfg.head_dim), dtype)
    v = jax.random.normal(ks[2], (1, n, cfg.n_kv_heads, cfg.head_dim), dtype)
    seg = jnp.ones((1, n), jnp.int32)
    out = {"n_tokens": n}

    attention = {
        "mask": lambda q, k, v: block_sparse.packed_attention(
            q, k, v, seg, sz, use_flash=False)[0],
        "gather": lambda q, k, v: gathered_attention(q, k, v, seg, sz),
        "kernel": lambda q, k, v: block_sparse.packed_attention(
            q, k, v, seg, sz, use_flash=True)[0],
    }

    def fwd(form):
        return jax.jit(attention[form])

    def both(form):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            attention[form](q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))

    dense = jax.jit(lambda q, k, v: packed_attention(q, k, v, seg, causal=True))
    dense_both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(packed_attention(
        q, k, v, seg, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)))
    never = block_sparse.Sizes(**{**sz.__dict__, "dense_len": 10**9})
    unselected = jax.jit(lambda q, k, v: block_sparse.packed_attention(
        q, k, v, seg, never, use_flash=False)[0])
    select = jax.jit(lambda q, k: jax.vmap(
        lambda q, k, seg: block_sparse._row_selection(
            q, k, seg, sz, block_sparse.QUERY_CHUNK)[0])(q, k, seg))
    out["select_ms"] = _ms(select, q, k)
    print("forms, select_ms", out["select_ms"], flush=True)
    for name, fn in (
        ("kernel_fwd_ms", fwd("kernel")), ("kernel_fwd_bwd_ms", both("kernel")),
        ("mask_fwd_ms", fwd("mask")), ("mask_fwd_bwd_ms", both("mask")),
        ("gather_fwd_ms", fwd("gather")), ("gather_fwd_bwd_ms", both("gather")),
        ("mask_without_selection_fwd_ms", unselected),
        ("flash_dense_fwd_ms", dense), ("flash_dense_fwd_bwd_ms", dense_both),
    ):
        try:
            out[name] = _ms(fn, q, k, v)
        except Exception as e:  # a form the chip refuses is a reading too
            out[name] = f"failed: {type(e).__name__}: {str(e)[:200]}"
        print("forms,", name, out[name], flush=True)
    want = fwd("mask")(q, k, v).astype(jnp.float32)
    for form in ("gather", "kernel"):
        key = f"mask_vs_{form}_max_abs"
        out[key] = float(
            jnp.abs(want - fwd(form)(q, k, v).astype(jnp.float32)).max())
        print("forms, mask against", form, "max abs", out[key], flush=True)
    return out


def empty_tiles(cfg, config, n, tile=128):
    """What the cell's choice would leave a tile-level skip: layer 0's q
    and k from the cell's weights over one row of `n` random bytes, the
    selection, and on the host the share of (q tile, k tile, key head)
    triples at or below the diagonal in which no query of the tile chose
    any block the tile's keys lie in."""
    sz = block_sparse.Sizes.of(cfg)
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = jnp.asarray(
        np.random.default_rng(55).integers(0, 256, n).astype(np.int32))[None]
    seg = jnp.ones((1, n), jnp.int32)

    @jax.jit
    def choice(params):
        blk = {name: params["blocks"][name][0] for name in (
            "ln1", "wq", "wk", "wv", "q_norm", "k_norm")}
        x = tfm._embed(params, cfg, tokens, jnp.arange(n)[None])
        q, k, _ = tfm._qkv(
            tfm._norm(x, blk["ln1"], None, cfg), blk, cfg, None, None)
        return block_sparse._row_selection(
            q[0], k[0], seg[0], sz, block_sparse.QUERY_CHUNK)[:2]

    chosen, key_block = (np.asarray(x) for x in choice(params))
    nt = n // tile
    # [q tile, key head, block]: some query of the tile chose the block
    any_q = chosen.reshape(nt, tile, *chosen.shape[1:]).any(axis=1)
    blocks = key_block.reshape(nt, tile)
    live = np.zeros((nt, nt, chosen.shape[1]), bool)
    for j in range(nt):
        live[:, j] = any_q[:, :, np.unique(blocks[j])].any(axis=-1)
    below = np.tril(np.ones((nt, nt), bool))[..., None]
    out = {
        "n_tokens": n, "tile": tile,
        "blocks_a_query": float(chosen.sum(-1).mean()),
        "empty_tile_share": float(1 - live[below[..., 0]].mean()),
    }
    print("tiles,", out, flush=True)
    return out


def tolerances(cfg, config, n):
    params = tfm.init_params(
        cfg, jax.random.PRNGKey(config["benchmark"]["weights_seed"]))
    tokens = np.random.default_rng(55).integers(0, 256, n).astype(np.int32)
    padded = ref._padded(tokens)
    cpu = jax.default_backend() == "cpu"
    out = {"n_tokens": n, "tolerance": {
        **ref.TOLERANCE,
        **(ref.STATE_TOLERANCE_FP32 if cpu else ref.STATE_TOLERANCE)}}
    want, _ = ref._next_token_logprobs(params, cfg, padded, n)
    got, _ = ref._next_token_logprobs(
        params, cfg, padded, n, ref.LOWER_PRECISION)
    d = np.abs(got[: n - 1] - want[: n - 1])
    out["reference_state_bf16"] = {
        "mean_abs": float(d.mean()), "max_abs": float(d.max())}
    print("reference, state in bfloat16, against the reference proper",
          out["reference_state_bf16"], flush=True)

    readings, problems = ref.check_generator(params, cfg, tokens)
    out["static_program"] = {**readings, "problems": problems}
    print("static program", readings, problems or "inside", flush=True)

    step = lightning.lightning_step_jnp

    def rounded(state, q, k, v):
        state, y = step(state, q, k, v)
        return jax.lax.reduce_precision(state, 8, 7), y

    lightning.lightning_step_jnp = rounded
    try:
        readings, problems = ref.check_generator(params, cfg, tokens)
    finally:
        lightning.lightning_step_jnp = step
    out["static_program_state_bf16"] = {**readings, "problems": problems}
    print("static program, decode state rounded to bfloat16", readings,
          "REFUSED by" if problems else "INSIDE (the limit does not hold)",
          problems, flush=True)
    out["refused"] = bool(problems)
    return out


def main():
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 13312
    config = files.load_json("configs", "minicpm-sala-l4-v8.json")
    cfg = model_config(config)
    out = {"platform": jax.default_backend()}
    if what in ("forms", "all"):
        out["forms"] = forms(cfg, n)
    if what in ("tiles", "all"):
        out["tiles"] = empty_tiles(cfg, config, n)
    if what in ("tolerances", "all"):
        out["tolerances"] = tolerances(cfg, config, n)
    os.makedirs("chiprun_out", exist_ok=True)
    path = "chiprun_out/sala_controls.json"
    if os.path.exists(path):  # an earlier part of the same call
        with open(path) as f:
            out = {**json.load(f), **out}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    # The control has to be refused: exit code 0 when it is.
    return 0 if out.get("tolerances", {}).get("refused", True) else 1


if __name__ == "__main__":
    sys.exit(main())
