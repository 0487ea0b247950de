"""What decides `correct`.

Per timed step, what `chip_smoke.py` checks of a step: a finite non-zero
loss, gradient and update norms above zero, the step not quarantined, the
trainer's importance weight of the generator's tokens near 1, rewards
that came from the verifier, token counts that add up.  Over the run: the
route the cell's file declares, no compilation inside the window (where
every step has the same batch), the generator holding the trainer's
weights after the last hand-back.  And,
outside the window, the architecture's plain reference
(`benchmark/references/`) against the log-probabilities the generator
returned and the ones the trainer recomputes.
"""

import numpy as np

from benchmark import files

REFERENCE_SEQS = 2


def reference_check(obs, rollout):
    """Teacher-force REFERENCE_SEQS rollout sequences (the first responses
    to the prompt of median length: the same shapes, set-up time and
    memory whatever the seed) through the plain reference and compare with
    the generator's returned log-probs and the trainer's recomputed ones."""
    import time

    import jax

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample

    t_start = time.monotonic()
    run = obs.run
    ref = files.load_module("references", run.config["benchmark"]["reference"])
    # On the CPU the generator computes in fp32; the trainer keeps bf16.
    tols = {"generator": ref.TOLERANCE, "trainer": ref.TOLERANCE,
            "gen_vs_trainer": ref.TOLERANCE}
    if jax.default_backend() == "cpu":
        tols["generator"] = ref.TOLERANCE_FP32
    by_len = sorted(
        range(rollout.bs),
        key=lambda i: (sum(rollout.seqlens["packed_input_ids"][i]), i),
    )
    pick = by_len[rollout.bs // 2]
    one = rollout.select_idx([pick])
    lens = [int(l) for l in one.seqlens["packed_input_ids"][0]][:REFERENCE_SEQS]
    n_tok = sum(lens)
    toks = np.asarray(one.data["packed_input_ids"])[:n_tok]
    pmask = np.asarray(one.data["prompt_mask"])[:n_tok]
    gen_lp = np.asarray(one.data["packed_logprobs"], np.float32)
    # The trainer re-scores just these sequences (a whole group's forward
    # pass would set the run's HBM peak).
    trainer = obs.interfaces["actor"].inference(
        obs.models["actor"],
        SequenceSample(
            keys={"packed_input_ids"}, ids=list(one.ids),
            seqlens={"packed_input_ids": [lens]},
            data={"packed_input_ids": toks},
        ),
        MicroBatchSpec(max_tokens_per_mb=8192),
    )
    trn_lp = np.asarray(trainer.data["logprobs"], np.float32)
    trn_lens = [int(l) for l in trainer.seqlens["logprobs"][0]]
    params = obs.models["actor_gen"].engine.get_params()
    report = {"n_tokens": 0}
    diffs = {"generator": [], "trainer": [], "gen_vs_trainer": []}
    off = lp_off = t_off = 0
    for j, length in enumerate(lens):
        seq = toks[off: off + length]
        n_prompt = int(pmask[off: off + length].sum())
        want = ref.next_token_logprobs(params, run.model_cfg, seq)
        # Position t scores token t + 1; responses start at n_prompt.
        resp = slice(n_prompt - 1, length - 1)
        g = gen_lp[lp_off: lp_off + length - 1][resp]
        t = trn_lp[t_off: t_off + trn_lens[j]][: length - 1][resp]
        diffs["generator"].append(np.abs(g - want[resp]))
        diffs["trainer"].append(np.abs(t - want[resp]))
        diffs["gen_vs_trainer"].append(np.abs(g - t))
        report["n_tokens"] += length - n_prompt
        off += length
        lp_off += length - 1
        t_off += trn_lens[j]
    ok = report["n_tokens"] > 0
    for name, d in diffs.items():
        d = np.concatenate(d)
        report[f"{name}_mean_abs"] = float(d.mean())
        report[f"{name}_max_abs"] = float(d.max())
        ok = ok and bool(
            np.isfinite(d).all() and d.mean() <= tols[name]["mean_abs"]
            and d.max() <= tols[name]["max_abs"]
        )
    report["ok"] = ok
    report["seconds"] = round(time.monotonic() - t_start, 2)
    return report


# --------------------------------------------------------------------------
# The weight check: any tree, on the device, scalars only
# --------------------------------------------------------------------------

_UINT = {1: "uint8", 2: "uint16", 4: "uint32"}


def _leaf_sums(x, dtype):
    """[plain sum, position-weighted sum] of a leaf's bits as wrapping
    uint32.  Both are sums of integers modulo 2**32, so they do not depend
    on the order of addition: the same leaf gives the same pair under any
    sharding (a floating sum would not).  The plain sum changes with any
    single flipped bit; the second multiplies each element's bits by an
    odd hash of its flat position, so two swapped rows show.  The position
    is built from one iota per dimension, not by flattening, so a sharded
    leaf is reduced where it lies and only the two scalars cross chips."""
    import jax
    import jax.numpy as jnp

    x = x.astype(dtype)  # the hand-back's cast; a no-op for equal types
    if x.dtype.itemsize not in _UINT:
        raise TypeError(f"no bit sum for a leaf of dtype {x.dtype}")
    bits = jax.lax.bitcast_convert_type(
        x, _UINT[x.dtype.itemsize]
    ).astype(jnp.uint32)
    pos = jnp.zeros((), jnp.uint32)
    stride = 1
    for axis in reversed(range(x.ndim)):
        pos = pos + jax.lax.broadcasted_iota(
            jnp.uint32, x.shape, axis
        ) * jnp.uint32(stride % 2**32)
        stride *= x.shape[axis]
    h = pos * jnp.uint32(0x9E3779B1)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA77)
    h = (h ^ (h >> 13)) | jnp.uint32(1)
    return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                      jnp.sum(bits * h, dtype=jnp.uint32)])


_PROGRAMS = {}  # (leaf avals and shardings, dtypes) -> compiled executable


def drop_programs():
    """Unload the compiled weight-sum programs.  A loaded executable takes
    device memory (2.1 MB for the four-chip tree: chip run, PR 25), so the
    sums taken before the window opens would otherwise sit in every
    later `peak_hbm_gb`; the pass after the window loads its program
    again from the persistent compile cache."""
    _PROGRAMS.clear()


def tree_sums(tree, like=None):
    """{path: (sum, weighted sum)} of every leaf of a parameter tree, as
    Python ints: one compiled program per tree (shapes, dtypes,
    shardings), held until `drop_programs`, computed where the leaves
    lie; only the scalars are fetched, explicitly.  `like`: a tree whose
    leaves give the dtype each leaf is cast to first, by path (the
    generator's, for the trainer's tree: the hand-back casts the master
    weights to the generator's compute type); a leaf it lacks keeps its
    own."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    want = {} if like is None else {
        jax.tree_util.keystr(p): x.dtype
        for p, x in jax.tree_util.tree_flatten_with_path(like)[0]
    }
    leaves = [x for _, x in flat]
    dtypes = tuple(
        str(want.get(p, x.dtype)) for p, x in zip(paths, leaves)
    )
    key = (tuple((x.shape, str(x.dtype), x.sharding) for x in leaves), dtypes)
    if key not in _PROGRAMS:
        # Compiled ahead of time, so that the executable is this dict's
        # to drop and not jit's to keep.
        _PROGRAMS[key] = jax.jit(
            lambda xs: [_leaf_sums(x, d) for x, d in zip(xs, dtypes)]
        ).lower(leaves).compile()
    # No leaf may come to the host (5.9 GB on four chips); an implicit
    # transfer raises here, the explicit fetch of the scalars does not.
    with jax.transfer_guard_device_to_host("disallow"):
        sums = jax.device_get(_PROGRAMS[key](leaves))
    return {p: (int(s[0]), int(s[1])) for p, s in zip(paths, sums)}


def matmul_leaves(tree):
    """Paths of the leaves that are matrices of a matmul: two or more
    dimensions, not counting the leading layer axis of a leaf under
    `blocks` (the program stacks its layers there, as the references
    read them).  Norm scales and biases are left out: a bf16 scale of 1.0
    may not move under a small Adam step."""
    import jax

    out = set()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        stacked = getattr(path[0], "key", None) == "blocks"
        if x.ndim - int(stacked) >= 2:
            out.add(jax.tree_util.keystr(path))
    return out


def handback_problems(train_sums, gen_sums, gen_sums_before, matmul):
    """What is wrong with the weights after the last hand-back, as text;
    empty means the generator holds the trainer's weights and training
    moved them.  The three arguments are `tree_sums` of the trainer's tree
    (cast as the hand-back casts it), of the generator's, and of the
    generator's taken before the window opened; `matmul` the paths that
    training has to have moved."""
    out = []
    only = sorted(set(train_sums) ^ set(gen_sums))
    if only:
        out.append(
            "leaves on one side only: "
            + ", ".join(f"{p} ({'trainer' if p in train_sums else 'generator'})"
                        for p in only)
        )
    differ = sorted(p for p in set(train_sums) & set(gen_sums)
                    if train_sums[p] != gen_sums[p])
    if differ:
        out.append("generator weights differ from the trainer's after the "
                   f"last hand-back in {len(differ)} of {len(gen_sums)} "
                   f"leaves: {', '.join(differ)}")
    if gen_sums_before is None:
        out.append("no weight sums were taken before the window")
        return out
    still = sorted(p for p in matmul & set(gen_sums)
                   if gen_sums[p] == gen_sums_before.get(p))
    if still:
        out.append("training did not move the generator's weights in: "
                   + ", ".join(still))
    return out


def handback_check(train, gen, gen_sums_before):
    """`handback_problems` for the two engines, and what it compared."""
    import time

    t0 = time.monotonic()
    gen_params = gen.get_params()
    gen_sums = tree_sums(gen_params)
    train_sums = tree_sums(train.get_params(), like=gen_params)
    problems = handback_problems(
        train_sums, gen_sums, gen_sums_before, matmul_leaves(gen_params)
    )
    return {
        "ok": not problems, "problems": problems, "leaves": len(gen_sums),
        "seconds": round(time.monotonic() - t0, 3),
    }


def check_step(i, step, run):
    st = step["stats"]
    n_seqs = run.traffic["n_prompts"] * run.traffic["group"]
    max_new = run.traffic["max_new_tokens"]
    out = []

    def need(cond, what):
        if not cond:
            out.append(f"step {i}: {what}")

    loss = st["actor_train/actor_loss"]
    need(np.isfinite(loss) and loss != 0.0, f"actor loss {loss}")
    need(np.isfinite(st["actor_train/grad_norm"])
         and st["actor_train/grad_norm"] > 0
         and st["actor_train/update_norm"] > 0,
         "gradient or update norm not above zero")
    need(st["actor_train/quarantined"] == 0, "step quarantined")
    iw = st["actor_train/importance_weight"]
    need(0.8 < iw < 1.25, f"importance weight {iw} not within 0.8-1.25")
    need(st["actor_train/task_reward"] == -5.0,
         "rewards did not come from the verifier (a random model scores -5)")
    n_gen = run.gen_tokens(step)
    need(len(step["seq_lens"]) == n_seqs, f"{len(step['seq_lens'])} sequences")
    need(st["actor_train/n_response_tokens"] == n_gen,
         f"trainer saw {st['actor_train/n_response_tokens']} response "
         f"tokens, generator returned {n_gen}")
    if run.traffic.get("eos_reachable"):
        need(0 < n_gen <= n_seqs * max_new, f"{n_gen} generated tokens")
    else:  # no reachable EOS: every row runs to its budget
        need(st["actor_train/no_eos_ratio"] == 1.0
             and n_gen == n_seqs * max_new,
             f"{n_gen} generated tokens, not {n_seqs} x {max_new}")
    return out


def check_route(run):
    out = []
    route = run.cell["route"]
    programs = set(run.programs)
    # One batch, every step the same: the warm-up step built every program.
    # Traffic with new batches builds programs inside the window; the cost
    # is reported (`compiles_in_window`), the route is still checked.
    fixed = run.traffic.get("batches", 1) == 1
    for i, step in enumerate(run.steps):
        g = step["gen"]
        if route == "serving":
            if not (g["lanes_dispatched"] > 0 and g["serving_lane_budget"] > 0
                    and g["prefill_dispatches"] == 0
                    and g["dead_live_lanes"] == 0
                    and g["cache_copy_bytes"] == 0):
                out.append(f"step {i}: not the ragged serving chunk: {g}")
        elif g["lanes_dispatched"] != 0 or g["prefill_dispatches"] != 0:
            out.append(f"step {i}: not the static decode program: {g}")
        if fixed and g["decode_compiles"] != 0:
            out.append(f"step {i}: {g['decode_compiles']} decode compiles")
    if route == "serving":
        if "serving_chunk" not in programs or programs & {
            "prefill_pages", "paged_inflight", "inflight", "prefill_slots"
        } or (fixed and run.programs.count("serving_chunk") != 1):
            out.append(f"serving route built programs {run.programs}")
    elif "serving_chunk" in programs:
        out.append(f"static route built programs {run.programs}")
    if fixed and run.compiles_in_window:
        out.append(f"{run.compiles_in_window} compilations inside the window")
    return out


def check_run(run):
    """Every problem found, as text; empty means `correct`."""
    out = []
    if len(run.steps) < 2:
        out.append(f"{len(run.steps)} timed steps")
    for i, step in enumerate(run.steps):
        out += check_step(i, step, run)
    out += check_route(run)
    handback = getattr(run, "handback", None)
    if handback is None:
        out.append("the weights were not compared after the last hand-back")
    else:
        out += handback["problems"]
    if not (run.reference or {}).get("ok"):
        out.append(f"reference check failed: {run.reference}")
    return out
