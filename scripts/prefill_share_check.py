"""The static program with a group's prompt prefilled once
(`GeneratorEngine._prefill_distinct`) against the program that prefills
every row, at a benchmark cell's shape and published widths.

    chiprun -- python3 scripts/prefill_share_check.py run mellum2,lfm2,sala
    python3 scripts/prefill_share_check.py compile lfm2        # no chip

Three cells prefill in waves; `q1p5b` (`q1p5b-train-longprompt`) fits ONE
prefill and shares because its prompt bucket is longer than its decode budget
(`GeneratorEngine._shared_rows`): four rows in the matmuls where the unshared
program has sixteen, so there the rows' bits may part (a matmul rounds a row
by the rows beside it) and `run` says by how much instead of `equal True`.

`run` (the chip, about a minute and a half a cell): one engine over random
weights of the cell's seed, the cell's count of prompts x its group, both
programs on one key, twice each.  A line a call (seconds, `prefill_rows` of
`prefill_rows_requested`), then what has to hold: tokens, log-probs and every
leaf of the `with_cache` cache EQUAL, bit for bit — the step at which each
row's tokens part where they do not, and the rows whose prompt slots differ
(prefill) as against those that part later (the decode loop).  Exit code 1
unless every cell that prefills in waves is equal, and every other cell's
prompt slots lie within 5% of their mean size (bf16 sums in another order).

`compile` (a described v5e, no chip, no time in it): both programs compiled
with XLA:TPU, their temporaries, and the compiled DECODE LOOP's body with
everything it calls compared line by line (names' numbers and metadata left
out).  The loop's text is the same in both programs; what XLA:TPU makes of
it follows what stands in front of it — with the prefill inlined into the
entry computation lfm2's loop kept other operands in VMEM, rounded a matmul
otherwise and its rows sampled apart from step 19 on (PR 61) — so a change
to the prefill's form is read here first: `differ` should stay in the tens
(scratch offsets and cycle estimates), `by memory space` near zero.
"""
import argparse
import collections
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CELLS = {
    "mellum2": ("mellum2-12b-a2.5b-l4-e16", "rollout32-ctx4k-512"),
    "lfm2": ("lfm2-8b-a1b-e8", "rollout32-ctx4k-512"),
    "sala": ("minicpm-sala-l4-v8", "rollout8-ctx9k-14k-256"),
    "q1p5b": ("qwen2.5-math-1.5b", "long-prompts-short-answers"),
}


def cell(name, toy):
    """(ModelConfig, the config file, the cell's prompt lengths longest
    first, the group, new tokens) of a cell; `toy`: the files' toy sizes
    and, where the cell's own batch goes in waves, a wave budget that the
    toy batch passes too."""
    from areal_tpu.engines import generator
    from benchmark import files, run as bench_run
    from benchmark.traffic.math_prompts import quantile_lengths

    config = files.load_json("configs", CELLS[name][0] + ".json")
    traffic = files.load_json("traffic", CELLS[name][1] + ".json")

    def lengths(t):
        return sorted(
            quantile_lengths(t["prompt_len"], t["n_prompts"]), reverse=True)

    in_waves = (
        traffic["n_prompts"] * traffic["group"]
        * generator.bucket_len(lengths(traffic)[0])
        > generator.PREFILL_WAVE_TOKENS)
    if toy:
        config, traffic = bench_run.toy(config, traffic)
        if in_waves:
            generator.PREFILL_WAVE_TOKENS = 256
    return (bench_run.model_config(config), config, lengths(traffic),
            traffic["group"], traffic["max_new_tokens"])


# ----------------------------------------------------------------- the chip


def run(name, toy):
    import jax
    import numpy as np

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine, bucket_len
    from areal_tpu.system.worker import _random_init_fn
    from benchmark import run as bench_run

    cfg, config, lens, n, new = cell(name, toy)
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    params = _random_init_fn(cfg, mesh)(
        np.uint32(bench_run.trial_seed(config, 7)))
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(3, min(cfg.vocab_size, 250), size=l).astype(np.int32)
        for l in lens]
    rows = [p for p in prompts for _ in range(n)]
    src = [r - r % n for r in range(len(rows))]
    sp = bucket_len(max(lens))
    eng = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size,
        max_decode_batch=len(rows), donation_safe_swap=False)
    g = GenerationHyperparameters(n=1, max_new_tokens=new, temperature=1.0)
    out = {}
    for form, s in (("own", None), ("shared", src)) * 2:
        t0 = time.monotonic()
        toks, logps, gen_len, cache = eng.static_rollout(
            rows, g, jax.random.PRNGKey(3), with_cache=True, src=s)
        jax.block_until_ready(cache)
        st = eng.last_pool_stats
        print(f"[share] {name} {form}: {time.monotonic() - t0:.3f} s, "
              f"prefilled {st['prefill_rows']} of "
              f"{st['prefill_rows_requested']} rows", flush=True)
        eng.last_pool_stats = {}
        if form not in out:  # the second round is for its seconds alone
            out[form] = (toks, logps, gen_len, *(
                np.asarray(x.astype("float32"))
                for x in jax.tree.leaves(cache)))
        del cache
    own, shared = out["own"], out["shared"]
    equal = all(np.array_equal(a, b) for a, b in zip(own, shared))
    part = [int(np.argmax(a != b)) if (a != b).any() else -1
            for a, b in zip(own[0], shared[0])]
    prompt = sorted({
        r for a, b in zip(own[3:], shared[3:]) if a.ndim == 5 and a.shape[2] > sp
        for r in range(a.shape[1])
        if not np.array_equal(a[:, r, :sp], b[:, r, :sp])})
    print(f"[share] {name} equal {equal}; step a row's tokens part (-1 never)"
          f" {part}; rows whose prompt slots differ {prompt}; first "
          f"log-probs differ by {float(np.abs(own[1][:, 0] - shared[1][:, 0]).max())}",
          flush=True)
    if eng._prefill_wave_rows(len(rows), sp) < len(rows):
        return equal
    # One prefill of sixteen rows against one of four: the same mathematics
    # through matmuls of another height.  How far the PROMPT's slots lie
    # apart (past them the rows hold other tokens once a sample parts).
    far = max(
        float(np.abs(a[:, :, :sp] - b[:, :, :sp]).mean()
              / max(np.abs(a[:, :, :sp]).mean(), 1e-30))
        for a, b in zip(own[3:], shared[3:])
        if a.ndim == 5 and a.shape[2] > sp)
    print(f"[share] {name} fits one prefill: bits may part; the prompts' "
          f"slots differ by {far:.3g} of their mean size", flush=True)
    return far < 0.05


# ------------------------------------------------------- a described v5e


def _computations(text):
    return {
        m.group(1).lstrip("%"): m.group(2) for m in re.finditer(
            r"^(?:ENTRY )?(%?[\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)^\}",
            text, re.S | re.M)}


def decode_body(text, spaces=True):
    """The compiled decode loop's body and all it calls, a Counter of its
    lines with the instructions' numbers and the metadata left out
    (`spaces` False: the memory-space marks too)."""
    comps = _computations(text)
    (loop,) = [l for l in text.splitlines() if " while(" in l and "u32[2]" in l]
    todo, seen = [re.search(r"body=%?([\w.\-]+)", loop).group(1)], []
    lines = collections.Counter()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.append(name)
        for line in comps[name].splitlines():
            todo += [c.strip().lstrip("%") for m in re.finditer(
                r"(?:calls|body|condition|to_apply|branch_computations)="
                r"\{?(%?[\w.\-]+(?:, %?[\w.\-]+)*)\}?", line)
                for c in m.group(1).split(",")]
            line = re.sub(r", metadata=\{[^}]*\}", "", line)
            line = re.sub(r"(%?[A-Za-z_][\w\-]*?)(?:\.\d+)+\b", r"\1", line)
            line = re.sub(r"region_\d+", "region", line).strip()
            lines[line if spaces else line.replace("S(1)", "")] += 1
    return lines


def compile_both(name, toy):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.engines import generator
    from areal_tpu.models import transformer as tfm

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # the kernels, not their interpreter
    jax.config.update("jax_enable_compilation_cache", False)
    cfg, _, lens, n, new = cell(name, toy)
    b, sp = n * len(lens), generator.bucket_len(max(lens))
    st = generator.bucket_len(sp + new)

    class OneDevice:
        size = 1

    eng = object.__new__(generator.GeneratorEngine)  # no weights to place
    eng.cfg, eng.mesh, eng.compute_dtype = cfg, OneDevice(), jnp.bfloat16
    eng.eos_token_id, eng._gen_fns, eng._use_flash = cfg.vocab_size, {}, True
    in_place = tfm.expert_leaves_in_place(cfg, jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))["blocks"])
    generator.GeneratorEngine._expert_leaves_in_place = property(
        lambda self: in_place)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda x: placed(x.shape, x.dtype), jax.eval_shape(
            lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    g = GenerationHyperparameters(n=1, max_new_tokens=new)
    bodies = {}
    for form, src in (("own", None), ("shared", [r - r % n for r in range(b)])):
        shared = eng._shared_rows(b, sp, new, src)
        fn = eng._get_gen_fn(b, sp, st, g, False, shared)
        t0 = time.monotonic()
        compiled = fn.lower(
            params, placed((b, sp), jnp.int32), placed((b,), jnp.int32),
            placed((2,), jnp.uint32)).compile()
        text = compiled.as_text()
        bodies[form] = text
        mem = compiled.memory_analysis()
        print(f"[share] {name} {form} [{b}, {sp}] + {new}, prefills "
              f"{b if shared is None else len(set(shared))} rows: compiled in "
              f"{time.monotonic() - t0:.0f} s, temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.3f} GB, code "
              f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB, arguments "
              f"{mem.argument_size_in_bytes / 1e9:.3f} GB, "
              f"{len(re.findall(r' while[(]', text))} loops", flush=True)
    for spaces in (True, False):
        a, c = (decode_body(bodies[f], spaces) for f in ("own", "shared"))
        print(f"[share] {name} decode loop body, "
              f"{'as compiled' if spaces else 'memory spaces left out'}: "
              f"{sum(a.values())} / {sum(c.values())} lines, differ "
              f"{sum((a - c).values())} / {sum((c - a).values())}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("run", "compile"))
    ap.add_argument("cells", nargs="?", default=",".join(CELLS))
    ap.add_argument("--toy", action="store_true",
                    help="the config files' toy sizes (a CPU rehearsal)")
    args = ap.parse_args()
    names = args.cells.split(",")
    if args.mode == "compile":
        for name in names:
            compile_both(name, args.toy)
        return 0
    return 0 if all([run(name, args.toy) for name in names]) else 1


if __name__ == "__main__":
    sys.exit(main())
