"""What a Pallas kernel at many call sites costs BEFORE the compile cache is
asked: the seconds a CPU host takes to trace and lower a benchmark
configuration's gradient program for a described v5e, with the
experts on the Pallas grouped matmul (`expert_kernel=None`: the program's own
choice on a TPU backend) and on `jax.lax.ragged_dot` (`False`: the parent's
program), the StableHLO text's size, its `tpu_custom_call`s and the distinct
kernel bodies among them.

A warm set-up pays tracing and lowering on every start (the cache key is made
from the lowered module), so the EXCESS over `ragged_dot`, a program, is what
a kernel adds to the driver's warm `setup_s` — times the programs of a cell
that carry it and the chip host's slowness (PERF.md section 6, PR 50: 13 to
24 to one).  No chip, no compile: about a minute.

    python3 scripts/lowering_check.py [--configs a,b] [--rows 4096,2048,1024]
        [--json chiprun_out/lowering_check.json]

The first program of a process also pays its imports and is left out of the
medians (`--rows` gives three or more lengths for that reason).

`--hash-only` is the program-identity record of a refactoring: for every
configuration of `BENCHMARK.json` (or `--configs`) it lowers, at the
published widths and for the same described chip, the programs `--programs`
names — `grad` (the gradient program above, the program's own kernel
choice), `decode` (`prefill` + one `decode_step` with its counters), `serving`
(one `decode_step_ragged_paged` chunk step; the plans the serving plane
refuses print `refused`) — and prints one line a configuration and program
with the sha256 of the StableHLO text — the results' names
(`jax.result_info`) left out, and every Mosaic kernel's serialised module,
which holds its callers' files and line numbers, replaced by the hash of its
assembly without them (`program_text`).  Run it on two commits: equal hashes
are equal programs.

    python3 scripts/lowering_check.py --hash-only
        [--programs grad,decode,serving] [--configs a,b]"""
import argparse
import gc
import hashlib
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

TOUCHED = ("mellum2-12b-a2.5b-l4-e16", "nemotron-3-nano-30b-a3b-l9-e16",
           "lfm2-8b-a1b-e8")


def kernel_bodies(text: str):
    """{kernel name: distinct Mosaic modules} of a lowered program's
    `tpu_custom_call`s (a call's `backend_config` holds the module)."""
    found = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name\s*=\s*"([^"]+)"', line)
        body = re.search(r'backend_config\s*=\s*"([^"]*)"', line)
        if body is None:
            body = re.search(r"backend_config\s*=\s*(\{.*\})", line)
        key = hashlib.sha256(
            (body.group(1) if body else line).encode()).hexdigest()
        found.setdefault(name.group(1) if name else "?", set()).add(key)
    return {k: len(v) for k, v in found.items()}


def lowered(cfg, rows: int, length: int, device, kernel):
    """(this process's CPU seconds to trace and lower — steadier than the
    wall's on a shared host —, the StableHLO text) of the whole gradient
    program under full remat over `rows` packed rows of `length` tokens,
    for `device`."""
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import transformer as tfm

    one = SingleDeviceSharding(device)

    def placed(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(placed, jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))))
    ints = jax.ShapeDtypeStruct((rows, length), jnp.int32, sharding=one)

    def loss(p, tok, seg):
        x, aux = tfm.hidden_states(
            p, cfg, tok, seg, remat="full", expert_kernel=kernel)
        return jnp.sum(x.astype(jnp.float32)) + aux

    fn = jax.grad(loss)
    gc.collect()
    gc.disable()  # a collection lands where it will, and takes 0.1-0.3 s
    try:
        t0 = time.process_time()
        text = jax.jit(fn).trace(params, ints, ints).lower().as_text()
        return time.process_time() - t0, text
    finally:
        gc.enable()


def _shapes(tree, one):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)


def decode_text(cfg, device, rows=4, prompt=512, s_max=1024) -> str:
    """The StableHLO text of `prefill` over `rows` prompts + one
    `decode_step`, counters and all, on a cache made inside the program."""
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import transformer as tfm

    one = SingleDeviceSharding(device)
    params = _shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))), one)
    ints = jax.ShapeDtypeStruct((rows, prompt), jnp.int32, sharding=one)
    new = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)

    def program(params, tokens, seg, tok):
        cache = tfm.init_kv_cache(cfg, rows, s_max)
        logits, cache = tfm.prefill(params, cfg, tokens, seg, cache)
        return logits, tfm.decode_step(
            params, cfg, tok, jnp.full((rows,), prompt, jnp.int32), cache,
            prompt, jnp.zeros((rows,), jnp.int32), with_counts=True)

    return jax.jit(program).trace(params, ints, ints, new).lower().as_text()


def serving_text(cfg, device, lanes=64, slots=16, pages=64, page=128) -> str:
    """The StableHLO text of one step of the serving chunk over a stream of
    `lanes` tokens, the pool an argument as the generator hands it in."""
    from jax.sharding import SingleDeviceSharding

    from areal_tpu.models import transformer as tfm

    one = SingleDeviceSharding(device)
    params = _shapes(jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0))), one)
    pool = _shapes(jax.eval_shape(lambda: tfm.init_paged_kv_cache(
        cfg, pages, page, n_slots=slots)), one)
    stream = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one)
    table = jax.ShapeDtypeStruct((slots, 8), jnp.int32, sharding=one)

    def program(params, tokens, positions, pool, table, row_of):
        return tfm.decode_step_ragged_paged(
            params, cfg, tokens, positions, pool, table, row_of, slot_lanes=4)

    return jax.jit(program).trace(
        params, stream, stream, pool, table, stream).lower().as_text()


def program_text(text: str) -> str:
    """A lowered program's text with what is no part of the program taken
    out: the results' names, and the debug locations inside each
    `tpu_custom_call`'s serialised kernel (the body becomes the sha256 of
    its assembly printed without them)."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(
                base64.b64decode(match.group(2))
            ).operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest()

    text = re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)', body, text)
    return re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)


def hashes(names, programs, device):
    """One line a configuration and program: the sha256 of its text."""
    from areal_tpu.models import transformer as tfm
    from benchmark import files
    from benchmark import run as bench_run

    for name in names:
        cfg = bench_run.model_config(files.load_json("configs", name + ".json"))
        for program in programs:
            if program == "serving" and tfm.plan_refusal(cfg, serving=True):
                print(f"{name} serving refused", flush=True)
                continue
            text = {
                "grad": lambda: lowered(cfg, 1, 1024, device, None)[1],
                "decode": lambda: decode_text(cfg, device),
                "serving": lambda: serving_text(cfg, device),
            }[program]()
            text = program_text(text)
            print(f"{name} {program} "
                  f"{hashlib.sha256(text.encode()).hexdigest()[:16]} "
                  f"{len(text)} bytes", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=None)
    ap.add_argument("--hash-only", action="store_true")
    ap.add_argument("--programs", default="grad,decode,serving")
    ap.add_argument("--rows", default="4096,3072,2048,1024")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    from jax.experimental import topologies

    from benchmark import files
    from benchmark import run as bench_run

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    jax.default_backend = lambda: "tpu"  # the kernels are not interpreted
    if args.hash_only:
        with open(os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "BENCHMARK.json")) as f:
            every = [c["name"] for c in json.load(f)["configs"]]
        names = args.configs.split(",") if args.configs else every
        return hashes(names, args.programs.split(","), device)
    lengths = [int(r) for r in args.rows.split(",")]
    report = {}
    for name in (args.configs or ",".join(TOUCHED)).split(","):
        cfg = bench_run.model_config(files.load_json("configs", name + ".json"))
        # The two sides turn about, each at lengths of its own: a program
        # meets no trace of its own shapes, as in a cell, and a drift of
        # the host's falls on both.
        sides = {"ragged_dot": [], "kernel": []}
        for length in lengths:
            for label, kernel, at in (("ragged_dot", False, length),
                                      ("kernel", None, length - 128)):
                secs, text = lowered(cfg, 1, at, device, kernel)
                sides[label].append({
                    "length": at, "seconds": round(secs, 3),
                    "text_mb": round(len(text) / 1e6, 3),
                    "custom_calls": text.count("tpu_custom_call"),
                    "funcs": text.count("func.func"),
                    "bodies": kernel_bodies(text),
                })
        later = {k: [r["seconds"] for r in v[1:]] for k, v in sides.items()}
        excess = statistics.median(
            k - r for k, r in zip(later["kernel"], later["ragged_dot"]))
        last = {k: v[-1] for k, v in sides.items()}
        print(
            f"{name}: ragged_dot {later['ragged_dot']} s "
            f"{last['ragged_dot']['text_mb']} MB "
            f"{last['ragged_dot']['custom_calls']} calls | kernel "
            f"{later['kernel']} s {last['kernel']['text_mb']} MB "
            f"{last['kernel']['custom_calls']} calls "
            f"{last['kernel']['funcs']} funcs | excess {excess:+.2f} s "
            f"a program, text x"
            f"{last['kernel']['text_mb'] / last['ragged_dot']['text_mb']:.2f}"
            f" | bodies {last['kernel']['bodies']}",
            flush=True,
        )
        report[name] = {"excess_s": excess, **sides}
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
