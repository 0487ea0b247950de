"""The lowered text of the programs every autoregressive cell runs, at toy
size, hashed: what `tests/test_sdar.py` holds to the hashes printed at the
commit before generation by diffusion over blocks (PR 68) came in — a
model with `block_length == 0` must lower to the text it lowered to.

    PYTHONPATH=<a checkout> python3 -m tests.lowered_programs

prints one line a configuration and program.  It imports nothing a
checkout before PR 68 lacks.  The flash kernels are forced (`use_flash`:
interpreted off a TPU, so their bodies — `_tile_mask` among them — are
part of the text); the fused log-prob head is part of the gradient
program."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp

from areal_tpu.models import transformer as tfm
from benchmark import files
from benchmark import run as bench_run

# One dense configuration and one expert-parallel rank's share (windows
# beside full layers: the kernels' band too), at their files' toy sizes.
CONFIGS = ("qwen2.5-math-1.5b", "mellum2-12b-a2.5b-l4-e16")
# One configuration a kind of static cache that holds more than k/v, by the
# `KVCache` field that makes it so (`wk`: the share configuration above):
# their `prefill` and `decode` are held too, since PR 70 (`kv_decode` is the
# attention of the plans whose cache is k/v alone, and of no other).
CACHE_KINDS = {
    "state": "nemotron-3-nano-30b-a3b-l9-e16",
    "latent": "glm-4.7-flash-l7-e8",
    "ck": "minicpm-sala-l4-v8",
}
ROWS, LENGTH, S_MAX = 2, 256, 384


def toy_config(name):
    config = files.load_json("configs", name + ".json")
    config, _ = bench_run.toy(
        config, files.load_json("traffic", "rollout64-512.json"))
    return dataclasses.replace(
        bench_run.model_config(config), param_dtype="float32")


def _sha(text: str) -> str:
    text = text.split("\n", 1)[1]  # the module's name line
    text = re.sub(r' \{jax\.result_info = "[^"]*"\}', "", text)
    return hashlib.sha256(text.encode()).hexdigest()


def programs(cfg, which=("grad", "prefill", "decode")):
    """{program: its lowered text's sha256} for `cfg`, of those `which`
    names."""
    params = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    ints = jax.ShapeDtypeStruct((ROWS, LENGTH), jnp.int32)
    new = jax.ShapeDtypeStruct((ROWS,), jnp.int32)

    def loss(p, tok, seg):
        x, aux = tfm.hidden_states(
            p, cfg, tok, seg, remat="full", use_flash=True)
        out = tfm.per_token_output(p, cfg, x, tok, seg)
        return jnp.sum(out) + aux

    def prefill(p, tok, seg):
        cache = tfm.init_kv_cache(cfg, ROWS, S_MAX)
        return tfm.prefill(p, cfg, tok, seg, cache, use_flash=True)

    def decode(p, tok, cache):
        return tfm.decode_step(
            p, cfg, tok, jnp.full((ROWS,), LENGTH, jnp.int32), cache, LENGTH,
            jnp.zeros((ROWS,), jnp.int32), with_counts=True)

    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, ROWS, S_MAX))
    texts = {
        "grad": lambda: jax.jit(jax.grad(loss)).lower(params, ints, ints),
        "prefill": lambda: jax.jit(prefill).lower(params, ints, ints),
        "decode": lambda: jax.jit(decode).lower(params, new, cache),
    }
    return {name: _sha(texts[name]().as_text()) for name in which}


if __name__ == "__main__":
    for name in CONFIGS:
        for program, sha in programs(toy_config(name)).items():
            print(f'    ("{name}", "{program}"): "{sha}",')
    for name in CACHE_KINDS.values():
        for program, sha in programs(
                toy_config(name), ("prefill", "decode")).items():
            print(f'    ("{name}", "{program}"): "{sha}",')
