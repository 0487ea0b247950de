"""How `data/scoped_v5e.xplane.pb` was recorded (TPU v5e, jax 0.9.0):

    chiprun -- env PYTHONPATH=. python benchmark/tests/record_scoped_trace.py

Two steps of a toy train step under the profiler: the program's tracer
writes `areal:` spans (a `step`, an MFC with `pack` / `grad_dispatch` /
`apply_dispatch` / `stats_sync`, a `param_sync` with `params_put`) beside
the benchmark's `bench:` spans, the device program runs three checkpointed
layers under `train/grad` with `layer/attn`, `layer/mlp`, `head_logprob`
scopes and the repo's flash kernels (`flash_fwd`, `flash_dq`,
`flash_dkv`), then `train/apply`.  The trace lands under
chiprun_out/tiny_scoped/; copy its .xplane.pb over the data file."""
import shutil
import time

import jax
import jax.numpy as jnp

from areal_tpu.base import tracer
from areal_tpu.ops.pallas.flash_attention import flash_attention

assert jax.default_backend() == "tpu", jax.default_backend()
B, S, H, D = 1, 512, 4, 128
W = jnp.ones((H * D, H * D), jnp.bfloat16) * 0.01

@jax.named_scope("layer/mlp")
def mlp(x, w):
    return jnp.tanh(x @ w) @ w

@jax.named_scope("layer/attn")
def attn(x, seg):
    q = x.reshape(B, S, H, D)
    return flash_attention(q, q, q, seg, causal=True).reshape(B, S, H * D)

def layer(x, w, seg):
    return mlp(x + attn(x, seg), w)

@jax.named_scope("head_logprob")
def head(x, w):
    return jax.nn.logsumexp((x @ w).astype(jnp.float32), axis=-1).sum()

@jax.jit
def grad_fn(w, x, seg):
    def loss(w):
        def body(c, _):
            return jax.checkpoint(layer)(c, w, seg), None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return head(y, w)
    with jax.named_scope("train/grad"):
        return jax.value_and_grad(loss)(w)

@jax.jit
@jax.named_scope("train/apply")
def apply_fn(w, g):
    return w - 0.01 * g.astype(w.dtype)

x = jnp.ones((B, S, H * D), jnp.bfloat16) * 0.1
seg = jnp.ones((B, S), jnp.int32)
l, g = grad_fn(W, x, seg); w2 = apply_fn(W, g); jax.block_until_ready(w2)  # warm

out = "chiprun_out/tiny_scoped"
shutil.rmtree(out, ignore_errors=True)
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench:window"):
    pass
w = W
for step in (1, 2):
    with tracer.step_span(step):
        with jax.profiler.TraceAnnotation("bench:actor:train_step"):
            with tracer.span("mfc:actor@0:train_step", cat="compute", step=step):
                with tracer.span("pack", cat="host"):
                    time.sleep(0.002)
                with tracer.span("grad_dispatch", cat="compute"):
                    l, g = grad_fn(w, x, seg)
                with tracer.span("apply_dispatch", cat="compute"):
                    w = apply_fn(w, g)
                with tracer.span("stats_sync", cat="compute"):
                    float(l)
        with jax.profiler.TraceAnnotation("bench:param_sync:actor_gen"):
            with tracer.span("param_sync:actor_gen@0", cat="comms", step=step):
                with tracer.span("params_put", cat="comms"):
                    time.sleep(0.003)
with jax.profiler.TraceAnnotation("bench:window"):
    pass
jax.profiler.stop_trace()
