"""Parameter reallocation: reshard a pytree between arbitrary mesh layouts.

Capability parity: the reference's signature feature — each model function
call runs under its own 3D layout, and parameters are *reallocated* between
layouts between calls (realhf/impl/model/comm/param_realloc.py: pairwise
NCCL groups + per-layer interval plans; default impl is disk save/load,
system/model_worker.py:1009-1068).

The TPU design collapses all of that machinery: a layout is a
`jax.sharding.NamedSharding` per leaf, and moving between layouts is
`jax.device_put` onto the destination shardings — XLA emits the collectives
(ICI when the meshes share devices, host/DCN transfer otherwise).  With
`donate=True` the source buffers are reused, avoiding the 2x memory spike
the reference dodges via disk.

This module is what `ParamReallocHook`s resolve to at runtime (see
areal_tpu/system/worker.py param-sync handling).
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from areal_tpu.base import tracer
from areal_tpu.parallel import sharding


def reshard(
    tree: Any,
    dst_shardings: Any,
    dtype: Optional[Any] = None,
    donate: bool = False,
) -> Any:
    """Move an (on-device or host) pytree onto `dst_shardings`.

    dst_shardings: a pytree of NamedSharding matching `tree`'s structure (or
    a single sharding applied to every leaf).  `dtype` optionally casts
    floating leaves in the same XLA program (casting before the transfer
    halves the bytes moved when going fp32 -> bf16).
    """
    with tracer.span("reshard", cat="comms") as targs:
        if dtype is not None:
            tree = jax.tree.map(
                lambda x: x.astype(dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                else x,
                tree,
            )
        out = jax.device_put(tree, dst_shardings, donate=donate)
        # The span times the dispatch and whatever of the transfer the
        # host itself carries; it never waits for the result, traced or
        # not (tracing must not change the schedule it observes).
        targs["bytes"] = tree_bytes(out)
    return out


def tree_bytes(tree: Any) -> int:
    """Global bytes of a pytree of arrays, from shapes and dtypes alone."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def reshard_params(
    params: Any,
    dst_mesh: Mesh,
    dtype: Optional[Any] = None,
    donate: bool = False,
) -> Any:
    """Reallocate a transformer param pytree onto `dst_mesh` under the
    framework's canonical sharding rules (areal_tpu/parallel/sharding.py).

    Works between any two layouts: same devices re-partitioned (pure ICI
    collectives), overlapping subsets, or fully disjoint device sets (the
    reference's decoupled gen/train meshes, e.g. sglang.d64p1m1+d32p2m1).
    """
    specs = sharding.param_pspecs(params)
    shardings = sharding.tree_named(dst_mesh, specs)
    return reshard(params, shardings, dtype=dtype, donate=donate)


def replicate_to(tree: Any, dst_mesh: Mesh, donate: bool = False) -> Any:
    """Reallocate with full replication on the destination mesh."""
    return reshard(tree, NamedSharding(dst_mesh, P()), donate=donate)
