"""Parameter reallocation: reshard a pytree between arbitrary mesh layouts.

Capability parity: the reference's signature feature — each model function
call runs under its own 3D layout, and parameters are *reallocated* between
layouts between calls (realhf/impl/model/comm/param_realloc.py: pairwise
NCCL groups + per-layer interval plans; default impl is disk save/load,
system/model_worker.py:1009-1068).

The TPU design collapses all of that machinery: a layout is a
`jax.sharding.NamedSharding` per leaf, and `reshard` is the one
implementation of "cast the floating leaves and place the tree under these
shardings".  It picks each leaf's route from the leaf itself:

- *in place*: a committed `jax.Array` that already has the target dtype
  and a sharding equivalent to its destination keeps its buffers (the same
  object when the shardings are equal, else the same buffers under the
  destination's sharding).  No byte moves.
- *on device*: a committed `jax.Array` whose device assignment (devices
  AND order) is its destination's, but whose layout or dtype differs.  All
  such leaves go through ONE held compiled program, `jit(relayout,
  out_shardings=...)`: the cast happens before the bytes move and XLA emits
  the all-to-alls over ICI.  `jax.device_put` does NOT do this on jax
  0.9.0: with no source shard holding exactly a destination index,
  `array._array_shard_arg` falls to `shard_sharded_device_array_slow_path`,
  which gathers the whole array to host numpy (`x._value`) and uploads
  every chip's slice — 0.57 GB/s for the colocated f4 -> m4 hand-back,
  with the chips idle (ledger, PR 23).
- *put*: anything else — host numpy (checkpoint load, pushed weights, an
  engine's first weights), uncommitted arrays, arrays on another or an
  overlapping device set (decoupled gen/train meshes) — `jax.device_put`,
  which carries the bytes through the host where the device sets differ.

This module is what `ParamReallocHook`s resolve to at runtime (see
areal_tpu/system/worker.py param-sync handling) and what the engines'
`set_params` call.
"""

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, Sharding

from areal_tpu.base import tracer
from areal_tpu.parallel import sharding


def _needs_cast(x, dtype: Optional[Any]) -> bool:
    return (
        dtype is not None
        and x.dtype != dtype
        and jnp.issubdtype(x.dtype, jnp.floating)
    )


def _cast(x, dtype: Optional[Any]):
    """`x` in `dtype` if it is a floating leaf and a dtype is asked for."""
    return x.astype(dtype) if _needs_cast(x, dtype) else x


def _device_assignment(s: Sharding) -> Optional[Tuple[Any, ...]]:
    """Devices of a sharding IN ORDER (`device_set` forgets the order, and
    one compiled program runs on one ordered assignment)."""
    if isinstance(s, NamedSharding):
        return tuple(s.mesh.devices.flat)
    da = getattr(s, "_device_assignment", None)
    return None if da is None else tuple(da)


@functools.lru_cache(maxsize=16)
def _relayout_program(dst: Tuple[Sharding, ...], dtype: Optional[Any]):
    """The held program that casts a list of leaves and lays them out
    under `dst`.  One per destination and dtype: a function made per call
    would retrace and reload every hand-back."""

    def relayout(leaves: List[jax.Array]) -> List[jax.Array]:
        # The constraint sits under the scope so that the collectives the
        # partitioner makes of it carry the name in a device trace;
        # out_shardings hands back exactly the destination's shardings.
        with jax.named_scope("param_sync/relayout"):
            return [
                jax.lax.with_sharding_constraint(_cast(x, dtype), to)
                for x, to in zip(leaves, dst)
            ]

    return jax.jit(relayout, out_shardings=list(dst))


def _rewrap(x: jax.Array, dst: Sharding) -> jax.Array:
    """`x`'s own buffers under an equivalent sharding: what `device_put`
    returns for such a pair, so the destination's compiled programs meet
    the sharding they were compiled for."""
    if x.sharding == dst:
        return x
    return jax.make_array_from_single_device_arrays(
        x.shape, dst, [s.data for s in x.addressable_shards]
    )


def reshard_counted(
    tree: Any, dst_shardings: Any, dtype: Optional[Any] = None
) -> Tuple[Any, Dict[str, float]]:
    """`reshard`, and how many leaves and bytes took which route:
    `leaves_aliased` (in place), `leaves_resharded` / `bytes_resharded`
    (the compiled on-device program), `leaves_put` / `bytes_put`
    (`jax.device_put`), and `bytes` of the whole placed tree (global, from
    shapes and dtypes).  Dispatches and returns: the caller that must know
    the weights are in place waits on the result."""
    dtype = None if dtype is None else jnp.dtype(dtype)
    leaves, treedef = jax.tree.flatten(tree)
    if isinstance(dst_shardings, Sharding):
        dsts = [dst_shardings] * len(leaves)
    else:
        dsts = treedef.flatten_up_to(dst_shardings)
    out: List[Any] = [None] * len(leaves)
    moved: List[int] = []  # the compiled on-device program
    put: List[int] = []  # jax.device_put
    for i, (x, dst) in enumerate(zip(leaves, dsts)):
        if not (isinstance(x, jax.Array) and x.committed):
            put.append(i)
        elif not _needs_cast(x, dtype) and x.sharding.is_equivalent_to(
            dst, x.ndim
        ):
            out[i] = _rewrap(x, dst)
        elif _device_assignment(x.sharding) == _device_assignment(dst):
            moved.append(i)
        else:
            put.append(i)
    with tracer.span("reshard", cat="comms") as targs:
        if moved:
            program = _relayout_program(tuple(dsts[i] for i in moved), dtype)
            for i, y in zip(moved, program([leaves[i] for i in moved])):
                out[i] = y
        if put:
            placed = jax.device_put(
                [_cast(leaves[i], dtype) for i in put],
                [dsts[i] for i in put],
            )
            for i, y in zip(put, placed):
                out[i] = y
        counts = {
            "bytes": float(tree_bytes(out)),
            "leaves_aliased": float(len(leaves) - len(moved) - len(put)),
            "leaves_resharded": float(len(moved)),
            "leaves_put": float(len(put)),
            "bytes_resharded": float(tree_bytes([out[i] for i in moved])),
            "bytes_put": float(tree_bytes([out[i] for i in put])),
        }
        targs.update(counts)
    return jax.tree.unflatten(treedef, out), counts


def reshard(
    tree: Any, dst_shardings: Any, dtype: Optional[Any] = None
) -> Any:
    """Place an (on-device or host) pytree under `dst_shardings`.

    dst_shardings: a pytree of shardings matching `tree`'s structure (or a
    single sharding applied to every leaf).  `dtype` optionally casts the
    floating leaves (on the source devices, so going fp32 -> bf16 halves
    the bytes moved).  The route is chosen per leaf (module docstring); the
    source tree is never donated and survives.
    """
    return reshard_counted(tree, dst_shardings, dtype)[0]


def tree_bytes(tree: Any) -> int:
    """Global bytes of a pytree of arrays, from shapes and dtypes alone."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def reshard_params(
    params: Any, dst_mesh: Mesh, dtype: Optional[Any] = None
) -> Any:
    """Reallocate a transformer param pytree onto `dst_mesh` under the
    framework's canonical sharding rules (areal_tpu/parallel/sharding.py).

    Works between any two layouts: same devices re-partitioned (one
    compiled program, ICI collectives), overlapping subsets, or fully
    disjoint device sets (the reference's decoupled gen/train meshes, e.g.
    sglang.d64p1m1+d32p2m1; `jax.device_put`, through the host).
    """
    specs = sharding.param_pspecs(params)
    shardings = sharding.tree_named(dst_mesh, specs)
    return reshard(params, shardings, dtype=dtype)


def replicate_to(tree: Any, dst_mesh: Mesh) -> Any:
    """Reallocate with full replication on the destination mesh."""
    return reshard(tree, NamedSharding(dst_mesh, P()))
