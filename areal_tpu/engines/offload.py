"""Host-offload support shared by the engines (OffloadHook backend).

Reference: realhf/impl/model/nn/real_llm_api.py:308-405 (async offload of
idle models) — here a synchronous host round-trip: `offload()` gathers the
device state to host numpy (collective when the mesh spans processes) and
drops the device buffers; `_ensure_loaded()` restores them on the next use.
"""

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.base import tracer
from areal_tpu.parallel import realloc, sharding


def buffers_alias(a, b) -> bool:
    """True when two arrays share any device buffer.  Object identity is
    NOT enough: `device_put`/`astype` can return a DISTINCT Array that
    still aliases the source's buffers (no-op cast, partial reshard), and
    decoding from a buffer the source engine later donates reads freed
    memory.  Compare the underlying per-shard buffer pointers instead."""
    if a is b:
        return True
    try:
        pa = {s.data.unsafe_buffer_pointer() for s in a.addressable_shards}
        pb = {s.data.unsafe_buffer_pointer() for s in b.addressable_shards}
        return bool(pa & pb)
    except Exception:  # non-Array leaves / backends without pointer access
        return False


class HostOffloadMixin:
    """Params-only offload; TrainEngine extends with optimizer state."""

    _host_offload: Optional[Any] = None
    _offload_shardings: Optional[Any] = None
    # What the last _take_params() placed: leaves and global bytes by
    # route, seconds of the placement (waited for) and of the alias copy.
    last_sync_stats: Optional[Dict[str, float]] = None

    def _offload_state(self) -> Tuple[Any, ...]:
        return (self.params,)

    def _restore_state(self, state: Tuple[Any, ...]) -> None:
        (self.params,) = state

    def _drop_state(self) -> None:
        self.params = None

    def _take_params(self, params, copy_aliases: bool) -> None:
        """Hot-swap weights: cast to `compute_dtype` and lay out on our
        mesh by `realloc.reshard`'s cheapest route per leaf, then WAIT for
        them — a compiled re-layout is only enqueued when it returns, and
        the caller's reply means "the weights are in place".
        `last_sync_stats` keeps what this call placed by which route and
        how long each statement took (the worker returns it to the
        master).

        copy_aliases: copy any leaf whose BUFFERS still alias the input
        (object identity alone misses distinct Arrays sharing storage).
        The source engine's optimizer step later DONATES those buffers, so
        a generation that overlaps it would decode from deleted memory.
        Only the in-place route aliases: a compiled program's outputs
        share nothing with inputs it did not donate."""
        # New weights supersede any host-offloaded copy.
        self._host_offload = None
        self._offload_shardings = None
        t0 = time.monotonic()
        with tracer.span("params_put", cat="comms"):
            placed, stats = realloc.reshard_counted(
                params,
                sharding.tree_named(self.mesh, sharding.param_pspecs(params)),
                self.compute_dtype,
            )
            jax.block_until_ready(placed)
        t1 = time.monotonic()
        if copy_aliases:
            with tracer.span("params_alias_copy", cat="comms"):
                placed = jax.tree.map(
                    lambda p, orig: (
                        jnp.copy(p) if buffers_alias(p, orig) else p
                    ),
                    placed, params,
                )
        self.params = placed
        self.last_sync_stats = {
            **stats,
            "put_s": t1 - t0,
            "alias_copy_s": time.monotonic() - t1,
        }
        tracer.counter("param_sync", **self.last_sync_stats)

    def offload(self) -> None:
        """Move device state to host, freeing HBM while the model is idle;
        the next engine call reloads transparently."""
        if self._host_offload is not None:
            return
        from areal_tpu.base.distributed import to_host

        state = self._offload_state()
        self._offload_shardings = jax.tree.map(
            lambda x: x.sharding, state
        )
        self._host_offload = jax.tree.map(to_host, state)
        self._drop_state()

    def _ensure_loaded(self) -> None:
        if self._host_offload is None:
            return
        state = jax.tree.map(
            jax.device_put, self._host_offload, self._offload_shardings
        )
        self._host_offload = None
        self._offload_shardings = None
        self._restore_state(state)

    def hbm_owned(self) -> Dict[str, Any]:
        """What this engine keeps on the device between calls, by owner,
        for the worker's HBM ledger (`system/worker._hbm_owners` counts
        the bytes, each buffer once): `weights` here; `moments` and
        `cache` where an engine has them."""
        return {"weights": self.params}
