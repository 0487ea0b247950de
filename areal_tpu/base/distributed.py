"""Multi-host world bootstrap + global-array host transfer.

Capability parity: realhf/impl/model/comm/global_comm.py:48-156 (NCCL world
setup from name_resolve-published addresses) — the TPU way: process 0
publishes a coordinator address via name_resolve, every process of the
trial calls `jax.distributed.initialize`, and XLA's multi-controller
runtime forms collectives over ICI/DCN (gloo when the fake CPU cluster is
in use).  After initialization each process sees the GLOBAL device list
(`jax.devices()`), so a worker group can lay one `jax.sharding.Mesh` across
hosts and jit SPMD programs over it.
"""

from typing import Optional

from areal_tpu.base import logging, name_resolve, names, network

logger = logging.getLogger("distributed")


def coordinator_name(experiment_name: str, trial_name: str) -> str:
    return names.trial_root(experiment_name, trial_name) + "/jax_coordinator"


def initialize(
    experiment_name: str,
    trial_name: str,
    process_id: int,
    num_processes: int,
    timeout: float = 300.0,
    coordinator_address: Optional[str] = None,
) -> None:
    """Form the multi-controller world.  No-op for single-process trials.

    Process 0 binds the coordinator; everyone else discovers it through
    name_resolve (the same rendezvous the reference uses for its NCCL store,
    global_comm.py:48).
    """
    if num_processes <= 1:
        return
    import jax

    if coordinator_address is None:
        key = coordinator_name(experiment_name, trial_name)
        if process_id == 0:
            port = network.find_free_port()
            coordinator_address = f"{network.gethostip()}:{port}"
            name_resolve.add(key, coordinator_address, replace=True)
        else:
            coordinator_address = name_resolve.wait(key, timeout=timeout)
    logger.info(
        f"process {process_id}/{num_processes} joining world at "
        f"{coordinator_address}"
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=int(timeout),
    )
    logger.info(
        f"world up: {jax.process_count()} processes, "
        f"{jax.local_device_count()} local / {jax.device_count()} global "
        "devices"
    )


def to_host(x):
    """Device -> host numpy, handling process-spanning arrays.

    For arrays sharded over a multi-host mesh this is a COLLECTIVE (an
    all-gather executed by every process in the mesh) — callers already run
    SPMD-symmetrically on every group member, so each reaches this point
    with the same array.  Single-process arrays take the plain asarray path.
    """
    import jax
    import numpy as np

    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def is_primary() -> bool:
    """True on the process that should write files / return results."""
    import jax

    return jax.process_index() == 0


def is_tpu_backend() -> bool:
    """True when JAX's default backend is the TPU.  Everything gated on
    this (compiled vs interpreted Pallas kernels, flash vs dense
    attention) follows the platform JAX reports and nothing else."""
    import jax

    return jax.default_backend() == "tpu"
