"""Model worker: owns model bundles (engine+interface+tokenizer), a local
data cache, and dataset shards; executes MFC requests from the master.

Capability parity: realhf/system/model_worker.py (request handling, dataset
fetch, MFC execution, save/load, data cache) — condensed for the TPU
process model: one worker per host-local mesh rather than one per GPU, since
XLA SPMD executes one program per mesh.  Transport-agnostic: the same
`ModelWorker.handle_request` serves the in-process pool (tests, single-host
trials) and the ZMQ stream runtime.
"""

import dataclasses
import functools
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api import dfg as dfg_api
from areal_tpu.api.config import (
    ModelAbstraction,
    ModelBackendAbstraction,
    ModelInterfaceAbstraction,
    ModelInterfaceType,
    ModelName,
)
from areal_tpu.api.data_api import (
    DatasetAbstraction,
    MicroBatchSpec,
    SequenceSample,
    make_dataset,
)
from areal_tpu.api.model_api import (
    FinetuneSpec,
    Model,
    OptimizerConfig,
    make_interface,
)
from areal_tpu.base import faults, logging, metrics, tracer
from areal_tpu.base.topology import ParallelConfig, make_mesh
from areal_tpu.models.config import ModelConfig

# Populate the dataset/interface registries.
import areal_tpu.data.datasets  # noqa: F401
import areal_tpu.interfaces.sft  # noqa: F401
import areal_tpu.interfaces.ppo  # noqa: F401
import areal_tpu.interfaces.reward  # noqa: F401
import areal_tpu.interfaces.fused  # noqa: F401
import areal_tpu.interfaces.null  # noqa: F401

# One xprof trace at a time per process (see _handle_mfc).
_TRACE_LOCK = threading.Lock()

# Compilation as jax.monitoring reports it goes to the tracer's program
# ledger (base/tracer.program_event: one row a program, the per-thread
# counts behind `perf/compiles`, `compile_s`, `cache_load_s`, `trace_s`
# and `lower_s`, the process's set-up totals).  One registration per
# process, made by the first worker; the callbacks fire only when
# something is traced, lowered, compiled or loaded.
_compile_listener_installed = False


def _step(req: Dict[str, Any]) -> Dict[str, int]:
    """Span argument naming the master's `step` span that sent `req`."""
    return {"step": req["step"]} if "step" in req else {}


def _install_compile_listener() -> None:
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    import jax.monitoring

    # jax hands durations and plain events (the cache's "written after a
    # miss") to separate lists of listeners: the one callback is on both.
    jax.monitoring.register_event_duration_secs_listener(tracer.program_event)
    jax.monitoring.register_event_listener(tracer.program_event)
    _compile_listener_installed = True


# What the tracer's HBM ledger reads the device with (base/tracer.
# hbm_readers): the local devices of every mesh a worker of this process
# built, the workers whose engines own what lies there, and the loaded
# executables already measured, (id, fingerprint) -> (code bytes, device
# ids).  Nothing is kept on a backend without `memory_stats()` (CPU).
_hbm_devices: Dict[int, Any] = {}
_hbm_workers: "weakref.WeakSet[ModelWorker]" = weakref.WeakSet()
_hbm_programs_seen: Dict[Any, Any] = {}


def _hbm_watch(worker: "ModelWorker", mesh) -> None:
    """Put `mesh`'s local devices and `worker`'s engines under the HBM
    ledger's marks."""
    import jax

    local = {
        d.id: d for d in mesh.devices.flat
        if d.process_index == jax.process_index()
    }
    _hbm_devices.update(local)
    if _hbm_stats() is None:
        for i in local:
            del _hbm_devices[i]
        return
    _hbm_workers.add(worker)
    tracer.hbm_readers(_hbm_stats, _hbm_programs, _hbm_owners)


def _hbm_stats() -> Optional[Dict[int, Dict[str, int]]]:
    """`memory_stats()` of every watched device by id; None where the
    backend keeps none (CPU)."""
    out = {i: d.memory_stats() for i, d in _hbm_devices.items()}
    return None if None in out.values() else out


def _hbm_programs(expect: int):
    """(a row of bytes each executable loaded since the last call, oldest
    first; code bytes of everything loaded, by device): `client.
    live_executables()` lists the newest first, and an executable is
    measured (`get_compiled_memory_stats()`, 0.1-1 ms) once.  Its
    module's name is read back (`hlo_modules()`: 4-28 ms a program on the
    chip, PERF.md section 6, PR 66) only where the new ones are not the
    `expect` the program ledger counted: their order is then not enough
    to join them to its rows."""
    global _hbm_programs_seen
    client = next(iter(_hbm_devices.values())).client
    fresh, live, code = [], {}, dict.fromkeys(_hbm_devices, 0)
    for exe in client.live_executables():
        key = (id(exe), exe.fingerprint)
        known = _hbm_programs_seen.get(key)
        if known is None:
            ms = exe.get_compiled_memory_stats()
            fresh.append((exe, ms))
            known = (
                ms.generated_code_size_in_bytes,
                [d.id for d in exe.local_devices()],
            )
        live[key] = known
        for i in known[1]:
            if i in code:
                code[i] += known[0]
    _hbm_programs_seen = live  # what was freed since goes with its id
    named = len(fresh) != expect
    new = [
        {
            "name": exe.hlo_modules()[0].name if named else None,
            "code_b": ms.generated_code_size_in_bytes,
            "temp_b": ms.temp_size_in_bytes,
            "arg_b": ms.argument_size_in_bytes,
            "out_b": ms.output_size_in_bytes,
            "alias_b": ms.alias_size_in_bytes,
        }
        for exe, ms in reversed(fresh)
    ]
    return new, code


def _hbm_buffers(a, device_id: int):
    """(identity, bytes) of each buffer of `a` on one device, WITHOUT
    making a per-shard view of it: `addressable_shards` on a sharded array
    makes one live array a shard and keeps them with it, and after a walk
    that did so over every live array the four-chip cell's generator
    weights outlived `release_params()` by 1.48 GB a chip a step until the
    chip refused the gradient program's reserve (PERF.md section 6,
    PR 66).  An unsharded array is known by its buffer's pointer; a
    sharded one by the views something else already made of it
    (`offload.buffers_alias` does, of the weights) or else by itself, an
    even share of its bytes on every device that holds it."""
    local = a.sharding.addressable_devices
    if not any(d.id == device_id for d in local):
        return []
    if len(a.sharding.device_set) == 1:
        return [(a.unsafe_buffer_pointer(), a.on_device_size_in_bytes())]
    views = a.__dict__.get("addressable_shards")
    if views is None:
        return [(id(a), a.on_device_size_in_bytes() // len(local))]
    return [
        (v.data.unsafe_buffer_pointer(), v.data.on_device_size_in_bytes())
        for v in views if v.device.id == device_id
    ]


def _hbm_owners(device_id: int) -> Dict[str, int]:
    """Bytes on one device by owner: what each engine of this process says
    it keeps between calls (`hbm_owned()`: weights, moments, cache), then
    `other_live`, every other live array.  A buffer is counted once, for
    the first owner that shows it: a colocated generator's weights that
    alias the trainer's are the trainer's."""
    import jax

    seen = set()

    def count(arrays) -> int:
        n = 0
        for a in arrays:
            if not isinstance(a, jax.Array) or a.is_deleted():
                continue
            try:
                buffers = _hbm_buffers(a, device_id)
            except RuntimeError:  # donated while we walked: not live
                continue
            for key, size in buffers:
                if key not in seen:
                    seen.add(key)
                    n += size
        return n

    out: Dict[str, int] = {}
    for worker in list(_hbm_workers):
        for model in worker.models.values():
            owned = getattr(model.engine, "hbm_owned", None)
            for name, tree in (owned() if owned else {}).items():
                out[name] = out.get(name, 0) + count(jax.tree.leaves(tree))
    out["other_live"] = count(jax.live_arrays())
    return out


def _hbm_perf(mark: Optional[Dict[str, Any]]) -> Dict[str, float]:
    """Device memory after a request, from its span's closing mark: of
    the fullest local device (reference: per-worker GPU mem/util tables,
    model_worker.py:1434-1537).  Empty where the backend has no
    `memory_stats()` (CPU)."""
    if not mark:
        return {}
    perf = {"perf/hbm_gb": mark["in_use"] / 1e9}
    if mark["limit"]:
        perf["perf/hbm_frac"] = mark["in_use"] / mark["limit"]
    return perf


def _zero_filled(meta_row: SequenceSample, keys) -> SequenceSample:
    """Zero-data placeholder for keys this member did not receive under
    sharded dispatch — correct layout (seqlens/dtype/trailing shape) with
    zero values; the real values live on the process whose devices consume
    those rows, and device_put only reads the rows local to each process."""
    data = {}
    seqlens = {}
    for k in keys:
        sls = meta_row.seqlens[k]
        n = sum(sum(s) for s in sls)
        trail = tuple(meta_row.trailing_shapes.get(k) or ())
        dt = meta_row.dtypes.get(k)
        if dt is None:
            raise ValueError(
                f"cannot zero-fill {k!r} for {meta_row.ids}: the shipped "
                "metadata carries no dtype (a silent float default would "
                "corrupt integer token ids)"
            )
        data[k] = np.zeros((n, *trail), dtype=dt)
        seqlens[k] = [list(s) for s in sls]
    return SequenceSample(
        keys=set(keys),
        ids=list(meta_row.ids),
        seqlens=seqlens,
        data=data,
    )


def _check_hbm_kill(perf: Dict[str, float]) -> None:
    """Fail the worker when device memory crosses a configured watermark
    (reference: model_worker.py:1434-1537 GPU-mem kill threshold) — a
    deliberate crash into the recover path beats an unpredictable OOM mid
    optimizer step."""
    kill = os.environ.get("AREAL_HBM_KILL_FRAC")
    frac = perf.get("perf/hbm_frac")
    if kill and frac is not None and frac > float(kill):
        raise MemoryError(
            f"device memory {frac:.1%} exceeds AREAL_HBM_KILL_FRAC={kill}; "
            "failing fast for the recover loop"
        )

logger = logging.getLogger("model_worker")


@dataclasses.dataclass
class ModelShardSpec:
    """Everything needed to build one named model on this worker."""

    name: ModelName
    model: ModelAbstraction  # random | hf
    backend: ModelBackendAbstraction  # train | inference | generator | mock
    interface: ModelInterfaceAbstraction
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    optimizer: Optional[OptimizerConfig] = None
    # First local device for this shard's mesh; None = the worker's offset.
    # Lets one worker host disjoint meshes (e.g. search-chosen gen/train
    # split, reference allocation `sglang.dXp1m1+dYp2m1`).
    device_offset: Optional[int] = None


@dataclasses.dataclass
class WorkerConfig:
    worker_index: int
    shards: List[ModelShardSpec]
    tokenizer_path: Optional[str] = None
    datasets: List[DatasetAbstraction] = dataclasses.field(default_factory=list)
    dataset_dp_rank: int = 0
    dataset_dp_size: int = 1
    batch_size: int = 8
    seed: int = 1
    ftspec: FinetuneSpec = dataclasses.field(default_factory=FinetuneSpec)
    device_offset: int = 0  # first device index for this worker's mesh
    # Multi-controller world membership: when dist_num_processes > 1 the
    # worker bootstrap calls jax.distributed.initialize (coordinator via
    # name_resolve) BEFORE building models, after which jax.devices() is the
    # GLOBAL device list and meshes may span hosts.
    dist_process_id: int = 0
    dist_num_processes: int = 1


@functools.lru_cache(maxsize=8)
def _random_init_fn(cfg: ModelConfig, mesh):
    """Jitted `seed -> params` that builds a random model ON `mesh`, in
    the engines' layout: a model destined for chips 2-3 never touches chip
    0, and the fp32 draws fuse into the cast instead of sitting in HBM
    whole.  Cached per (config, mesh): a trial builds the same model for
    several roles."""
    import jax

    from areal_tpu.models import transformer as tfm
    from areal_tpu.parallel import sharding

    def init(seed):
        return tfm.init_params(cfg, jax.random.PRNGKey(seed))

    shardings = sharding.tree_named(
        mesh, sharding.param_pspecs(jax.eval_shape(init, 0))
    )
    return jax.jit(init, out_shardings=shardings)


def _build_params_and_config(spec: ModelAbstraction, seed: int, mesh):
    if spec.type_ == "null":
        return None, None  # engine-less models (e.g. verification rewards)
    if spec.type_ == "config":
        # Config-only: no local weights (remote_generator workers).
        return spec.args["config"], None
    if spec.type_ == "random":
        cfg: ModelConfig = spec.args["config"]
        # As uint32: a Python int past 2**31 overflows jit's int32 argument,
        # and the key is the same [0, seed] either way.
        return cfg, _random_init_fn(cfg, mesh)(np.uint32(seed % 2**32))
    elif spec.type_ == "hf":
        from areal_tpu.models.hf import registry as hf

        return hf.load_hf_checkpoint(
            spec.args["path"], is_critic=spec.args.get("is_critic", False)
        )
    raise ValueError(f"unknown model abstraction {spec.type_!r}")


class ModelWorker:
    def __init__(self, config: WorkerConfig, tokenizer=None, transfer=None):
        self.config = config
        self.tokenizer = tokenizer
        self.transfer = transfer  # TransferPlane (system/transfer.py) or None
        self._xfer_stash: Dict[int, Any] = {}
        import threading

        # Single-receiver discipline: transfer.recv() is never called from
        # two threads at once (ZMQ sockets are not thread-safe, and two
        # drainers could steal each other's payload).  One thread at a time
        # owns the socket; the rest wait on the condition for their
        # xfer_id to appear in the stash.
        self._xfer_cond = threading.Condition()
        self._xfer_recv_busy = False
        self.models: Dict[str, Model] = {}
        self.interfaces: Dict[str, Any] = {}
        # Per-model mesh layout string ("d4f2m2"), stamped onto every
        # MFC span so the profile store (analysis/profile.py) can key
        # records by (mfc, model_shape, layout, batch_shape).
        self._layouts: Dict[str, str] = {}
        self.data_cache: Dict[str, SequenceSample] = {}
        # Serialize-once cache for param pushes, keyed by model name:
        # (host tree, checksum, wire encoding) survive across targets
        # AND across the master's checksum-reject retry; invalidated by
        # identity when a train step replaces the device tree.
        self._param_send_cache: Dict[str, Dict] = {}
        # Open pipeline-overlapped train streams, keyed by model name
        # (mfc_stream_begin -> N x mfc_stream_chunk -> mfc_stream_end).
        self._streams: Dict[str, Dict[str, Any]] = {}
        self.datasets = []
        self.dataloaders = []
        reg = metrics.default_registry()
        self._m_mfc_seconds = reg.histogram(
            "areal_worker_mfc_seconds",
            "MFC wall time on this worker",
            ("mfc",),
            buckets=(0.05, 0.1, 0.5, 1, 2, 5, 10, 30, 60, 120),
        )
        self._m_mfc_mfu = reg.gauge(
            "areal_worker_mfc_mfu_ratio",
            "last model FLOP utilization, per MFC",
            ("mfc",),
        )
        self._m_mfc_tokens = reg.counter(
            "areal_worker_mfc_tokens_total",
            "tokens processed, per MFC",
            ("mfc",),
        )
        # Chaos hooks (env-gated, AREAL_FAULTS): kill/hang/slow/error on
        # MFC execution at points "mfc_<itype>" / "mfc_stream_*", so the
        # trainer chaos leg breaks a REAL worker with no test-only code
        # path.  None when unset — the fault-free hot path pays one
        # attribute check per request.
        self._faults = faults.FaultInjector.from_env()
        with tracer.setup_span("worker", worker=config.worker_index):
            self._setup()

    # ---------------- setup ----------------

    def _setup(self):
        """Build every shard's model and the datasets, each phase under a
        `setup:*` span (host seconds: nothing here waits for the device,
        so what the initialiser dispatched is paid where the host next
        waits, as a rule in the first MFC)."""
        import jax

        _install_compile_listener()
        from areal_tpu.engines.generator import GeneratorEngine
        from areal_tpu.engines.inference import InferenceEngine
        from areal_tpu.engines.train import TrainEngine

        if self.tokenizer is None and self.config.tokenizer_path:
            from areal_tpu.data.tokenizer import load_hf_tokenizer

            self.tokenizer = load_hf_tokenizer(self.config.tokenizer_path)

        for shard in self.config.shards:
            off = (
                shard.device_offset
                if shard.device_offset is not None
                else self.config.device_offset
            )
            key = str(shard.name)
            with tracer.setup_span("mesh", model=key):
                devices = jax.devices()[off : off + shard.parallel.world_size]
                mesh = make_mesh(shard.parallel, devices)
                _hbm_watch(self, mesh)
            with tracer.setup_span("weights", model=key):
                cfg, params = _build_params_and_config(
                    shard.model, seed=self.config.seed, mesh=mesh
                )
            btype = shard.backend.type_
            with tracer.setup_span("engine", model=key, backend=btype):
                if btype in ("train", "mock"):
                    engine = TrainEngine(
                        cfg, params, mesh,
                        optimizer_config=shard.optimizer or OptimizerConfig(),
                        ftspec=self.config.ftspec,
                        **shard.backend.args,
                    )
                elif btype == "inference":
                    engine = InferenceEngine(
                        cfg, params, mesh, **shard.backend.args
                    )
                elif btype == "generator":
                    engine = GeneratorEngine(
                        cfg, params, mesh,
                        eos_token_id=self.tokenizer.eos_token_id,
                        pad_token_id=getattr(
                            self.tokenizer, "pad_token_id", None
                        ),
                        **shard.backend.args,
                    )
                elif btype == "remote_generator":
                    # Decoupled allocation: generation served by a standalone
                    # GenerationServer; this worker holds no gen weights
                    # (reference: sglang backend, backend/sglang.py:354).
                    from areal_tpu.system.gen_server import (
                        RemoteGeneratorEngine,
                    )

                    engine = RemoteGeneratorEngine(cfg, **shard.backend.args)
                elif btype == "null":
                    engine = None
                else:
                    raise ValueError(f"unknown backend {btype!r}")
            self.models[key] = Model(
                name=key, engine=engine, tokenizer=self.tokenizer, config=cfg
            )
            self._layouts[key] = shard.parallel.to_str()
            self.interfaces[key] = make_interface(
                shard.interface.type_, **shard.interface.args
            )
            logger.info(
                f"worker {self.config.worker_index}: built model {key} "
                f"({shard.backend.type_}, mesh {shard.parallel.to_str()})"
            )

        with tracer.setup_span("datasets", n=len(self.config.datasets)):
            from areal_tpu.data.datasets import PackedDataLoader

            for ds_spec in self.config.datasets:
                ds = make_dataset(
                    ds_spec,
                    seed=self.config.seed,
                    dp_rank=self.config.dataset_dp_rank,
                    world_size=self.config.dataset_dp_size,
                    tokenizer=self.tokenizer,
                )
                self.datasets.append(ds)
                self.dataloaders.append(
                    iter(
                        _Cycler(
                            PackedDataLoader(
                                ds, batch_size=self.config.batch_size,
                                seed=self.config.seed,
                            )
                        )
                    )
                )

    # ---------------- request handling ----------------

    def handle_request(self, req: Dict[str, Any]) -> Dict[str, Any]:
        handler = getattr(self, f"_handle_{req['type']}", None)
        if handler is None:
            raise ValueError(f"unknown request type {req['type']!r}")
        if self._faults is not None:
            self._fire_faults(req)
        return handler(req)

    def _fire_faults(self, req: Dict[str, Any]) -> None:
        """Chaos injection on MFC execution.  Points: ``mfc_<itype>``
        (mfc_train_step / mfc_generate / mfc_inference) for plain MFCs,
        and the raw request type for streamed ones (mfc_stream_begin /
        mfc_stream_chunk / mfc_stream_end).  A matching point-scoped
        kill exits the process hard — from the master's view the worker
        simply stops beating, exactly like a preempted pod."""
        rtype = req["type"]
        if not rtype.startswith("mfc"):
            return
        if rtype == "mfc":
            point = f"mfc_{ModelInterfaceType(req['interface_type']).value}"
        else:
            point = rtype
        if self._faults.kill_point(point):
            os._exit(43)
        self._faults.fire(point)

    def _handle_spec(self, req):
        sizes = [len(ds) for ds in self.datasets]
        steps = (
            (sum(sizes) + self.config.batch_size - 1) // self.config.batch_size
            if sizes
            else 0
        )
        return {"dataset_size": sum(sizes), "steps_per_epoch": steps}

    def _handle_fetch(self, req):
        """Load the next dataset batch into the cache; return its metadata.
        Batches can come up short after difficulty filtering shrinks the
        dataset mid-epoch — top up from the stream so the master's buffer
        (which waits for exactly n_seqs) never stalls."""
        with tracer.span("fetch", cat="host", **_step(req)):
            return self._fetch(req)

    def _fetch(self, req):
        dl_idx = req.get("dataset_index", 0)
        dl = self.dataloaders[dl_idx]
        singles: List[SequenceSample] = []
        have = set()
        attempts = 0
        while len(singles) < self.config.batch_size:
            if attempts > 16:
                raise RuntimeError(
                    f"dataset {dl_idx} cannot fill a batch of "
                    f"{self.config.batch_size} (filtered too far?)"
                )
            attempts += 1
            for one in next(dl).unpack():
                # Top-ups can repeat ids (epoch wrap on a shrunken
                # dataset); the cache and buffer are id-keyed, so dedup.
                if one.ids[0] not in have:
                    have.add(one.ids[0])
                    singles.append(one)
        batch = SequenceSample.gather(singles)
        for one in batch.unpack():
            self.data_cache[one.ids[0]] = one
        return {"meta": batch.meta()}

    def _handle_shard_info(self, req):
        """(shard_rank, n_shards) of the batch rows this process consumes
        for the named model — the master's sharded data plane ships only
        that row block when n > 1 (see master._dispatch_mfc)."""
        engine = self.models[req["model_name"]].engine
        if engine is None:
            return {"rank": 0, "n": 1}
        rank, n = engine.data_shard_info()
        return {"rank": int(rank), "n": int(n)}

    def _assemble_sample(
        self, ids, input_keys, shard_of, shard_meta, remap_in
    ) -> SequenceSample:
        """Gather the per-id cache entries for an MFC into one packed
        sample (zero-filling other members' rows under sharded
        dispatch), tag shard_of metadata, and apply the input remap."""
        parts = []
        for idx, sid in enumerate(ids):
            entry = self.data_cache.get(sid)
            have = input_keys & entry.keys if entry is not None else set()
            part = entry.select_keys(have) if have else None
            if shard_of:
                missing = input_keys - have
                if missing:
                    mrow = shard_meta.select_idx([idx])
                    unknown = missing - mrow.keys
                    if unknown:
                        # A key absent from BOTH the member's cache and
                        # the shipped shard metadata cannot be
                        # zero-filled; dropping it would surface later as
                        # a bewildering KeyError deep in pack/interface
                        # code — fail here, at the cause.
                        raise KeyError(
                            f"worker {self.config.worker_index}: input "
                            f"key(s) {sorted(unknown)} for {sid!r} are in "
                            "neither the data cache nor the shard "
                            "metadata"
                        )
                    zero = _zero_filled(mrow, missing)
                    if part is None:
                        part = zero
                    else:
                        part.update_(zero)
            if part is None:
                raise KeyError(
                    f"worker {self.config.worker_index}: no data for "
                    f"{sid!r} (keys {sorted(input_keys)})"
                )
            parts.append(part)
        sample = SequenceSample.gather(parts)
        if shard_of:
            sample.metadata["shard_of"] = [
                list(shard_of[sid]) for sid in ids
            ]
        sample.remap_keys_(remap_in)
        return sample

    def _handle_mfc(self, req):
        """Execute one model function call on cached data."""
        model_key: str = req["model_name"]
        itype = ModelInterfaceType(req["interface_type"])
        ids: List[str] = req["ids"]
        remap_out: Dict[str, str] = req.get("output_key_remap", {})
        mb_spec: MicroBatchSpec = req.get("mb_spec") or MicroBatchSpec()
        # Sharded dispatch: heavy keys arrived only for this member's own
        # rows; other rows' arrays are zero-filled from metadata (their
        # real values live on the processes whose devices consume them —
        # identical PACK layout everywhere, local VALUES only where they
        # land; see api/dfg.py MFCDef.shard_keys).
        with tracer.span("mfc_gather", cat="host", **_step(req)):
            sample = self._assemble_sample(
                ids,
                set(req["input_keys"]),
                req.get("shard_of") or {},
                req.get("shard_meta"),
                req.get("input_key_remap", {}),
            )

        model = self.models[model_key]
        interface = self.interfaces[model_key]
        fn = getattr(interface, itype.value)
        tracer.take_compiles()  # executor threads are reused: start from zero
        mfc_span = tracer.span(
            f"mfc:{model_key}:{itype.value}", cat="compute",
            **_step(req),
        )
        with mfc_span as targs:
            t0 = time.monotonic()
            # Env-gated xprof capture per MFC (reference: REAL_DUMP_TRACE
            # torch profiler export, model_worker.py:84-99,788-869).  Each
            # MFC call writes a TensorBoard-viewable trace under
            # $AREAL_DUMP_TRACE/<model>_<itype>/.
            trace_root = os.environ.get("AREAL_DUMP_TRACE")
            # JAX allows ONE active trace per process; concurrent MFCs (the
            # in-process runner overlaps independent graph nodes) contend,
            # so whoever holds the lock traces and the rest run untraced.
            if trace_root and _TRACE_LOCK.acquire(blocking=False):
                import jax

                tdir = os.path.join(
                    trace_root,
                    f"{model_key.replace('/', '-')}_{itype.value}",
                )
                try:
                    with jax.profiler.trace(tdir):
                        result = fn(model, sample, mb_spec)
                finally:
                    _TRACE_LOCK.release()
            else:
                result = fn(model, sample, mb_spec)
            mfc_seconds = time.monotonic() - t0
            if itype == ModelInterfaceType.GENERATE:
                model.inc_version()  # advances the sampling seed per step

            out_sample = result if isinstance(result, SequenceSample) else None
            if out_sample is not None:
                out_sample.remap_keys_(remap_out)
            with tracer.span("mfc_perf", cat="host"):
                perf = self._mfc_perf(
                    model, itype, sample, out_sample, mfc_seconds
                )
            perf.update(tracer.take_compiles())
            mfc_label = f"{model_key}:{itype.value}"
            self._m_mfc_seconds.labels(mfc_label).observe(mfc_seconds)
            if "perf/mfu" in perf:
                self._m_mfc_mfu.labels(mfc_label).set(perf["perf/mfu"])
            self._m_mfc_tokens.labels(mfc_label).inc(
                int(sum(sum(s) for s in sample.seqlens[next(iter(sample.keys))]))
            )
            if tracer.enabled():
                targs["mfc"] = f"{model_key}:{itype.value}"
                # Same key preference as _mfc_perf: train samples carry
                # per-sequence scalar keys (rewards, ...) whose "lens"
                # are 1 — counting those as tokens poisons the profile.
                key0 = (
                    "packed_input_ids"
                    if "packed_input_ids" in sample.keys
                    else next(iter(sample.keys))
                )
                targs["tokens"] = int(
                    sum(sum(s) for s in sample.seqlens[key0])
                )
                targs["seqs"] = len(sample.seqlens[key0])
                if "perf/tflops" in perf:
                    targs["tflops"] = perf["perf/tflops"]
                if "perf/mfu" in perf:
                    targs["mfu"] = perf["perf/mfu"]
                self._span_profile_fields(model_key, model, targs)

        # Seconds of the handler's own: the mfc span under no child span.
        perf["perf/self_s"] = mfc_span.self_ns / 1e9
        perf.update(_hbm_perf(mfc_span.mark))
        _check_hbm_kill(perf)
        perf.update(self._own_host_record())
        if out_sample is not None:
            with tracer.span("mfc_scatter", cat="host", **_step(req)):
                for one in out_sample.unpack():
                    sid = one.ids[0]
                    if sid in self.data_cache:
                        self.data_cache[sid].update_(one)
                    else:
                        self.data_cache[sid] = one
                meta = out_sample.meta()
            return {"meta": meta, "stats": perf}
        return {"meta": None, "stats": {**dict(result or {}), **perf}}

    @staticmethod
    def _own_host_record() -> Dict[str, float]:
        """`host/<key>` stats (and, in the process's first reply,
        `setup/<key>`; `hbm/<key>` of the step closed last) for an MFC's
        reply where this worker runs in a process of its own; under the
        master's roof the master's step close reports the one host watch,
        the one set-up and the one HBM ledger they share."""
        if tracer.role() == "master":
            return {}
        return {
            **tracer.host_take(), **tracer.setup_take(), **tracer.hbm_take()
        }

    # ------------- pipeline-overlapped train stream -------------
    #
    # The master's streamed executor feeds TRAIN nodes one retired
    # rollout chunk at a time: mfc_stream_begin opens interface+engine
    # stream state, each mfc_stream_chunk computes that chunk's
    # advantages and accumulates grads (no optimizer step), and
    # mfc_stream_end fires the single scaled optimizer step and returns
    # the merged step stats.  Perf accounting sums the chunks' active
    # seconds (not begin→end wall, which includes master-paced gaps
    # while later chunks decode).

    def _handle_mfc_stream_begin(self, req):
        model_key: str = req["model_name"]
        if model_key in self._streams:
            raise RuntimeError(
                f"worker {self.config.worker_index}: train stream for "
                f"{model_key!r} already open"
            )
        model = self.models[model_key]
        interface = self.interfaces[model_key]
        mb_spec: MicroBatchSpec = req.get("mb_spec") or MicroBatchSpec()
        self._streams[model_key] = {
            "state": interface.train_stream_begin(model, mb_spec),
            "busy_s": 0.0,
            "tokens": 0,
            "seqs": 0,
            "sum_sq": 0.0,
            "n_chunks": 0,
            "compiles": {},
        }
        return {"meta": None, "stats": {}}

    def _handle_mfc_stream_chunk(self, req):
        model_key: str = req["model_name"]
        st = self._streams[model_key]
        model = self.models[model_key]
        interface = self.interfaces[model_key]
        mb_spec: MicroBatchSpec = req.get("mb_spec") or MicroBatchSpec()
        sample = self._assemble_sample(
            req["ids"],
            set(req["input_keys"]),
            req.get("shard_of") or {},
            req.get("shard_meta"),
            req.get("input_key_remap", {}),
        )
        tracer.take_compiles()
        # The fields below are stamped after the block: the span's event
        # holds this same dict, flushed later.
        with tracer.span(
            f"mfc:{model_key}:train_chunk", cat="compute",
            **_step(req),
        ) as targs:
            t0 = time.monotonic()
            stats = interface.train_stream_chunk(
                model, st["state"], sample, mb_spec
            )
            seconds = time.monotonic() - t0
        for k, v in tracer.take_compiles().items():
            st["compiles"][k] = st["compiles"].get(k, 0.0) + v
        st["busy_s"] += seconds
        st["n_chunks"] += 1
        # Prefer the packed key (see _mfc_perf): a scalar key's seqlens
        # are all 1, which would undercount the stream's token total and
        # poison the end-of-stream FLOP/MFU accounting.
        key0 = (
            "packed_input_ids"
            if "packed_input_ids" in sample.keys
            else next(iter(sample.keys))
        )
        lens = [sum(s) for s in sample.seqlens[key0]]
        st["tokens"] += int(sum(lens))
        st["seqs"] += len(lens)
        st["sum_sq"] += float(sum(l * l for l in lens))
        if tracer.enabled():
            targs["mfc"] = f"{model_key}:train_chunk"
            targs["tokens"] = int(sum(lens))
            targs["chunk"] = st["n_chunks"] - 1
        self._m_mfc_tokens.labels(f"{model_key}:train_chunk").inc(
            int(sum(lens))
        )
        return {"meta": None, "stats": dict(stats)}

    def _handle_train_stream_abort(self, req):
        """Drop every open train stream (accumulated grads and all) so a
        master recovering from a worker death can restart the step from a
        clean slate — a leaked stream would make the next
        mfc_stream_begin raise "already open"."""
        dropped = sorted(self._streams)
        self._streams.clear()
        if dropped:
            logger.warning(
                f"worker {self.config.worker_index}: aborted open train "
                f"stream(s) {dropped}"
            )
        return {"dropped": dropped}

    def _handle_mfc_stream_end(self, req):
        from areal_tpu.base import monitor

        model_key: str = req["model_name"]
        st = self._streams.pop(model_key)
        model = self.models[model_key]
        interface = self.interfaces[model_key]
        mb_spec: MicroBatchSpec = req.get("mb_spec") or MicroBatchSpec()
        tracer.take_compiles()
        mfc_span = tracer.span(
            f"mfc:{model_key}:train_step", cat="compute",
            **_step(req),
        )
        with mfc_span as targs:
            t0 = time.monotonic()
            result = interface.train_stream_end(
                model, st["state"], mb_spec
            )
            seconds = time.monotonic() - t0
        busy = st["busy_s"] + seconds
        perf = {"perf/time_s": busy, "perf/self_s": mfc_span.self_ns / 1e9}
        perf.update(_hbm_perf(mfc_span.mark))
        perf.update(self._own_host_record())
        for k, v in tracer.take_compiles().items():
            perf[k] = st["compiles"].get(k, 0.0) + v
        try:
            cfg = model.config
            if cfg is not None and st["tokens"]:
                flops = monitor.flops_train(cfg, st["tokens"], st["sum_sq"])
                perf["perf/tflops"] = flops / 1e12
                n_dev = (
                    model.engine.mesh.devices.size
                    if getattr(model.engine, "mesh", None) is not None
                    else 0
                )
                u = monitor.mfu(flops, busy, n_dev)
                if u is not None:
                    perf["perf/mfu"] = u
        except Exception as e:  # perf accounting must never fail the MFC
            logger.warning(f"perf accounting failed: {e!r}")
        mfc_label = f"{model_key}:train_step"
        self._m_mfc_seconds.labels(mfc_label).observe(busy)
        if "perf/mfu" in perf:
            self._m_mfc_mfu.labels(mfc_label).set(perf["perf/mfu"])
        if tracer.enabled():
            targs["mfc"] = mfc_label
            targs["stream_chunks"] = st["n_chunks"]
            targs["tokens"] = st["tokens"]
            targs["seqs"] = st["seqs"]
            # Busy seconds over all chunks + the optimizer step: the
            # span itself wraps only the latter (profile-store wall).
            targs["wall_s"] = round(busy, 6)
            if "perf/tflops" in perf:
                targs["tflops"] = perf["perf/tflops"]
            if "perf/mfu" in perf:
                targs["mfu"] = perf["perf/mfu"]
            self._span_profile_fields(model_key, model, targs)
        return {"meta": None, "stats": {**dict(result or {}), **perf}}

    def _span_profile_fields(self, model_key, model, targs) -> None:
        """Profile-store fields on MFC spans (analysis/profile.py keys
        records by them): mesh layout, model shape, and the engine's
        memory/compile counters."""
        targs["layout"] = self._layouts.get(model_key, "")
        cfg = model.config
        if cfg is not None:
            targs["model_shape"] = (
                f"l{cfg.n_layers}h{cfg.hidden_dim}q{cfg.n_q_heads}"
                f"kv{cfg.n_kv_heads}v{cfg.vocab_size}"
            )
        counters = getattr(model.engine, "perf_counters", None)
        if counters is not None:
            try:
                targs.update(counters())
            except Exception as e:  # accounting must never fail the MFC
                logger.warning(f"perf counters failed: {e!r}")

    def _mfc_perf(
        self, model, itype, sample, result, seconds: float
    ) -> Dict[str, float]:
        """Per-MFC wall time + analytic FLOPs + MFU (reference:
        system/flops_counter.py + master_worker.py:434-473)."""
        from areal_tpu.base import monitor

        perf = {"perf/time_s": seconds}
        if os.environ.get("AREAL_MFC_WALL_MARKERS"):
            # Debug-only overlap markers (async rollout vs training).  Raw
            # monotonic values: only comparable within ONE process — off by
            # default so distributed runs don't log cross-process garbage.
            now = time.monotonic()
            perf["perf/t_start"] = now - seconds
            perf["perf/t_end"] = now
        cfg = model.config
        if cfg is None:
            return perf
        try:
            flops = None
            if itype == ModelInterfaceType.GENERATE and result is not None:
                prompt_lens = [
                    sum(s) for s in sample.seqlens[next(iter(sample.keys))]
                ]
                out_lens = [
                    sum(s) for s in result.seqlens["packed_input_ids"]
                ]
                n_rep = max(len(out_lens) // max(len(prompt_lens), 1), 1)
                p_exp, g_lens = [], []
                for i, total in enumerate(out_lens):
                    p = prompt_lens[i // n_rep]
                    p_exp.append(p)
                    g_lens.append(max(total - p, 0))
                flops = monitor.flops_generate(cfg, p_exp, g_lens)
            else:
                key = (
                    "packed_input_ids"
                    if "packed_input_ids" in sample.keys
                    else next(iter(sample.keys))
                )
                lens = [sum(s) for s in sample.seqlens[key]]
                tokens = int(sum(lens))
                sum_sq = float(sum(l * l for l in lens))
                if itype == ModelInterfaceType.TRAIN_STEP:
                    flops = monitor.flops_train(cfg, tokens, sum_sq)
                else:
                    flops = monitor.flops_forward(cfg, tokens, sum_sq)
            if flops is not None:
                perf["perf/tflops"] = flops / 1e12
                n_dev = (
                    model.engine.mesh.devices.size
                    if getattr(model.engine, "mesh", None) is not None
                    else 0
                )
                u = monitor.mfu(flops, seconds, n_dev)
                if u is not None:
                    perf["perf/mfu"] = u
        except Exception as e:  # perf accounting must never fail the MFC
            logger.warning(f"perf accounting failed: {e!r}")
        return perf

    # ---------------- cross-worker transfer plane ----------------
    # The master orchestrates transfers as a concurrent (send, recv) request
    # pair; payloads are tagged with a master-assigned xfer_id so concurrent
    # transfers from different sources can't mismatch (reference: the
    # data_manager's planned NCCL redistribution, data_manager.py:144-416).

    def _recv_xfer(self, xfer_id: int, timeout: float = 300.0):
        import time

        deadline = time.monotonic() + timeout
        while True:
            with self._xfer_cond:
                while True:
                    if xfer_id in self._xfer_stash:
                        return self._xfer_stash.pop(xfer_id)
                    if not self._xfer_recv_busy:
                        self._xfer_recv_busy = True
                        break  # this thread becomes the socket receiver
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"worker {self.config.worker_index}: xfer "
                            f"{xfer_id} not received within {timeout}s"
                        )
                    self._xfer_cond.wait(remaining)
            try:
                got_id, payload = self.transfer.recv(
                    timeout=max(deadline - time.monotonic(), 0.001)
                )
                with self._xfer_cond:
                    if got_id == xfer_id:
                        return payload
                    self._xfer_stash[got_id] = payload
            finally:
                with self._xfer_cond:
                    self._xfer_recv_busy = False
                    self._xfer_cond.notify_all()

    def _handle_data_send(self, req):
        """Ship cached entries (selected keys) to another worker.  Replies
        with wire bytes + send seconds so the master can surface per-step
        transfer stats (reference: data_manager's redistribution timing)."""
        t0 = time.monotonic()
        keys = set(req["keys"])
        parts = []
        for sid in req["ids"]:
            entry = self.data_cache[sid]
            have = keys & entry.keys
            if not have:
                raise KeyError(
                    f"worker {self.config.worker_index}: no keys {keys} "
                    f"cached for id {sid}"
                )
            parts.append(entry.select_keys(have))
        nbytes = self.transfer.send(
            req["dst"], req["xfer_id"], ("data", parts)
        )
        return {"bytes": nbytes, "seconds": time.monotonic() - t0}

    def _handle_data_recv(self, req):
        t0 = time.monotonic()
        kind, parts = self._recv_xfer(req["xfer_id"])
        assert kind == "data", kind
        for one in parts:
            sid = one.ids[0]
            if sid in self.data_cache:
                self.data_cache[sid].update_(one)
            else:
                self.data_cache[sid] = one
        return {"n": len(parts), "seconds": time.monotonic() - t0}

    def _handle_param_send(self, req):
        """Ship a model's host-side param pytree to other workers (the
        cross-worker half of param realloc; reference model_worker.py:1009).
        Every member of a process-spanning src mesh calls this — the host
        gather is a collective — but only the designated sender pushes.

        With ``checksum`` set (the default, master-gated by
        ``weight_push_checksum``), the payload carries a content
        checksum stamped BEFORE the wire so the receiver can reject a
        push corrupted in flight instead of swapping poisoned weights in
        (see base/integrity.py).

        Serialize-once discipline: the (host gather, checksum, wire
        encoding) triple is computed once per distinct device tree and
        cached — ``send_many`` shares the encoding across every target,
        and a checksum-reject retry (the master re-dispatches this
        request wholesale) reuses the cache instead of re-gathering and
        re-pickling the full tree.  The cache is validated by OBJECT
        identity of the tree and its first leaf (jax updates replace
        leaf arrays, never mutates them), so a train step naturally
        invalidates it.  A poisoned attempt (`corrupt_push` chaos)
        encodes its corrupted copy fresh and never touches the cache —
        the retry must land the clean payload."""
        import jax

        from areal_tpu.base import integrity
        from areal_tpu.base.distributed import to_host
        from areal_tpu.system.paramstore import M_PUSH_BYTES

        t0 = time.monotonic()
        params = self.models[req["model_name"]].engine.get_params()
        leaves = jax.tree.leaves(params)
        cache = self._param_send_cache.get(req["model_name"])
        if (
            cache is None
            or cache["params"] is not params
            or (leaves and cache["leaf0"] is not leaves[0])
            or cache["with_checksum"] != bool(req.get("checksum", True))
        ):
            host = jax.tree.map(to_host, params)
            cache = {
                "params": params,
                "leaf0": leaves[0] if leaves else None,
                "with_checksum": bool(req.get("checksum", True)),
                "host": host,
                "checksum": (
                    integrity.params_checksum(host)
                    if req.get("checksum", True)
                    else None
                ),
                "encoded": None,
            }
            self._param_send_cache[req["model_name"]] = cache
        host, checksum = cache["host"], cache["checksum"]
        nbytes = 0
        if req.get("sender", True):
            encoded = cache["encoded"]
            if (
                self._faults is not None
                and self._faults.poison("weight_push") == "corrupt_push"
            ):
                host = integrity.corrupt_params(host)
                encoded = None  # poisoned payloads are never cached
            dsts = req.get("dsts") or [req["dst"]]
            xids = req.get("xfer_ids") or [req["xfer_id"]]
            payload = ("params", host, checksum)
            if encoded is None and host is cache["host"]:
                from areal_tpu.system.transfer import encode_oob

                encoded = cache["encoded"] = encode_oob(payload)
            nbytes = self.transfer.send_many(
                dsts, xids, payload, encoded=encoded
            )
            M_PUSH_BYTES.inc(nbytes)
        return {"bytes": nbytes, "seconds": time.monotonic() - t0}

    def _handle_param_recv(self, req):
        import jax

        from areal_tpu.base import integrity
        from areal_tpu.base.distributed import to_host

        t0 = time.monotonic()
        payload = self._recv_xfer(req["xfer_id"])
        kind, host, checksum = (
            payload if len(payload) == 3 else (*payload, None)
        )
        assert kind == "params", kind
        if checksum is not None:
            # Fail fast BEFORE set_params: a rejected push leaves the
            # receiver serving its previous (healthy) weights.
            integrity.verify_checksum(host, checksum)
        eng = self.models[req["model_name"]].engine
        eta = float(req.get("eta", 1.0))
        if eta >= 1.0:
            eng.set_params(host)
        else:
            cur = jax.tree.map(to_host, eng.get_params())
            mixed = jax.tree.map(
                lambda a, b: eta * np.asarray(a, np.float32)
                + (1 - eta) * np.asarray(b, np.float32),
                host,
                cur,
            )
            eng.set_params(mixed)
        return {"seconds": time.monotonic() - t0}

    def _handle_release_params(self, req):
        """Drop an aliasing generator's weight reference ahead of the
        colocated train step (master: _release_aliased_generators).  Only
        engines that opted out of the defensive swap copy hold an alias
        worth releasing; everything else (donation-safe generators,
        remote/inference engines) answers released=False untouched."""
        eng = self.models[req["model_name"]].engine
        if (
            getattr(eng, "donation_safe_swap", True) is False
            and hasattr(eng, "release_params")
        ):
            eng.release_params()
            return {"released": True}
        return {"released": False}

    def _handle_param_sync(self, req):
        """Copy/EMA params src -> dst (generator hot-swap, EMA ref).
        Reference: param_realloc hooks (model_worker.py:1009)."""
        import jax

        src = self.models[req["src"]].engine
        dst = self.models[req["dst"]].engine
        eta = float(req.get("eta", 1.0))
        # The master's span of the same name times the RPC; this one is
        # the work, and the engine's statement spans nest inside it.
        with tracer.span(
            f"param_sync:{req['dst']}", cat="comms", **_step(req)
        ):
            t0 = time.monotonic()
            params = src.get_params()
            if eta < 1.0:
                params = jax.tree.map(
                    lambda a, b: eta * a + (1 - eta) * b,
                    params, dst.get_params(),
                )
            dst.set_params(params)
            seconds = time.monotonic() - t0
        # What the engine says it placed (global bytes from shapes, leaves
        # and bytes by route, the seconds of the placement it waited for);
        # engines that keep no such record moved the tree they were handed.
        stats = dict(getattr(dst, "last_sync_stats", None) or {})
        if "bytes" not in stats:
            from areal_tpu.parallel.realloc import tree_bytes

            stats["bytes"] = float(tree_bytes(params))
        stats["time_s"] = seconds
        return {"sync": stats}

    def _handle_save(self, req):
        key = req["model_name"]
        self.interfaces[key].save(self.models[key], req["save_dir"])
        return {"path": req["save_dir"]}

    def _handle_load_model(self, req):
        """Restore a model's weights (and optionally optimizer state) from
        a checkpoint dir — the worker half of trial recovery (reference:
        model_worker recover path via make_model from recover ckpts)."""
        from areal_tpu.models.hf import registry as hf

        key = req["model_name"]
        model = self.models[key]
        _, params = hf.load_hf_checkpoint(
            req["ckpt_dir"],
            is_critic=bool(model.config is not None and model.config.is_critic),
            dtype=np.float32,  # exact recover: ckpts store f32 masters
        )
        model.engine.set_params(params)
        opt = req.get("optimizer_path")
        if opt and os.path.exists(opt) and hasattr(
            model.engine, "load_optimizer_state"
        ):
            model.engine.load_optimizer_state(opt)
        return {}

    def _handle_data_state(self, req):
        return {"states": [dl.state_dict() for dl in self.dataloaders]}

    def _handle_interface_state(self, req):
        """Algorithm state per model (e.g. value-norm moments) for recover
        checkpoints."""
        out = {}
        for key, iface in self.interfaces.items():
            sd = iface.state_dict()
            if sd:
                out[key] = sd
        return {"states": out}

    def _handle_load_interface_state(self, req):
        for key, sd in (req.get("states") or {}).items():
            if key in self.interfaces:
                self.interfaces[key].load_state_dict(sd)
        return {}

    def _handle_load_data_state(self, req):
        for dl, sd in zip(self.dataloaders, req["states"]):
            dl.load_state_dict(sd)
        return {}

    def _handle_save_optimizer(self, req):
        eng = self.models[req["model_name"]].engine
        os.makedirs(os.path.dirname(req["path"]), exist_ok=True)
        eng.save_optimizer_state(req["path"])
        return {}

    def _handle_offload(self, req):
        """Host-offload a model's device state (OffloadHook; reference
        model_worker.py:1009 offload path).  Reload is transparent on the
        engine's next call."""
        eng = self.models[req["model_name"]].engine
        if eng is not None and hasattr(eng, "offload"):
            eng.offload()
        return {}

    def _handle_data_accuracy(self, req):
        """Per-id mean success over a group's rewards (the input to dynamic
        difficulty filtering; reference model_worker.py:574-639)."""
        out = {}
        for sid in req["ids"]:
            entry = self.data_cache.get(sid)
            if entry is None or "rewards" not in entry.keys:
                continue
            r = np.asarray(entry.data["rewards"], np.float32)
            out[sid] = float((r > 0).mean()) if r.size else 0.0
        return {"accuracy": out}

    def _handle_clear_cache(self, req):
        with tracer.span("clear_cache", cat="host", **_step(req)):
            keep = set(req.get("keep_ids", ()))
            for sid in list(self.data_cache):
                if sid not in keep:
                    del self.data_cache[sid]
        # Once-per-step broadcast from the master: a natural trace flush
        # point so shards stay current even if the worker later crashes,
        # and where a worker in a process of its own closes its step
        # ledger (under the master's roof the master closes the one they
        # share).
        if tracer.role() != "master":
            tracer.close_step(req.get("step", 0))
        tracer.flush()
        return {}

    def _handle_filter_dataset(self, req):
        removed = 0
        for ds in self.datasets:
            removed += int(ds.filter(req["ids"]) or 0)
        return {"removed": removed}

    def _handle_model_versions(self, req):
        """Per-model weight-version counters — inventoried into the
        recover checkpoint's MANIFEST.json and RecoverInfo."""
        return {
            "versions": {k: int(m.version) for k, m in self.models.items()}
        }

    def _handle_set_model_versions(self, req):
        for k, v in (req.get("versions") or {}).items():
            if k in self.models:
                self.models[k].version = int(v)
        return {}

    def _handle_ping(self, req):
        return {"pong": self.config.worker_index}


class _Cycler:
    """Endless epoch iterator over a PackedDataLoader, with a resumable
    (epoch, cursor) position: shuffling is seeded per epoch, so replaying
    `cursor` batches restores the exact data stream — the mechanism behind
    recover's no-resample guarantee (reference tracks consumed-data hashes
    instead, master_worker.py:113-155)."""

    def __init__(self, loader):
        self.loader = loader
        self.epoch = 0
        self.cursor = 0  # batches already yielded in the current epoch
        self._it = None

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            if self._it is None:
                self._it = iter(self.loader)
            try:
                batch = next(self._it)
                self.cursor += 1
                return batch
            except StopIteration:
                self._it = None
                self.epoch += 1
                self.cursor = 0

    def state_dict(self):
        return {"epoch": self.epoch, "cursor": self.cursor}

    def load_state_dict(self, state):
        self.epoch = int(state["epoch"])
        self.cursor = 0
        # PackedDataLoader increments its epoch counter per __iter__; align
        # it, then replay the already-consumed batches of this epoch.
        self.loader._epoch = self.epoch
        self._it = None
        for _ in range(int(state["cursor"])):
            next(self)
