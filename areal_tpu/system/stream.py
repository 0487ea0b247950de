"""ZMQ master⇄worker request-reply stream for multi-process trials.

Capability parity: realhf/system/request_reply_stream.py (ZMQ PUSH/PULL
pairs + a syn-ack protocol for ordered delivery) — simplified: one ROUTER
socket on the master and a DEALER per worker gives per-peer FIFO ordering
from ZMQ/TCP itself, so no syn-ack layer is needed.  Request/response
matching uses explicit request ids (the master pipelines many concurrent
requests per worker from the asyncio DFG executor).

Discovery mirrors the reference: the master publishes its tcp address via
name_resolve (names.request_reply_stream) and every worker announces itself
with a hello frame carrying its index.

Payloads are pickled python dicts (SequenceSample metadata/arrays are
numpy-based); this is the CONTROL plane — bulk tensors live on device and
move via jax collectives / device_put (areal_tpu/parallel/realloc.py).

Liveness: each worker runs a heartbeat thread on its OWN dealer socket
(zmq sockets are single-threaded; the serve loop blocks for the whole
duration of an inline MFC, so beats must not share its socket) sending
``{"type": "beat", "worker_index": i}`` every ``worker_heartbeat_s``.
``ZMQWorkerPool.request`` takes a deadline (default: the pool's
``mfc_timeout_s``); on expiry a fresh heartbeat means "slow" (the
deadline re-arms), a stale one means "dead" — the worker's in-flight
futures fail with ``WorkerDeadError`` and its hello slot is cleared so
``wait_workers`` re-arms for a relaunched replacement.  With
``mfc_timeout_s=None`` (the default) the request path is the original
single ``await`` — zero overhead off the hot path.
"""

import asyncio
import pickle
from collections import deque
from typing import Any, Dict, Optional, Set, Tuple

import zmq
import zmq.asyncio

from areal_tpu.base import logging, name_resolve, names, network
from areal_tpu.system.master import (
    PoolClosedError,
    WorkerDeadError,
    WorkerPool,
    pool_metrics,
)

logger = logging.getLogger("stream")

STREAM_NAME = "master"

# req_ids of deadline-expired requests, kept so a late reply is dropped as
# an ACCOUNTED orphan (debug log + counter), not warned as an anomaly.
# Bounded: timed-out ids older than this many entries age out and a
# straggler reply for them downgrades to the "unknown" reason.
_TIMED_OUT_KEEP = 4096

_UNSET = object()


class ZMQWorkerPool(WorkerPool):
    """Master side: ROUTER socket, one outstanding-request table."""

    def __init__(
        self,
        experiment_name: str,
        trial_name: str,
        n_workers: int,
        mfc_timeout_s: Optional[float] = None,
        worker_heartbeat_s: float = 5.0,
    ):
        self._n_workers = n_workers
        self.mfc_timeout_s = mfc_timeout_s
        self.worker_heartbeat_s = worker_heartbeat_s
        # A worker is "dead" only when a deadline expired AND its beats
        # are older than this grace (3 missed beats); a long blocking MFC
        # keeps beating from its heartbeat thread and stays "slow".
        self._beat_grace_s = max(3.0 * worker_heartbeat_s, 1.0)
        self._ctx = zmq.asyncio.Context()
        self._sock = self._ctx.socket(zmq.ROUTER)
        # bind_to_random_port probes and binds atomically (no TOCTOU).
        port = self._sock.bind_to_random_port("tcp://*")
        host = network.gethostip()
        self._addr = f"tcp://{host}:{port}"
        name_resolve.add(
            names.request_reply_stream(experiment_name, trial_name, STREAM_NAME),
            self._addr,
            replace=True,
        )
        # req_id -> (future, worker_id); worker_id lets a death fail
        # exactly the futures parked on the dead peer.
        self._pending: Dict[int, Tuple[asyncio.Future, int]] = {}
        self._hello: Dict[int, bytes] = {}  # worker index -> zmq identity
        self._ident2worker: Dict[bytes, int] = {}
        self._hello_event = asyncio.Event()
        self._last_beat: Dict[int, float] = {}  # worker index -> loop time
        self._dead_workers: Set[int] = set()
        self._timed_out: Set[int] = set()
        self._timed_out_order: deque = deque()
        self._next_req_id = 0
        self._recv_task = None
        self._closed = False
        self._m_worker_dead, self._m_mfc_timeout, self._m_orphans = (
            pool_metrics()
        )
        logger.info(f"master stream bound at {self._addr}")

    @property
    def n_workers(self) -> int:
        return self._n_workers

    @property
    def dead_workers(self) -> Set[int]:
        return set(self._dead_workers)

    def _ensure_recv_loop(self):
        if self._recv_task is None:
            self._recv_task = asyncio.get_running_loop().create_task(
                self._recv_loop()
            )

    def _note_beat(self, worker_index: int):
        self._last_beat[worker_index] = asyncio.get_running_loop().time()

    def _fail_pending(self, exc: Exception):
        for fut, _wid in self._pending.values():
            if not fut.done():
                fut.set_exception(exc)
        self._pending.clear()

    async def _recv_loop(self):
        try:
            while True:
                ident, payload = await self._sock.recv_multipart()
                try:
                    msg = pickle.loads(payload)
                except Exception as e:  # corrupt frame: drop, keep serving
                    logger.error(f"undecodable frame from {ident!r}: {e!r}")
                    continue
                mtype = msg.get("type")
                if mtype == "beat":
                    self._note_beat(int(msg["worker_index"]))
                    continue
                if mtype == "hello":
                    widx = int(msg["worker_index"])
                    self._hello[widx] = ident
                    self._ident2worker[ident] = widx
                    self._note_beat(widx)
                    if widx in self._dead_workers:
                        # A relaunched replacement re-announced itself:
                        # it is a fresh peer with no model state (the
                        # master replays it via _restore_worker_state).
                        self._dead_workers.discard(widx)
                        logger.info(f"worker {widx} re-joined the stream")
                    if len(self._hello) >= self._n_workers:
                        self._hello_event.set()
                    continue
                req_id = msg.get("req_id")
                entry = self._pending.pop(req_id, None)
                widx = self._ident2worker.get(ident)
                if widx is not None:
                    # Any traffic is proof of life.
                    self._note_beat(widx)
                if entry is None:
                    if req_id in self._timed_out:
                        # Late reply to a deadline-expired request: the
                        # normal aftermath of a "slow" verdict, accounted
                        # and dropped without alarm.
                        self._m_orphans.labels("timed_out").inc()
                        logger.debug(
                            f"late reply for timed-out req_id={req_id} "
                            "dropped"
                        )
                    else:
                        self._m_orphans.labels("unknown").inc()
                        logger.warning(f"orphan reply req_id={req_id}")
                    continue
                fut, _wid = entry
                if fut.done():  # request cancelled during teardown
                    continue
                if msg.get("error"):
                    fut.set_exception(RuntimeError(msg["error"]))
                else:
                    fut.set_result(msg["result"])
        except asyncio.CancelledError:
            # Pool teardown must not strand awaiting requests: anyone
            # still parked on a future gets a typed "pool closed" error
            # instead of hanging forever.
            self._fail_pending(PoolClosedError("worker pool closed"))
            raise
        except Exception as e:
            # A dead recv loop must not strand awaiting requests: fail them.
            logger.error(f"stream recv loop died: {e!r}")
            self._fail_pending(RuntimeError(f"stream recv loop died: {e!r}"))
            raise

    async def wait_workers(self, timeout: float = 300.0):
        """Block until every worker has said hello.

        Re-armable: a worker declared dead clears its hello slot and the
        event, so a second call waits for the relaunched replacement.
        """
        self._ensure_recv_loop()
        await asyncio.wait_for(self._hello_event.wait(), timeout)
        logger.info(f"all {self._n_workers} workers connected")

    def _record_timed_out(self, req_id: int):
        self._timed_out.add(req_id)
        self._timed_out_order.append(req_id)
        while len(self._timed_out_order) > _TIMED_OUT_KEEP:
            self._timed_out.discard(self._timed_out_order.popleft())

    def _fail_worker(self, worker_id: int, reason: str):
        """Declare a worker dead: fail its in-flight futures, clear its
        hello slot so wait_workers re-arms, count the death."""
        if worker_id in self._dead_workers:
            return
        self._dead_workers.add(worker_id)
        self._m_worker_dead.inc()
        ident = self._hello.pop(worker_id, None)
        if ident is not None:
            self._ident2worker.pop(ident, None)
        self._hello_event.clear()
        err = WorkerDeadError(worker_id, reason)
        for req_id in [
            r for r, (_f, w) in self._pending.items() if w == worker_id
        ]:
            fut, _w = self._pending.pop(req_id)
            self._record_timed_out(req_id)
            if not fut.done():
                fut.set_exception(err)
        logger.error(f"worker {worker_id} declared dead: {reason}")

    async def request(
        self,
        worker_id: int,
        payload: Dict[str, Any],
        timeout: Any = _UNSET,
    ) -> Dict:
        self._ensure_recv_loop()
        if worker_id in self._dead_workers:
            raise WorkerDeadError(
                worker_id, "worker previously declared dead"
            )
        if not self._hello_event.is_set():
            await self.wait_workers()
        req_id = self._next_req_id
        self._next_req_id += 1
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._pending[req_id] = (fut, worker_id)
        msg = pickle.dumps(
            {"req_id": req_id, "request": self.stamp(payload)}
        )
        await self._sock.send_multipart([self._hello[worker_id], msg])
        if timeout is _UNSET:
            timeout = self.mfc_timeout_s
        if timeout is None:
            return await fut
        # Deadline lane.  shield() keeps the future alive across each
        # wait_for slice; on expiry a fresh heartbeat re-arms the
        # deadline ("slow"), a stale one declares the worker dead.
        deadline = loop.time() + timeout
        poll_s = min(timeout, max(self.worker_heartbeat_s, 0.05))
        while True:
            try:
                return await asyncio.wait_for(asyncio.shield(fut), poll_s)
            except asyncio.TimeoutError:
                if fut.done():
                    return fut.result()
                if loop.time() < deadline:
                    continue
                self._m_mfc_timeout.inc()
                beat_age = loop.time() - self._last_beat.get(
                    worker_id, -1e18
                )
                if beat_age <= self._beat_grace_s:
                    logger.warning(
                        f"request {req_id} ({payload.get('type')}) to "
                        f"worker {worker_id} exceeded {timeout}s but the "
                        f"worker is beating (last beat {beat_age:.1f}s "
                        "ago): slow, not dead — deadline re-armed"
                    )
                    deadline = loop.time() + timeout
                    continue
                self._fail_worker(
                    worker_id,
                    f"no reply to {payload.get('type')} within {timeout}s "
                    f"and no heartbeat for {beat_age:.1f}s "
                    f"(grace {self._beat_grace_s:.1f}s)",
                )
                # _fail_worker failed this future with WorkerDeadError.
                return await fut

    async def broadcast(self, payload: Dict[str, Any]):
        # Dead workers are skipped: a post-recovery exit/abort broadcast
        # must not hang on (or instantly fail over) a corpse.
        return await asyncio.gather(
            *[
                self.request(w, payload)
                for w in range(self._n_workers)
                if w not in self._dead_workers
            ]
        )

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._recv_task is not None:
            self._recv_task.cancel()
        # The cancelled recv loop also fails pending, but only once the
        # event loop runs it again — which never happens when close() is
        # the loop's last act.  Fail synchronously too (idempotent).
        try:
            self._fail_pending(PoolClosedError("worker pool closed"))
        except Exception:  # futures on an already-closed loop
            pass
        self._sock.close(linger=0)
        self._ctx.term()


# Request types served on background threads: the transfer-plane recv side
# BLOCKS until the peer's send lands, so a serial loop could deadlock when
# the master dispatches send/recv pairs between two workers concurrently
# (each stuck in recv while the matching send sits queued behind it).
# Compute requests stay serial, matching the reference's one-blocking-
# request-at-a-time model worker (model_worker.py:667).
_THREADED_TYPES = frozenset(
    {"data_send", "data_recv", "param_send", "param_recv"}
)


def _start_heartbeat(
    ctx, addr: str, worker_index: int, heartbeat_s: float
):
    """Heartbeat lane: its OWN dealer socket (zmq sockets are not
    thread-safe and the serve loop's socket blocks for the whole span of
    an inline MFC), beating every ``heartbeat_s`` until stopped.  The
    thread dies with the process — which is exactly the signal: beats
    stop iff the worker process is gone, while a hung or slow MFC keeps
    beating and stays "slow" to the master."""
    import threading

    stop = threading.Event()

    def _beat():
        sock = ctx.socket(zmq.DEALER)
        sock.connect(addr)
        frame = pickle.dumps(
            {"type": "beat", "worker_index": worker_index}
        )
        try:
            while not stop.is_set():
                sock.send(frame)
                stop.wait(heartbeat_s)
        finally:
            sock.close(linger=0)

    t = threading.Thread(
        target=_beat, name=f"heartbeat-{worker_index}", daemon=True
    )
    t.start()
    return stop


def run_worker_stream(
    worker,  # ModelWorker
    experiment_name: str,
    trial_name: str,
    timeout: float = 300.0,
    control=None,  # Optional[worker_control.WorkerServer]
    heartbeat_s: Optional[float] = None,
) -> None:
    """Worker side: connect, announce, serve requests until 'exit'."""
    import os
    import queue
    import threading

    if heartbeat_s is None:
        heartbeat_s = float(
            os.environ.get("AREAL_WORKER_HEARTBEAT_S", "5.0")
        )
    addr = name_resolve.wait(
        names.request_reply_stream(experiment_name, trial_name, STREAM_NAME),
        timeout=timeout,
    )
    ctx = zmq.Context()
    sock = ctx.socket(zmq.DEALER)
    sock.connect(addr)
    sock.send(
        pickle.dumps(
            {"type": "hello", "worker_index": worker.config.worker_index}
        )
    )
    logger.info(
        f"worker {worker.config.worker_index} connected to master at {addr}"
    )
    beat_stop = None
    if heartbeat_s > 0:
        beat_stop = _start_heartbeat(
            ctx, addr, worker.config.worker_index, heartbeat_s
        )

    replies: "queue.Queue[bytes]" = queue.Queue()
    threads: list = []

    def _serve(req, req_id):
        try:
            result = worker.handle_request(req)
            reply = {"req_id": req_id, "result": result}
        except Exception as e:  # noqa: BLE001 — forwarded to master
            logger.error(
                f"worker {worker.config.worker_index} request "
                f"{req.get('type')} failed: {e!r}"
            )
            reply = {"req_id": req_id, "error": repr(e)}
        replies.put(pickle.dumps(reply))

    def _drain_replies():
        while True:
            try:
                sock.send(replies.get_nowait())
            except queue.Empty:
                return

    try:
        while True:
            # Controller-initiated exit (side channel; see worker_control).
            if control is not None and control.state.value == "exiting":
                for t in threads:  # in-flight transfers finish first
                    t.join(timeout=timeout)
                _drain_replies()
                break
            if not sock.poll(100):
                _drain_replies()
                continue
            msg = pickle.loads(sock.recv())
            req = msg["request"]
            # A paused worker parks non-exit requests until the controller
            # resumes it (pausing mid-step stalls the trial; reference:
            # worker_base.py PAUSED state gating _poll).  Exit requests —
            # master shutdown broadcast OR controller side channel — are
            # never parked, so teardown cannot deadlock on a paused
            # worker.
            if control is not None and req.get("type") != "exit":
                while control.paused and control.state.value != "exiting":
                    control.wait_if_paused(timeout=0.5)
            if req.get("type") == "exit":
                for t in threads:
                    t.join(timeout=timeout)
                _drain_replies()
                sock.send(
                    pickle.dumps({"req_id": msg["req_id"], "result": {}})
                )
                break
            if req.get("type") in _THREADED_TYPES:
                t = threading.Thread(
                    target=_serve, args=(req, msg["req_id"]), daemon=True
                )
                t.start()
                threads.append(t)
                threads = [t for t in threads if t.is_alive()]
            else:
                _serve(req, msg["req_id"])
            _drain_replies()
    finally:
        if beat_stop is not None:
            beat_stop.set()
        sock.close(linger=0)
        ctx.term()
