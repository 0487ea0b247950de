"""Decoupled generation service: HTTP server around a GeneratorEngine.

Capability parity: realhf/impl/model/backend/sglang.py — the reference
spawns one SGLang HTTP server per generation DP rank (:161-226), streams
per-request generation with logprobs over REST (:267-352), and refreshes
weights from disk after each train step (:383 `update_weights_from_disk`).
TPU version: the in-repo continuous-batching GeneratorEngine IS the
inference runtime, so the server is a thin stdlib-HTTP shell around it:

- POST /generate  — one prompt (+ sampling params) per request; concurrent
  requests are MERGED by a collector thread into shared engine calls, so
  client-side fan-out gets true cross-request batching.
- POST /update_weights — hot-swap from an HF checkpoint dir.
- GET  /health — liveness + current weight version.

Two transports share that collector, BOTH on by default:
- HTTP (ThreadingHTTPServer): the ops/debug surface — curl-able, JSON.
  Thread-per-request, fine for humans and health checks; not the plane
  a multi-rank trainer should pump thousands of requests through.
- ZMQ ROUTER (`zmq_port`, default 0 = auto-bind): the high-throughput
  trainer plane — JSON frames, one DEALER connection per client
  pipelining any number of in-flight requests with rid correlation, no
  thread-per-request.  The `zmq://host:port` URL scheme selects it in
  RemoteGeneratorEngine; the CLI prints both URLs and experiment
  configs should point `gen_server_url` at the zmq one for serving at
  rank scale (`zmq_port=None` turns the plane off).

`RemoteGeneratorEngine` (backend "remote_generator") makes a model worker
talk to such a server instead of holding generation weights itself — the
reference's decoupled `sglang.dXpYmZ+...` allocation shape, with the
param-sync hook saving a checkpoint and POSTing /update_weights exactly
like the reference's disk-based weight refresh (model_worker.py:1040-1067).
"""

import dataclasses
import json
import os
import queue
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    APIGenerateInput,
    APIGenerateOutput,
    BoundedAgenerateMixin,
    Engine,
    GenerationHyperparameters,
    LLMAPIClient,
    SlotGoneError,
    register_backend,
)
from areal_tpu.base import integrity, logging, metrics, tracer
from areal_tpu.base.faults import FaultInjector

logger = logging.getLogger("gen_server")

# Module-level registration (replay.py idiom): the registry's
# get-or-create already makes every server in a process share one
# series per name, so per-instance handles would alias these anyway —
# and helper methods like _fail_request must work on partially
# constructed instances (tests build them via __new__).
_REG = metrics.default_registry()
_M_QUEUE_DEPTH = _REG.gauge(
    "areal_gen_queue_depth",
    "requests waiting in the batching collector queue",
)
_M_REQUESTS = _REG.counter(
    "areal_gen_requests_total",
    "generate requests finished, by terminal status",
    ("status",),
)
_M_REQUEST_SECONDS = _REG.histogram(
    "areal_gen_request_seconds",
    "request latency, enqueue to reply",
)
_M_BATCHES = _REG.counter(
    "areal_gen_batches_total", "collector batches dispatched"
)
_M_WEIGHT_VERSION = _REG.gauge(
    "areal_gen_weight_version", "current serving weight version"
)
_M_WEIGHT_UPDATES = _REG.counter(
    "areal_gen_weight_updates_total", "weight swaps applied"
)
_M_CAPACITY = _REG.gauge(
    "areal_gen_capacity_slots", "max concurrent decode slots"
)
_M_PAUSED = _REG.gauge(
    "areal_gen_paused", "1 while paused for a weight swap"
)
_M_FAULTS = _REG.counter(
    "areal_gen_faults_total",
    "injected chaos faults fired (AREAL_FAULTS), by kind",
    ("kind",),
)
# Episode continuations rejected because the engine reclaimed the slot
# (eviction under pool pressure / restart) — each one costs the
# controller a full-conversation re-admission through the prefix cache.
_M_EPISODE_SLOT_LOST = _REG.counter(
    "areal_gen_episode_slot_lost_total",
    "episode continuations rejected: slot reclaimed",
)


@dataclasses.dataclass
class _Pending:
    qid: str
    prompt_ids: List[int]
    gconfig: GenerationHyperparameters
    done: threading.Event
    seed: Optional[int] = None
    result: Optional[Dict[str, Any]] = None
    error: Optional[str] = None
    # Enqueue timestamp (monotonic ns) — the request lifetime span in the
    # trace runs from here to completion, covering queue + batch-merge wait.
    t_enq: Optional[int] = None
    # Causal-lineage id carried over the transport (X-Areal-Trace header
    # / ZMQ frame field); stamps the request span + lineage instants so
    # the sample joins its dispatcher's root in the merged trace.
    trace_id: Optional[str] = None


def _gkey(p: _Pending):
    g = p.gconfig
    # Seed is part of the key: requests merged into one engine call share
    # one PRNG stream, so a seeded trainer's batch never co-samples with
    # other clients' requests (stream ISOLATION).  Bitwise replay across
    # runs is NOT guaranteed — group composition still follows HTTP
    # arrival timing; exact-replay trainers should use the in-process
    # generator.
    return (g.n, g.max_new_tokens, g.min_new_tokens, g.greedy, g.top_p,
            g.top_k, g.temperature, g.spec_decode_k, g.spec_ngram, g.stop,
            p.seed)


class GenerationServer:
    """Batching HTTP front-end over one GeneratorEngine."""

    def __init__(
        self,
        engine,  # GeneratorEngine
        host: str = "127.0.0.1",
        port: int = 0,
        max_wait_ms: float = 5.0,
        max_batch: int = 256,
        token: str = "",
        ckpt_root: str = "",
        zmq_port: Optional[int] = 0,  # 0 = random; None = HTTP only
        # Chaos (base/faults.py): defaults to the env-gated AREAL_FAULTS
        # spec, so a chaos harness breaks the REAL server binary.
        faults: Optional[FaultInjector] = None,
        # Starting weight version — a restarted fleet member rejoins at
        # the trainer's current version instead of 0 (which would make
        # every response it serves look maximally stale).
        version: int = 0,
    ):
        self.engine = engine
        self.version = int(version)
        # /update_weights loads an arbitrary path and hot-swaps serving
        # weights: restrict it to a checkpoint root when configured.
        self.ckpt_root = ckpt_root or os.environ.get(
            "AREAL_GEN_CKPT_ROOT", ""
        )
        self.max_wait_ms = max_wait_ms
        self.max_batch = max_batch
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        self._stop = threading.Event()
        self._seed = 0
        # Serializes weight swaps against in-flight generation: a batch
        # must run wholly under one weight version, and its outputs must be
        # stamped with that version.
        self._engine_lock = threading.Lock()
        # pause/resume control (async RL): pause() interrupts the engine
        # at its next chunk boundary; the parked _run_subgroup releases
        # the engine lock and waits here until resume().
        self._pause_evt = threading.Event()
        self._resume_cond = threading.Condition()
        # Serializes in-memory weight pushes (each is pause→swap→resume).
        self._update_mutex = threading.Lock()
        self.inmem_updates = 0
        # Guards the (version, paused) pair health_info() reports: a
        # poll landing mid-swap must see a consistent snapshot, not a
        # new version with stale pause state (or vice versa).
        self._health_lock = threading.Lock()
        _M_CAPACITY.set(int(getattr(engine, "max_decode_batch", 0) or 0))
        self._faults = faults if faults is not None else FaultInjector.from_env()
        if self._faults is not None and self._faults.on_fire is None:
            self._faults.on_fire = lambda kind: _M_FAULTS.labels(kind).inc()
        # Fleet membership (announce()): the keepalive key + beat thread.
        self._announce_key: Optional[str] = None
        self._announce_thread: Optional[threading.Thread] = None
        # A kill fault tears down WITHOUT deregistering (a preempted node
        # runs no graceful teardown; its announcement expires by TTL).
        self._crashed = False
        # episode_id -> trace_id: extend/release turns join the lineage
        # root their start op carried (ops on one episode are serialized
        # by the controller, so plain dict ops under the GIL suffice).
        self._episode_traces: Dict[str, str] = {}

        srv = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                logger.debug(fmt % args)

            def _send(self, code: int, payload: Dict):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/health":
                    self._send(200, srv.health_info())
                elif self.path.split("?")[0] == "/metrics":
                    body = metrics.default_registry().expose().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._send(404, {"error": "unknown path"})

            def do_POST(self):
                if srv._token and (
                    self.headers.get("X-Areal-Token") != srv._token
                ):
                    self._send(403, {"error": "bad token"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    if self.path == "/param_push":
                        # Binary plane (system/paramstore.py): the body
                        # is a meta-length prefix + meta JSON + the raw
                        # serialized params — it must never reach the
                        # JSON parse below.
                        from areal_tpu.system import paramstore

                        meta, blob = paramstore.unframe_push_body(
                            self.rfile.read(n)
                        )
                        self._send(200, srv._handle_param_push(meta, blob))
                        return
                    req = json.loads(self.rfile.read(n))
                    # Trace context rides the header so any client (or a
                    # proxy) can stamp it without touching the body.
                    trace_hdr = self.headers.get("X-Areal-Trace")
                    if trace_hdr and isinstance(req, dict):
                        req.setdefault("trace_id", trace_hdr)
                    if self.path == "/generate":
                        self._send(200, srv._handle_generate(req))
                    elif self.path == "/episode":
                        self._send(200, srv.handle_episode(req))
                    elif self.path == "/update_weights":
                        self._send(200, srv._handle_update(req))
                    elif self.path == "/pause":
                        srv.pause()
                        self._send(
                            200, {"paused": True, "version": srv.version}
                        )
                    elif self.path == "/resume":
                        srv.resume()
                        self._send(
                            200, {"paused": False, "version": srv.version}
                        )
                    else:
                        self._send(404, {"error": "unknown path"})
                except SlotGoneError as e:
                    # Typed rejection, NOT a silent fresh admission: the
                    # controller decides to re-admit the conversation.
                    self._send(
                        409,
                        {
                            "error": str(e),
                            "error_type": "slot_gone",
                            "episode_id": e.episode_id,
                            "reason": e.reason,
                        },
                    )
                except Exception as e:  # noqa: BLE001 — report to client
                    self._send(500, {"error": repr(e)})

        self._token = token or os.environ.get("AREAL_GEN_TOKEN", "")
        if not self._token and host not in ("127.0.0.1", "localhost", "::1"):
            # An open bind without auth lets any peer repoint the serving
            # weights via /update_weights.
            if os.environ.get("AREAL_GEN_INSECURE") != "1":
                raise ValueError(
                    f"refusing to bind {host} without a token: set "
                    "token=/AREAL_GEN_TOKEN, or AREAL_GEN_INSECURE=1 to "
                    "serve an open network port anyway"
                )
            logger.warning(
                f"INSECURE: serving on {host} with no auth token — any "
                "process that can reach the port can swap the model"
            )
        self._http = ThreadingHTTPServer((host, port), _Handler)
        self.port = self._http.server_port
        self.url = f"http://{host}:{self.port}"
        self._http_thread = threading.Thread(
            target=self._http.serve_forever, daemon=True
        )
        self._collector_thread = threading.Thread(
            target=self._collect_loop, daemon=True
        )
        self._http_thread.start()
        self._collector_thread.start()
        self.zmq_port: Optional[int] = None
        self.zmq_url: Optional[str] = None
        if zmq_port is not None:
            self._start_zmq(host, zmq_port)
        if self._faults is not None and self._faults.kill_spec is not None:
            threading.Thread(target=self._kill_loop, daemon=True).start()
        logger.info(
            f"generation server at {self.url}"
            + (f" + {self.zmq_url}" if self.zmq_url else "")
        )

    # ---------------- chaos (base/faults.py) ----------------

    def _fire_fault(self, point: str) -> None:
        if self._faults is not None:
            self._faults.fire(point)

    def _kill_loop(self) -> None:
        """Arm the injector's `kill` fault: once due, tear the server
        down as a CRASH — no deregistration, no draining — exactly like
        a preempted node.  The fleet announcement expires by TTL."""
        while not self._stop.is_set():
            if self._faults.kill_due():
                logger.warning("FAULT kill: crashing the generation server")
                self._crashed = True
                # Black-box dump: the ring holds the victim's last
                # dispatches/spans — the post-mortem a preempted node
                # otherwise takes to its grave.
                tracer.flight_event("kill", port=self.port)
                tracer.flight_dump(
                    "fault_kill", role="gen_server", rank=self.port
                )
                self.close()
                return
            self._stop.wait(0.05)

    # ---------------- ZMQ transport ----------------

    def _start_zmq(self, host: str, port: int) -> None:
        import zmq

        router = zmq.Context.instance().socket(zmq.ROUTER)
        # Bind the host the operator chose, VERBATIM: widening a narrow
        # bind to 0.0.0.0 would bypass the constructor's no-token gate.
        bind_host = {"localhost": "127.0.0.1"}.get(host, host)
        if ":" in bind_host:  # IPv6 literal
            router.setsockopt(zmq.IPV6, 1)
            bind_host = f"[{bind_host}]"
        if port == 0:
            port = router.bind_to_random_port(f"tcp://{bind_host}")
        else:
            router.bind(f"tcp://{bind_host}:{port}")
        self.zmq_port = port
        self.zmq_url = f"zmq://{host}:{port}"
        self._zmq_thread = threading.Thread(
            target=self._zmq_loop, args=(router,), daemon=True
        )
        self._zmq_thread.start()

    def _zmq_loop(self, router) -> None:
        """ROUTER loop: parse requests into the SAME collector queue the
        HTTP path feeds; park (identity, pending) pairs and reply as their
        done events set.  The socket is touched by this thread only; any
        number of in-flight requests per client, no thread-per-request.

        Wire format is JSON (like the HTTP path), NOT pickle: frames
        arrive from the network BEFORE authentication, and unpickling
        untrusted bytes executes code — the token must gate everything a
        payload can do."""
        jobs: List = []  # (identity, rid, _Pending)

        def reply(ident, rid, msg: Dict):
            msg["rid"] = rid
            router.send_multipart([ident, json.dumps(msg).encode()])

        def handle(ident, payload: bytes, blob: Optional[bytes] = None):
            try:
                req = json.loads(payload)
                rid = req.get("rid")
            except Exception:
                # No rid recoverable: send an uncorrelated error (clients
                # fail fast on rid-less errors rather than timing out).
                router.send_multipart(
                    [ident, json.dumps({"error": "bad request"}).encode()]
                )
                return
            try:
                if self._token and req.get("token") != self._token:
                    reply(ident, rid, {"error": "bad token"})
                    return
                cmd = req.get("cmd")
                if cmd == "health":
                    reply(ident, rid, self.health_info())
                elif cmd == "pause":
                    self.pause()
                    reply(ident, rid, {
                        "paused": True, "version": self.version,
                    })
                elif cmd == "resume":
                    self.resume()
                    reply(ident, rid, {
                        "paused": False, "version": self.version,
                    })
                elif cmd == "generate":
                    p = _Pending(
                        qid=str(req["qid"]),
                        prompt_ids=[int(t) for t in req["prompt_ids"]],
                        gconfig=GenerationHyperparameters(
                            **req.get("gconfig", {})
                        ),
                        done=threading.Event(),
                        seed=req.get("seed"),
                        t_enq=time.monotonic_ns(),
                        trace_id=(
                            str(req["trace_id"])
                            if req.get("trace_id") else None
                        ),
                    )
                    self._queue.put(p)
                    jobs.append((ident, rid, p))
                elif cmd == "episode":
                    # Episode turns block for a full decode; spawn like
                    # update_weights so the ROUTER loop stays responsive.
                    # slot_gone replies carry error_type WITHOUT "error"
                    # so the client future resolves and the caller can
                    # raise the typed SlotGoneError itself.
                    p = _Pending(
                        qid="", prompt_ids=[],
                        gconfig=GenerationHyperparameters(),
                        done=threading.Event(),
                    )

                    def _ep(p=p, req=dict(req)):
                        try:
                            p.result = self.handle_episode(req)
                        except SlotGoneError as e:
                            p.result = {
                                "error_type": "slot_gone",
                                "episode_id": e.episode_id,
                                "reason": e.reason,
                            }
                        except Exception as e:  # noqa: BLE001
                            p.error = repr(e)
                        p.done.set()

                    threading.Thread(target=_ep, daemon=True).start()
                    jobs.append((ident, rid, p))
                elif cmd == "update_weights":
                    p = _Pending(
                        qid="", prompt_ids=[],
                        gconfig=GenerationHyperparameters(),
                        done=threading.Event(),
                    )

                    def _upd(p=p, path=req.get("path")):
                        try:
                            p.result = self._handle_update({"path": path})
                        except Exception as e:  # noqa: BLE001
                            p.error = repr(e)
                        p.done.set()

                    threading.Thread(target=_upd, daemon=True).start()
                    jobs.append((ident, rid, p))
                elif cmd == "param_push":
                    # Binary fabric push (system/paramstore.py): the
                    # serialized params ride a THIRD frame, relayed
                    # verbatim — relaying + applying blocks, so spawn
                    # like update_weights to keep the ROUTER responsive.
                    p = _Pending(
                        qid="", prompt_ids=[],
                        gconfig=GenerationHyperparameters(),
                        done=threading.Event(),
                    )

                    def _pp(p=p, req=dict(req), blob=blob):
                        try:
                            p.result = self._handle_param_push(
                                req, blob if blob is not None else b""
                            )
                        except Exception as e:  # noqa: BLE001
                            p.error = repr(e)
                        p.done.set()

                    threading.Thread(target=_pp, daemon=True).start()
                    jobs.append((ident, rid, p))
                else:
                    reply(ident, rid, {"error": f"unknown cmd {cmd!r}"})
            except Exception as e:  # noqa: BLE001 — malformed fields
                # Always rid-correlated: the client must fail THIS request
                # immediately, not block until its timeout.
                reply(ident, rid, {"error": f"bad request: {e!r}"})

        while not self._stop.is_set():
            try:
                # Short poll while replies are pending keeps added reply
                # latency ~10ms; idle ticks stay cheap at 100ms.
                while router.poll(10 if jobs else 100):
                    # 2 frames = JSON request; a 3rd frame carries a
                    # binary param_push payload (relayed verbatim —
                    # never JSON, never pickled).
                    frames = router.recv_multipart()
                    handle(
                        frames[0],
                        frames[1] if len(frames) > 1 else b"",
                        frames[2] if len(frames) > 2 else None,
                    )
                still = []
                for ident, rid, p in jobs:
                    if p.done.is_set():
                        reply(
                            ident, rid,
                            {"error": p.error} if p.error else dict(p.result),
                        )
                    elif (
                        p.qid and not self._collector_thread.is_alive()
                    ):
                        reply(ident, rid, {"error": "collector thread died"})
                    else:
                        still.append((ident, rid, p))
                jobs = still
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.exception("zmq transport error")
        for ident, rid, p in jobs:
            try:
                reply(ident, rid, {"error": "server shutting down"})
            except Exception:  # noqa: BLE001
                pass
        router.close(linger=200)

    # ---------------- fleet membership ----------------

    def announce(
        self,
        experiment: str,
        trial: str,
        server_id: Optional[str] = None,
        ttl: float = 10.0,
    ) -> str:
        """Join the elastic fleet: register this server's URL under the
        `names.gen_servers` subtree with a keepalive TTL, and start a
        heartbeat thread touching the key at ttl/3.  A server that stops
        beating (crash, preemption) expires out of the listing and the
        rollout controller drains it; a graceful close() deregisters
        immediately.  Returns the server id (default: port-stable
        `s<port>`, so a restart on the same port resumes the same fleet
        identity)."""
        from areal_tpu.base import name_resolve, names

        sid = server_id or f"s{self.port}"
        key = names.gen_server(experiment, trial, sid)
        name_resolve.add(
            key,
            self.zmq_url or self.url,
            keepalive_ttl=ttl,
            replace=True,
            delete_on_exit=True,
        )
        self._announce_key = key
        beat_s = max(ttl / 3.0, 0.05)

        def beat():
            repo = name_resolve.default()
            while not self._stop.wait(beat_s):
                try:
                    repo.touch(key)
                except Exception:  # noqa: BLE001 — key deleted: stop beating
                    return

        self._announce_thread = threading.Thread(target=beat, daemon=True)
        self._announce_thread.start()
        logger.info(f"announced fleet member {sid} (ttl {ttl}s)")
        return sid

    # ---------------- pause / resume / in-memory weight sync ----------------

    def health_info(self) -> Dict:
        """Liveness + the load signals a rollout controller balances on.

        Snapshot discipline (a mid-admission poll must not report torn
        state): (version, paused) are read together under _health_lock —
        the same lock every weight swap bumps them under; the engine's
        (live_slots, kv_utilization) pair comes from its atomically
        replaced `load_state` tuple, so the two can never be from
        different chunk boundaries; queue depth is one qsize() call.
        The same snapshot feeds the /metrics gauges, so /health and the
        metrics plane agree."""
        self._fire_fault("health")
        eng = self.engine
        with self._health_lock:
            version = self.version
            paused = self._pause_evt.is_set()
        load = getattr(eng, "load_state", None)
        if load is not None:
            live, kvu = load
        else:
            live = getattr(eng, "live_slots", 0)
            kvu = getattr(eng, "kv_utilization", 0.0)
        qd = self._queue.qsize()
        _M_QUEUE_DEPTH.set(qd)
        _M_WEIGHT_VERSION.set(version)
        return {
            "status": "ok",
            "version": version,
            "queue_depth": qd,
            "live_slots": int(live),
            "kv_utilization": float(kvu),
            "capacity": int(getattr(eng, "max_decode_batch", 0) or 0),
            "paused": paused,
        }

    def pause(self) -> None:
        """Stop decoding at the next chunk boundary: the in-flight
        generate call parks (releasing the engine lock) and new batches
        wait until resume().  Engines without interrupt support simply
        drain their current call first."""
        with self._health_lock:
            self._pause_evt.set()
        _M_PAUSED.set(1)
        if hasattr(self.engine, "interrupt"):
            self.engine.interrupt()

    def resume(self) -> None:
        with self._health_lock:
            self._pause_evt.clear()
        _M_PAUSED.set(0)
        if hasattr(self.engine, "clear_interrupt"):
            self.engine.clear_interrupt()
        with self._resume_cond:
            self._resume_cond.notify_all()

    def update_weights_inmem(self, params, checksum=None, version=None) -> int:
        """Interruptible in-memory weight push (async RL): pause at a
        chunk boundary, hot-swap the given params pytree directly into
        the engine (no disk checkpoint), bump the version, resume —
        interrupted requests continue on their existing KV pages, so the
        push costs one chunk of replay instead of a full drain.
        Reachable from the Python API and, since the parameter fabric
        (system/paramstore.py), from the binary ``param_push`` wire on
        both transports via :meth:`_handle_param_push`.

        `version` (fabric pushes) sets the ABSOLUTE serving version so
        the fleet tracks the store's version time; a push at or behind
        the current version is an idempotent no-op (a repair and a relay
        racing on one server must not double-apply).  Without it the
        version bumps by one (Python-API pushes).

        `checksum` (from ``integrity.params_checksum`` at the pusher) is
        verified BEFORE the swap; a mismatch raises
        :class:`~areal_tpu.base.integrity.WeightChecksumError`, bumps
        ``areal_gen_weight_push_rejected_total``, and leaves the server
        decoding on its previous healthy weights — the pusher retries.
        A server therefore NEVER serves a torn version: the swap is
        atomic under the engine lock and only checksummed payloads reach
        it.  The ``corrupt_push@point=weight_push`` chaos kind corrupts
        the incoming payload here, modeling in-flight corruption against
        the real verification path."""
        if version is not None:
            with self._health_lock:
                if int(version) <= self.version:
                    return self.version
        if (
            self._faults is not None
            and self._faults.poison("weight_push") == "corrupt_push"
        ):
            params = integrity.corrupt_params(params)
        with self._update_mutex:
            if checksum is not None:
                try:
                    integrity.verify_checksum(params, checksum)
                except integrity.WeightChecksumError:
                    # A corrupted push is a fault instant: dump the ring
                    # so the post-mortem shows what this server was doing
                    # when the bad payload arrived.
                    tracer.flight_event(
                        "push_rejected", port=self.port,
                        version=self.version,
                    )
                    tracer.flight_dump(
                        "push_rejected", role="gen_server", rank=self.port
                    )
                    raise
            self.pause()
            try:
                with self._engine_lock:
                    with self._health_lock:
                        if (
                            version is not None
                            and int(version) <= self.version
                        ):
                            # Raced with another push of the same (or a
                            # newer) version while waiting on the mutex.
                            return self.version
                    self.engine.set_params(params)
                    with self._health_lock:
                        if version is None:
                            self.version += 1
                        else:
                            self.version = int(version)
                        v = self.version
                    self.inmem_updates += 1
                    _M_WEIGHT_VERSION.set(v)
                    _M_WEIGHT_UPDATES.inc()
            finally:
                self.resume()
        logger.info(f"weights updated in memory -> version {v}")
        return v

    def _handle_param_push(self, req: Dict, payload: bytes) -> Dict:
        """One hop of a fabric broadcast (system/paramstore.py): relay
        the raw payload to this node's subtree children FIRST (the
        fan-out must keep moving even when the local apply is slow),
        then deserialize against the engine's own treedef and apply via
        the interruptible checksummed :meth:`update_weights_inmem`.

        The ack aggregates per-sid outcomes for the whole subtree:
        ``applied`` (sids now serving the pushed version) and ``failed``
        (orphaned sids + why).  A local reject/failure never fails the
        ack — degradation is PER-SUBTREE and the pusher counts orphans.
        """
        # Chaos: a point-scoped kill here models a relay preempted
        # mid-broadcast — crash semantics (no deregistration), black-box
        # flight dump, subtree orphaned.
        if self._faults is not None and self._faults.kill_point(
            "param_push"
        ):
            logger.warning("FAULT kill: crashing relay mid-broadcast")
            self._crashed = True
            tracer.flight_event("kill", port=self.port)
            tracer.flight_dump(
                "fault_kill", role="gen_server", rank=self.port
            )
            self.close()
            raise RuntimeError("server killed at param_push")
        self._fire_fault("param_push")
        from areal_tpu.system import paramstore

        version = int(req["version"])
        manifest = req["manifest"]
        checksum = (
            np.asarray(req["checksum"], np.float64)
            if req.get("checksum") is not None else None
        )
        node = req.get("subtree") or {}
        sid = str(node.get("sid") or f"s{self.port}")
        applied, failed = paramstore.relay_subtrees(
            node.get("children") or [],
            {
                "cmd": "param_push",
                "version": version,
                "manifest": manifest,
                "checksum": req.get("checksum"),
            },
            payload,
            token=self._token,
            timeout_s=float(req.get("timeout_s", 120.0)),
        )
        try:
            like = getattr(self.engine, "params", None)
            if like is None:
                raise RuntimeError(
                    "engine exposes no params pytree to deserialize "
                    "against"
                )
            params = paramstore.deserialize_params(like, manifest, payload)
            self.update_weights_inmem(
                params, checksum=checksum, version=version
            )
            applied.insert(0, sid)
        except Exception as e:  # noqa: BLE001 — per-subtree degradation
            logger.warning(f"local param_push apply failed: {e!r}")
            failed.append({"sid": sid, "error": repr(e)})
        return {
            "version": self.version,
            "applied": applied,
            "failed": failed,
        }

    def _await_resume(self) -> None:
        """Block a parked _run_subgroup until resume() (engine lock NOT
        held by the caller — the weight swap needs it)."""
        while self._pause_evt.is_set():
            if self._stop.is_set():
                raise RuntimeError("generation server shutting down")
            with self._resume_cond:
                self._resume_cond.wait(timeout=0.2)

    # ---------------- request handling ----------------

    def _handle_generate(self, req: Dict) -> Dict:
        # Chaos: may sleep (`slow`), wedge this request thread (`hang`),
        # or raise (`error` -> HTTP 500 like any handler failure).
        self._fire_fault("generate")
        g = GenerationHyperparameters(
            n=int(req.get("n", 1)),
            max_new_tokens=int(req.get("max_new_tokens", 256)),
            min_new_tokens=int(req.get("min_new_tokens", 0)),
            greedy=bool(req.get("greedy", False)),
            top_p=float(req.get("top_p", 1.0)),
            top_k=int(req.get("top_k", 0)),
            temperature=float(req.get("temperature", 1.0)),
            spec_decode_k=int(req.get("spec_decode_k", 0)),
            spec_ngram=int(req.get("spec_ngram", 3)),
            stop=req.get("stop") or (),
        )
        p = _Pending(
            qid=str(req["qid"]),
            prompt_ids=[int(t) for t in req["prompt_ids"]],
            gconfig=g,
            done=threading.Event(),
            seed=(int(req["seed"]) if req.get("seed") is not None else None),
            t_enq=time.monotonic_ns(),
            trace_id=(str(req["trace_id"]) if req.get("trace_id") else None),
        )
        self._queue.put(p)
        while not p.done.wait(timeout=1.0):
            if self._stop.is_set():
                raise RuntimeError("generation server shutting down")
            if not self._collector_thread.is_alive():
                # Never leave a client blocked on a dead collector.
                raise RuntimeError("generation collector thread died")
        if p.error:
            raise RuntimeError(p.error)
        return p.result

    def handle_episode(self, req: Dict) -> Dict:
        """Agent-serving episode ops (start/extend/release) — one turn per
        request, pinned to the engine slot holding the episode's KV pages.

        Runs on the calling transport thread, NOT through the collector:
        an episode op needs ITS slot, so batching it with strangers buys
        nothing, and the engine lock already serializes it against
        batched generates and weight swaps.  A mid-turn weight push parks
        the turn at a chunk boundary; the park loop below releases the
        engine for the swap and resumes the SAME turn on its pages.  An
        op against a reclaimed slot raises the typed
        :class:`SlotGoneError` (HTTP 409 / ZMQ ``error_type`` payload)
        and bumps ``areal_gen_episode_slot_lost_total`` — the controller
        re-admits the full conversation via the prefix cache."""
        self._fire_fault("episode")
        eng = self.engine
        if not hasattr(eng, "episode_start"):
            raise RuntimeError(
                "engine has no episode support (agent episodes run on "
                "GeneratorEngine's serving plane)"
            )
        op = str(req.get("op", ""))
        ep_id = str(req.get("episode_id", ""))
        if not ep_id:
            raise ValueError("episode op needs a non-empty episode_id")
        # Lineage: the start op carries the trace_id (header/frame); later
        # turns on this episode inherit it from the per-episode store.
        trace_id = str(req["trace_id"]) if req.get("trace_id") else None
        if op == "start" and trace_id:
            self._episode_traces[ep_id] = trace_id
        elif trace_id is None:
            trace_id = self._episode_traces.get(ep_id)
        if op == "release":
            self._episode_traces.pop(ep_id, None)
            with self._engine_lock:
                released = bool(eng.episode_release(ep_id))
            if trace_id:
                tracer.lineage(
                    "turn", trace_id, episode_id=ep_id, op="release"
                )
            return {
                "episode_id": ep_id,
                "released": released,
                "version": self.version,
            }
        if op == "start":
            g = GenerationHyperparameters(**req.get("gconfig", {}))
            prompt_ids = [int(t) for t in req.get("prompt_ids", [])]
            budget = int(req.get("token_budget", 0))
            seed = int(req.get("seed", 0))

            def first():
                return eng.episode_start(
                    ep_id, prompt_ids, g, token_budget=budget, seed=seed
                )
        elif op == "extend":
            obs = [int(t) for t in req.get("obs_ids", [])]

            def first():
                return eng.episode_extend(ep_id, obs)
        else:
            raise ValueError(f"unknown episode op {op!r}")
        try:
            if self._pause_evt.is_set():
                self._await_resume()
            self._engine_lock.acquire()
            locked = True
            try:
                version_start = self.version
                out = first()
                while out is None:
                    # Parked by pause(): free the engine for the weight
                    # swap, then resume THIS turn on its existing pages.
                    self._engine_lock.release()
                    locked = False
                    self._await_resume()
                    self._engine_lock.acquire()
                    locked = True
                    out = eng.episode_resume(ep_id)
                version = self.version
            finally:
                if locked:
                    self._engine_lock.release()
        except SlotGoneError:
            _M_EPISODE_SLOT_LOST.inc()
            self._episode_traces.pop(ep_id, None)
            raise
        out = dict(out)
        out["version"] = version
        out["version_start"] = version_start
        if trace_id:
            tracer.lineage(
                "turn",
                trace_id,
                episode_id=ep_id,
                op=op,
                stop_reason=str(out.get("stop_reason", "")),
                version=version,
            )
        return out

    def _handle_update(self, req: Dict) -> Dict:
        from areal_tpu.models.hf import registry as hf

        path = os.path.realpath(str(req["path"]))
        if self.ckpt_root and not path.startswith(
            os.path.realpath(self.ckpt_root) + os.sep
        ):
            raise ValueError(
                f"update path {path!r} outside checkpoint root "
                f"{self.ckpt_root!r}"
            )
        # Load the RESOLVED path: loading the raw one would let a symlink
        # swapped after the check escape the root.
        _, params = hf.load_hf_checkpoint(path)
        with self._engine_lock:
            self.engine.set_params(params)
            with self._health_lock:
                self.version += 1
            _M_WEIGHT_VERSION.set(self.version)
            _M_WEIGHT_UPDATES.inc()
        logger.info(
            f"weights updated from {req['path']} -> version {self.version}"
        )
        return {"version": self.version}

    # ---------------- batching collector ----------------

    def _collect_loop(self):
        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            batch = [first]
            # The loop body must never kill the collector thread: every
            # /generate blocks on p.done, so an uncaught error here would
            # hang all future clients.  _run_group guards per-group errors;
            # this guards the batching glue and fails the batch loudly.
            try:
                # Linger briefly so concurrent clients land in one call.
                # Blocking sleep is correct here: _collect_loop runs on
                # the dedicated batcher THREAD, never on an event loop
                # (rule async-blocking only fires inside coroutines).
                time.sleep(self.max_wait_ms / 1000.0)
                while len(batch) < self.max_batch:
                    try:
                        batch.append(self._queue.get_nowait())
                    except queue.Empty:
                        break
                # Sampled gauge: how deep the request queue sits when a
                # batch is picked — the server-side pressure signal.
                tracer.counter(
                    "gen_queue",
                    depth=self._queue.qsize(),
                    batch=len(batch),
                )
                _M_QUEUE_DEPTH.set(self._queue.qsize())
                _M_BATCHES.inc()
                by_g: Dict[Any, List[_Pending]] = {}
                for p in batch:
                    by_g.setdefault(_gkey(p), []).append(p)
                for group in by_g.values():
                    self._run_group(group)
                tracer.flush()
            except Exception as e:  # noqa: BLE001
                logger.exception("collector batching error")
                for p in batch:
                    if not p.done.is_set():
                        p.error = f"collector error: {e!r}"
                        p.done.set()
        # Shutdown: fail anything still queued so no client hangs.
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            p.error = "generation server shutting down"
            p.done.set()

    def _run_group(self, group: List[_Pending]):
        """Split the batched group against the engine's KV page budget,
        then run each sub-group as one generate call.  A paged engine
        with a bounded pool (kv_pool_pages set) exposes the budget in
        tokens; admitting a group whose worst-case footprint exceeds it
        would either exhaust the pool mid-flight or serialize behind
        the allocator — splitting up front keeps every call feasible.
        Footprints are CoW-aware when the engine exposes
        `group_footprint_tokens` (the serving plane's prompt-page
        sharing makes a group of n responses cost prompt + n*(tail+new),
        not n*(prompt+new) — without this the splitter would shard
        groups the pool can in fact hold whole).  A request whose
        worst-case footprint exceeds the budget EVEN ALONE — singletons
        included, which previously bypassed the check entirely — fails
        up front with the capacity error instead of burning a generate
        call destined to exhaust the pool mid-flight."""
        budget = getattr(self.engine, "page_budget_tokens", None)
        if budget is None:
            return self._run_subgroup(group)
        foot = getattr(self.engine, "group_footprint_tokens", None)

        def need_of(p: _Pending) -> int:
            g = p.gconfig
            if foot is not None:
                return foot(len(p.prompt_ids), g.max_new_tokens, g.n)
            return g.n * (len(p.prompt_ids) + g.max_new_tokens)

        sub: List[_Pending] = []
        used = 0
        for p in group:
            need = need_of(p)
            if need > budget:
                self._fail_request(
                    p,
                    f"request footprint {need} tokens (n={p.gconfig.n}, "
                    f"prompt {len(p.prompt_ids)} + max_new "
                    f"{p.gconfig.max_new_tokens}) exceeds the KV page "
                    f"budget of {budget} tokens; raise kv_pool_pages or "
                    f"shrink the request",
                )
                continue
            if sub and used + need > budget:
                self._run_subgroup(sub)
                sub, used = [], 0
            sub.append(p)
            used += need
        if sub:
            self._run_subgroup(sub)

    def _fail_request(self, p: _Pending, msg: str) -> None:
        logger.error(f"rejecting {p.qid}: {msg}")
        p.error = msg
        _M_REQUESTS.labels("rejected").inc()
        if p.t_enq is not None:
            _M_REQUEST_SECONDS.observe(
                (time.monotonic_ns() - p.t_enq) / 1e9
            )
        if p.t_enq is not None:
            args = dict(
                qid=p.qid,
                n=p.gconfig.n,
                prompt_len=len(p.prompt_ids),
                error=True,
            )
            if p.trace_id:
                args["trace_id"] = p.trace_id
            tracer.complete(
                f"request:{p.qid}", start_ns=p.t_enq, **args
            )
        if p.trace_id:
            tracer.lineage("failed", p.trace_id, qid=p.qid, error=msg)
        p.done.set()

    def _run_subgroup(self, group: List[_Pending]):
        try:
            # Park BEFORE dispatch while paused.  The inflight path parks
            # itself at the next chunk boundary, but the static
            # (short-decode) path is one uninterruptible program — without
            # this gate a request arriving mid-pause would race the weight
            # swap for the engine lock instead of waiting for resume().
            if self._pause_evt.is_set():
                self._await_resume()
            g = group[0].gconfig
            # Internal ids are positional: client qids may collide across
            # concurrent trainers sharing this server.
            uids = [f"u{i}" for i in range(len(group))]
            sample = SequenceSample(
                keys={"packed_prompts"},
                ids=uids,
                seqlens={
                    "packed_prompts": [[len(p.prompt_ids)] for p in group]
                },
                data={
                    "packed_prompts": np.concatenate(
                        [np.asarray(p.prompt_ids, np.int32) for p in group]
                    )
                },
            )
            self._seed += 1
            seed = group[0].seed if group[0].seed is not None else self._seed
            # Uncategorized on purpose: the engine's own compute spans
            # attribute the time; this shows engine-lock wait + call shape.
            with tracer.span("gen_batch", n_reqs=len(group)):
                self._engine_lock.acquire()
                locked = True
                try:
                    version_start = self.version
                    for p in group:
                        if p.trace_id:
                            tracer.lineage(
                                "first_token", p.trace_id, qid=p.qid
                            )
                    out = self.engine.generate(
                        sample, MicroBatchSpec(), g, seed=seed
                    )
                    while out is None:
                        # Parked by pause(): free the engine for the
                        # weight swap, wait for resume(), continue the
                        # interrupted decode on its existing KV pages.
                        self._engine_lock.release()
                        locked = False
                        self._await_resume()
                        self._engine_lock.acquire()
                        locked = True
                        out = self.engine.resume_generate()
                    version = self.version
                finally:
                    if locked:
                        self._engine_lock.release()
            per_id = {s.ids[0]: s for s in out.unpack()}
            for uid, p in zip(uids, group):
                p.result = _extract_output(
                    per_id[uid], len(p.prompt_ids), g.n, version,
                    version_start,
                )
        except Exception as e:  # noqa: BLE001 — fail the whole group
            logger.error(f"generation batch failed: {e!r}")
            for p in group:
                p.error = repr(e)
        finally:
            for p in group:
                _M_REQUESTS.labels(
                    "error" if p.error else "ok"
                ).inc()
                if p.t_enq is not None:
                    _M_REQUEST_SECONDS.observe(
                        (time.monotonic_ns() - p.t_enq) / 1e9
                    )
                    args = dict(
                        qid=p.qid,
                        n=p.gconfig.n,
                        prompt_len=len(p.prompt_ids),
                        error=bool(p.error),
                    )
                    if p.trace_id:
                        args["trace_id"] = p.trace_id
                    tracer.complete(
                        f"request:{p.qid}", start_ns=p.t_enq, **args
                    )
                if p.trace_id:
                    tracer.lineage(
                        "generated",
                        p.trace_id,
                        qid=p.qid,
                        error=bool(p.error),
                    )
                p.done.set()

    def close(self):
        self._stop.set()
        if self._faults is not None:
            # Unblock blocked `hang` request threads so they fail fast.
            self._faults.release()
        if self._announce_key and not self._crashed:
            # Graceful leave: deregister now so the controller drains us
            # within one refresh.  A crash skips this — the announcement
            # expires by TTL, exactly like a preempted node.
            from areal_tpu.base import name_resolve

            try:
                name_resolve.delete(self._announce_key)
            except Exception:  # noqa: BLE001 — already expired/deleted
                pass
            self._announce_key = None
        self._http.shutdown()
        self._http.server_close()
        tracer.flush()


def _extract_output(
    s: SequenceSample, prompt_len: int, n: int, version: int,
    version_start: Optional[int] = None,
) -> Dict[str, Any]:
    """Slice one request's SequenceSample (GeneratorEngine._assemble
    layout) back into API JSON: per-response generated ids + logprobs."""
    toks = np.asarray(s.data["packed_input_ids"])
    lps = np.asarray(s.data["packed_logprobs"])
    noe = np.asarray(s.data["seq_no_eos_mask"])
    lens = s.seqlens["packed_input_ids"][0]
    out_ids, out_lps = [], []
    t_off = lp_off = 0
    for r in range(n):
        full_len = int(lens[r])
        row = toks[t_off : t_off + full_len]
        row_lp = lps[lp_off : lp_off + full_len - 1]
        out_ids.append([int(x) for x in row[prompt_len:]])
        out_lps.append(
            [float(x) for x in row_lp[prompt_len - 1 : full_len - 1]]
        )
        t_off += full_len
        lp_off += full_len - 1
    return {
        "output_ids": out_ids,
        "output_logprobs": out_lps,
        "no_eos": [bool(x) for x in noe[:n]],
        "version": version,
        # Head version: the weights sampling STARTED under — what
        # bounded-staleness admission keys on (an interrupted request
        # finishes under a newer version than it started).
        "version_start": version if version_start is None else version_start,
    }


class ZMQGenClient(BoundedAgenerateMixin):
    """High-throughput client for a GenerationServer's ZMQ transport.

    One DEALER connection pipelines any number of in-flight requests
    (correlated by client-assigned rids) — no per-request thread or TCP
    connection, unlike the HTTP path's urllib fan-out.  Same surface as
    LLMAPIClient where RemoteGeneratorEngine needs it."""

    def __init__(
        self,
        url: str,
        timeout_s: float = 7200.0,
        token: str = "",
        max_inflight: int = 64,
    ):
        assert url.startswith("zmq://"), url
        self.url = url
        self.timeout_s = timeout_s
        self.token = token or os.environ.get("AREAL_GEN_TOKEN", "")
        self.max_inflight = max_inflight
        # ZMQ sockets are not thread-safe, so ONE IO thread owns the
        # DEALER; callers enqueue frames and wait on per-rid futures.  A
        # simple send+recv-under-lock design would serialize CONCURRENT
        # callers (each holding the lock for a full generation round
        # trip) — with futures, any number of threads/tasks pipeline
        # their requests over the one connection.
        import concurrent.futures as _cf

        # Each entry is a frame LIST: [json] for ordinary requests,
        # [json, payload] for binary param pushes.
        self._send_q: "queue.Queue[List[bytes]]" = queue.Queue()
        self._pending: Dict[int, _cf.Future] = {}
        self._plock = threading.Lock()
        self._rid = 0
        self._stop_evt = threading.Event()
        self._ready = threading.Event()
        self._io = threading.Thread(
            target=self._io_loop,
            args=("tcp://" + url[len("zmq://"):],),
            daemon=True,
        )
        self._io.start()

    def _fail_all(self, err: str) -> None:
        with self._plock:
            failed = list(self._pending.values())
            self._pending.clear()
        for f in failed:
            if not f.done():
                f.set_exception(RuntimeError(err))

    def _io_loop(self, addr: str) -> None:
        import collections

        import zmq

        sock = zmq.Context.instance().socket(zmq.DEALER)
        sock.connect(addr)
        self._ready.set()
        outbox: "collections.deque[List[bytes]]" = collections.deque()

        def fail_all(err: str) -> None:
            # Also purge queued frames: their futures are failed, so
            # sending them later would make the server burn minutes of
            # generation nobody will consume.
            self._fail_all(err)
            outbox.clear()
            try:
                while True:
                    self._send_q.get_nowait()
            except queue.Empty:
                pass

        while not self._stop_evt.is_set():
            # The loop must SURVIVE (a dead IO thread strands every
            # pending and future request until its full timeout) and must
            # never block uninterruptibly (a dead server + full SNDHWM
            # would wedge a blocking send forever, making close() a no-op).
            try:
                try:
                    while True:
                        outbox.append(self._send_q.get_nowait())
                except queue.Empty:
                    pass
                while outbox:
                    try:
                        sock.send_multipart(outbox[0], zmq.NOBLOCK)
                        outbox.popleft()
                    except zmq.Again:
                        break  # HWM full: retry next tick, stay stoppable
                if not sock.poll(10):
                    continue
                try:
                    msg = json.loads(sock.recv())
                except (ValueError, UnicodeDecodeError):
                    # One garbled frame cannot be correlated: fail all
                    # outstanding (never silently kill the thread).
                    fail_all("generation server sent a garbled frame")
                    continue
                rid = msg.pop("rid", None)
                if rid is None:
                    fail_all(
                        f"generation server error: {msg.get('error')}"
                    )
                    continue
                with self._plock:
                    f = self._pending.pop(rid, None)
                if f is not None and not f.done():
                    if "error" in msg:
                        f.set_exception(RuntimeError(
                            f"generation server error: {msg['error']}"
                        ))
                    else:
                        f.set_result(msg)
            except zmq.ContextTerminated:
                # Process/context teardown: nothing left to serve.
                fail_all("generation client context terminated")
                return
            except Exception as e:  # noqa: BLE001 — zmq/system errors
                logger.exception("gen client io error")
                fail_all(f"generation client io error: {e!r}")
                # Persistent socket errors must not become a hot loop.
                # Thread context: this IO loop owns its own daemon thread
                # (no event loop to stall), so a blocking backoff is fine.
                time.sleep(0.05)
        # Clean stop must not strand blocked callers until their timeout.
        fail_all("generation client closed")
        sock.close(linger=200)

    def close(self) -> None:
        self._stop_evt.set()

    def _call_many(
        self, reqs: List[Dict], extras: Optional[List[Optional[bytes]]] = None
    ) -> List[Dict]:
        import concurrent.futures as _cf

        # Fail fast instead of enqueueing onto a dead IO loop: a call made
        # after close(), or before the IO thread ever connected, would
        # otherwise park frames in the send queue and block the caller for
        # the full timeout_s (default hours).
        if self._stop_evt.is_set():
            raise RuntimeError(
                f"generation client for {self.url} is closed"
            )
        if not self._ready.wait(30):
            raise TimeoutError(
                f"generation server {self.url}: IO thread not connected "
                "after 30s"
            )
        futs = []
        with self._plock:
            for i, req in enumerate(reqs):
                self._rid += 1
                rid = self._rid
                f: _cf.Future = _cf.Future()
                self._pending[rid] = f
                futs.append((rid, f))
                frames = [
                    json.dumps(
                        dict(req, rid=rid, token=self.token)
                    ).encode()
                ]
                if extras is not None and extras[i] is not None:
                    frames.append(extras[i])
                self._send_q.put(frames)
        deadline = time.monotonic() + self.timeout_s
        out = []
        try:
            for rid, f in futs:
                left = max(deadline - time.monotonic(), 0.001)
                try:
                    out.append(f.result(timeout=left))
                except _cf.TimeoutError:
                    raise TimeoutError(
                        f"generation server {self.url}: no reply for "
                        f"request {rid} within {self.timeout_s}s"
                    ) from None
        finally:
            with self._plock:
                for rid, f in futs:
                    self._pending.pop(rid, None)
        return out

    def health(self) -> Dict:
        return self._call_many([{"cmd": "health"}])[0]

    def generate_batch(
        self, inps: List[APIGenerateInput], max_concurrency: int = 0
    ) -> List[APIGenerateOutput]:
        reqs = [
            {
                "cmd": "generate",
                "qid": inp.qid,
                "prompt_ids": list(map(int, inp.prompt_ids)),
                "gconfig": dataclasses.asdict(inp.gconfig),
                "seed": inp.seed,
                "trace_id": inp.trace_id,
            }
            for inp in inps
        ]
        outs = self._call_many(reqs)
        return [
            APIGenerateOutput(
                qid=inp.qid,
                prompt_ids=list(inp.prompt_ids),
                output_ids=out["output_ids"],
                output_logprobs=out["output_logprobs"],
                no_eos=out["no_eos"],
                version=int(out.get("version", 0)),
                version_start=int(
                    out.get("version_start", out.get("version", 0))
                ),
            )
            for inp, out in zip(inps, outs)
        ]

    def generate(self, inp: APIGenerateInput) -> APIGenerateOutput:
        return self.generate_batch([inp])[0]

    def update_weights_from_disk(self, path: str) -> int:
        out = self._call_many([{"cmd": "update_weights", "path": path}])[0]
        return int(out["version"])

    def push_weights(self, meta: Dict, payload: bytes) -> Dict:
        """Binary fabric push (system/paramstore.py): the meta rides the
        JSON frame, the serialized params ride a second raw frame —
        relayed verbatim hop to hop, never re-encoded."""
        return self._call_many(
            [dict(meta, cmd="param_push")], extras=[payload]
        )[0]

    def pause(self) -> Dict:
        return self._call_many([{"cmd": "pause"}])[0]

    def resume(self) -> Dict:
        return self._call_many([{"cmd": "resume"}])[0]

    # ---- agent-serving episodes (same surface as LLMAPIClient) ----

    def _episode_call(self, req: Dict) -> Dict:
        out = self._call_many([dict(req, cmd="episode")])[0]
        if out.get("error_type") == "slot_gone":
            raise SlotGoneError(
                str(out.get("episode_id", "")),
                str(out.get("reason", "unknown")),
            )
        return out

    def episode_start(
        self,
        episode_id: str,
        prompt_ids,
        gconfig: GenerationHyperparameters,
        token_budget: int = 0,
        seed: int = 0,
        trace_id: Optional[str] = None,
    ) -> Dict:
        return self._episode_call(
            {
                "op": "start",
                "episode_id": episode_id,
                "prompt_ids": list(map(int, prompt_ids)),
                "gconfig": dataclasses.asdict(gconfig),
                "token_budget": int(token_budget),
                "seed": int(seed),
                "trace_id": trace_id,
            }
        )

    def episode_extend(self, episode_id: str, obs_ids) -> Dict:
        return self._episode_call(
            {
                "op": "extend",
                "episode_id": episode_id,
                "obs_ids": list(map(int, obs_ids)),
            }
        )

    def episode_release(self, episode_id: str) -> Dict:
        return self._episode_call(
            {"op": "release", "episode_id": episode_id}
        )


def make_gen_client(url: str, **kw):
    """zmq:// URLs take the pipelined ZMQ transport; everything else HTTP."""
    if url.startswith("zmq://"):
        return ZMQGenClient(url, **kw)
    return LLMAPIClient(url, **kw)


class RemoteGeneratorEngine(Engine):
    """Generation engine backed by a remote GenerationServer (backend
    "remote_generator") — the decoupled allocation: this worker holds NO
    generation weights; `set_params` ships a checkpoint to the server
    (reference: sglang backend + disk-based weight refresh,
    model_worker.py:1040-1067)."""

    def __init__(
        self,
        cfg,
        url,  # str | List[str] — one client per serving rank
        model_type: str = "qwen2",
        sync_dir: Optional[str] = None,
        # Interruptible weight sync (async RL): pause the servers at a
        # chunk boundary around the push, so a sync costs one chunk of
        # decode latency instead of a full drain of in-flight requests.
        inmem_sync: bool = False,
        # "fabric" routes set_params through the versioned parameter
        # store + broadcast tree (system/paramstore.py): serialize once,
        # relay server-to-server, O(log N) push wall-time, no disk
        # checkpoint.  "disk" keeps the reference's save+POST loop.
        push_mode: str = "disk",
        push_fanout: int = 2,
    ):
        self.cfg = cfg
        self.inmem_sync = inmem_sync
        if push_mode not in ("disk", "fabric"):
            raise ValueError(f"unknown push_mode {push_mode!r}")
        self.push_mode = push_mode
        self.push_fanout = int(push_fanout)
        self._fabric = None  # built lazily on the first fabric push
        # Multiple URLs = the reference's one-server-per-DP-rank shape
        # (sglang.py:161-226): prompts round-robin across servers, weight
        # updates broadcast to all.
        urls = [url] if isinstance(url, str) else list(url)
        if not urls:
            raise ValueError("remote generator needs at least one URL")
        self.clients = [make_gen_client(u) for u in urls]
        self.model_type = model_type
        # Unique per engine instance: two trials on one host must never
        # interleave checkpoint shards in a shared dir.
        self.sync_dir = sync_dir or tempfile.mkdtemp(
            prefix="areal_tpu_gen_sync_"
        )

    def train_batch(self, *a, **k):
        raise NotImplementedError("RemoteGeneratorEngine is generation-only")

    def forward(self, *a, **k):
        raise NotImplementedError("RemoteGeneratorEngine is generation-only")

    def generate(
        self,
        sample: SequenceSample,
        mb_spec: MicroBatchSpec,
        gconfig: GenerationHyperparameters,
        prompt_key: str = "packed_prompts",
        seed: int = 0,
    ) -> SequenceSample:
        from areal_tpu.engines.generator import assemble_rollout

        prompts = np.asarray(sample.data[prompt_key])
        bounds = sample.cu_seqlens(prompt_key)
        inps = [
            APIGenerateInput(
                qid=sample.ids[i],
                prompt_ids=[int(t) for t in prompts[bounds[i]:bounds[i + 1]]],
                gconfig=gconfig,
                seed=seed,
            )
            for i in range(sample.bs)
        ]
        # Round-robin across serving ranks; each client's batch still
        # co-batches server-side.
        from concurrent.futures import ThreadPoolExecutor

        outs: Dict[str, APIGenerateOutput] = {}
        shards = [
            inps[k :: len(self.clients)] for k in range(len(self.clients))
        ]
        with ThreadPoolExecutor(len(self.clients)) as pool:
            for batch in pool.map(
                lambda cs: cs[0].generate_batch(cs[1]),
                zip(self.clients, shards),
            ):
                for o in batch:
                    outs[o.qid] = o

        def fetch(i, r):
            o = outs[sample.ids[i]]
            return o.output_ids[r], o.output_logprobs[r], o.no_eos[r]

        return assemble_rollout(sample, prompt_key, gconfig.n, fetch)

    def get_params(self):
        raise NotImplementedError(
            "remote generator weights live on the server"
        )

    def set_params(self, params) -> None:
        """Ship new weights to every serving rank.  Fabric mode
        (push_mode="fabric"): publish once into the versioned store and
        broadcast-tree push over the binary wire — no disk checkpoint,
        O(log N) wall-time.  Disk mode: persist -> POST /update_weights
        (the reference's path)."""
        if self.push_mode == "fabric":
            self._fabric_push(params)
            return
        from areal_tpu.models.hf import registry as hf

        os.makedirs(self.sync_dir, exist_ok=True)
        hf.save_hf_checkpoint(
            self.sync_dir, self.cfg, params, model_type=self.model_type
        )
        # Broadcast concurrently: sync latency stays ~one checkpoint
        # load, not one per serving rank.
        from concurrent.futures import ThreadPoolExecutor

        if self.inmem_sync:
            # Interrupt in-flight decode at the next chunk boundary; the
            # parked requests resume on their existing KV pages under the
            # new weights (version_start keeps their head stamp).  Without
            # this the update waits for a full drain of the engine.
            for c in self.clients:
                c.pause()
        try:
            with ThreadPoolExecutor(len(self.clients)) as pool:
                list(pool.map(
                    lambda c: c.update_weights_from_disk(self.sync_dir),
                    self.clients,
                ))
        finally:
            if self.inmem_sync:
                for c in self.clients:
                    c.resume()

    def _fabric_push(self, params) -> None:
        """Versioned-store push: to_host + checksum + serialize ONCE,
        then fan out over the broadcast tree.  Orphaned subtrees (a
        relay died mid-push) keep serving their pinned previous version
        and catch up on the next push — a partial push degrades
        staleness, never correctness (every apply is checksummed)."""
        import jax

        from areal_tpu.system import paramstore

        if self._fabric is None:
            store = paramstore.ParamStore()
            # Membership is the engine's static client set: sid = url.
            self._fabric = paramstore.BroadcastFabric(
                store,
                discovery=lambda: {c.url: c.url for c in self.clients},
                fanout=self.push_fanout,
            )
        host = jax.tree.map(
            lambda x: np.ascontiguousarray(np.asarray(x)), params
        )
        self._fabric.store.publish(host)
        report = self._fabric.push()
        if report.orphans:
            logger.warning(
                f"fabric push v{report.version}: "
                f"{len(report.orphans)} server(s) orphaned "
                f"({[o['sid'] for o in report.orphans]}); they serve the "
                "previous version until the next push"
            )


register_backend(
    "remote_generator",
    lambda cfg, url, **kw: RemoteGeneratorEngine(cfg, url, **kw),
)


def main():
    """Standalone server: python -m areal_tpu.system.gen_server
    --path <hf_ckpt_dir> [--parallel d1] [--port 8091]"""
    import argparse

    import jax

    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models.hf import registry as hf

    p = argparse.ArgumentParser(prog="areal_tpu.system.gen_server")
    p.add_argument("--path", required=True, help="HF checkpoint dir")
    p.add_argument("--parallel", default="d1")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8091)
    p.add_argument("--eos-token-id", type=int, default=None)
    p.add_argument("--max-decode-batch", type=int, default=64)
    p.add_argument("--kv-page-size", type=int, default=128,
                   help="tokens per KV page in the serving plane's pool")
    p.add_argument("--kv-pool-pages", type=int, default=0,
                   help="fixed KV pool size in pages (0 = auto-size); "
                        "positive values bound concurrent admissions "
                        "via the page budget")
    p.add_argument("--prefill-chunk-tokens", type=int, default=8,
                   help="prompt tokens consumed per inner step inside "
                        "the serving chunk (>= 1)")
    p.add_argument("--no-kv-share-prefix", action="store_true",
                   help="disable copy-on-write prompt page sharing "
                        "(prefix cache) in the serving plane")
    p.add_argument("--serving-admit-lanes", type=int, default=0,
                   help="extra packed-stream query lanes above one-per-"
                        "slot in the ragged serving chunk (0 = auto: "
                        "4x the widest per-row q_len). More lanes admit "
                        "prompts faster per chunk at a wider compiled "
                        "stream")
    p.add_argument("--token", default="",
                   help="shared secret (or AREAL_GEN_TOKEN)")
    p.add_argument("--zmq-port", type=int, default=None,
                   help="also serve the pipelined ZMQ transport on this "
                        "port (0 = random); clients use zmq://host:port")
    p.add_argument("--experiment", default="",
                   help="announce this server's /metrics endpoint into "
                        "name_resolve under the experiment/trial metrics "
                        "subtree (see apps/metrics_report.py) AND join "
                        "the elastic fleet under names.gen_servers")
    p.add_argument("--trial", default="trial")
    p.add_argument("--keepalive-ttl", type=float, default=10.0,
                   help="fleet-membership keepalive TTL in seconds; a "
                        "server that stops heartbeating expires out of "
                        "the fleet after this long")
    args = p.parse_args()

    tracer.configure(role="gen_server", rank=args.port)
    cfg, params = hf.load_hf_checkpoint(args.path)
    pc = ParallelConfig.from_str(args.parallel)
    mesh = make_mesh(pc, jax.devices()[: pc.world_size])
    eos = args.eos_token_id
    if eos is None:
        cfg_path = os.path.join(args.path, "config.json")
        try:
            with open(cfg_path) as f:
                eos = json.load(f).get("eos_token_id")
        except (OSError, json.JSONDecodeError) as e:
            raise RuntimeError(
                f"gen_server config missing/unreadable at {cfg_path}: {e}; "
                "pass --eos-token-id explicitly or point --path at a "
                "checkpoint directory containing config.json"
            ) from e
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=eos,
        max_decode_batch=args.max_decode_batch,
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        kv_share_prefix=not args.no_kv_share_prefix,
        serving_admit_lanes=args.serving_admit_lanes,
    )
    server = GenerationServer(
        engine, host=args.host, port=args.port, token=args.token,
        zmq_port=args.zmq_port,
    )
    if args.experiment:
        # The server's own HTTP plane serves /metrics; announce its base
        # URL so the fleet poller finds this role.
        from areal_tpu.base import name_resolve, names

        name_resolve.add(
            names.metrics_endpoint(
                args.experiment, args.trial, f"gen_server/{server.port}"
            ),
            server.url, replace=True, delete_on_exit=True,
        )
        # Elastic fleet: a controller running with fleet_discovery()
        # starts dispatching here within one health-refresh interval.
        server.announce(
            args.experiment, args.trial, ttl=args.keepalive_ttl
        )
    logger.info(
        f"serving {args.path} at {server.url}"
        + (f" + {server.zmq_url}" if server.zmq_url else "")
        + "; Ctrl-C to stop"
    )
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        server.close()


if __name__ == "__main__":
    main()
