#!/usr/bin/env python
"""Asynchronous-RL smoke check: the full decoupled loop on CPU.

    python scripts/check_async.py [--prompts 24] [--versions 3]

Part 1 drives the serving plane end to end: a RolloutController pumps a
prompt stream through a live GenerationServer into a staleness-bounded
ReplayBuffer while a fake trainer consumes batches and pushes fresh
weights IN MEMORY between steps.  Verified:

  - the controller feeds the buffer across >= 3 weight versions;
  - at least one in-flight request is interrupted by a weight push and
    RESUMED on its existing KV pages (engine.resume_replays), finishing
    under a newer version than it started (version_start < version);
  - every consumed trajectory obeys the max_head_offpolicyness bound.

Part 2 runs the trainer plane: a tiny PPO trial through the master's
replay-driven pipeline with max_head_offpolicyness=1 (decoupled-PPO
stats must appear in the step stats), then the degradation check —
max_head_offpolicyness=0 must reproduce the synchronous trial's stats
and final weights bit for bit.

Part 3 (`--chaos`, also runnable standalone) is the elastic-fleet chaos
leg: THREE gen servers join via fleet discovery, one is killed
mid-decode by an injected `AREAL_FAULTS=kill@t=...` fault, and the leg
asserts ZERO lost prompts (every prompt accepted, rejected-as-stale, or
explicitly failed — and none failed), the staleness bound holding, the
dead server's circuit breaker opening then re-closing after a restart
on the same port, and at least one redispatched prompt.

Part 4 (`--overlap`) is the pipeline-overlapped PPO leg: the same tiny
PPO trial run four ways — barrier, `pipeline_overlap` with
overlap_window=1 (serial streamed semantics, traced), overlap_window=3
with 2-seq chunks (traced), and a short overlapped run for compile
accounting.  The reward interface carries a small per-call latency
(modeling a remote verifier RPC) so the pipeline has real idle to
hide.  Asserted: window=1 reproduces the barrier scheduler's stats and
final weights bit for bit; the overlapped steady-state step is faster
than the barrier's; the per-stage idle (window - busy, from the merged
trace via trace_report.pipeline_rows) shrinks; overlap_frac is zero
serial and positive overlapped; and jit trace/compile counters are
identical between the 2-step and 4-step overlapped runs (no per-step
retrace churn from streaming).  `--bench-out` additionally writes the
bench JSONL consumed by scripts/check_regression.py
(bench_overlap_cpu8_*.json).

Part 5 (`--trainer-chaos`) is the crash-safe trainer plane leg, three
sub-legs over the same deterministic 4-step tiny-PPO trial with a
recover checkpoint every step: (a) an injected `AREAL_FAULTS` hang on
the third train MFC — the master's `mfc_timeout_s` deadline declares
the worker dead, aborts the step, invokes the relauncher hook, rolls
back to the last recover checkpoint, and resumes; asserted: exactly one
recovery, the `areal_master_worker_dead_total` /
`areal_master_mfc_timeout_total` / `areal_master_recoveries_total`
counters each move by one, and the resumed run's per-step stats AND
final weights are bit-identical to a fault-free baseline.  (b) a
subprocess victim killed (`kill@point=recover_stage`, exit 42) between
staging and flipping its second recover-save — the step-1 checkpoint
must stay manifest-valid, and a faultless restart must resume from it
and finish at step 4 with no stale stage dirs.  (c) the committed
checkpoint is torn (a manifest-listed file overwritten) —
`latest_valid_checkpoint` must fall back to `.prev` and a third restart
must restore from it and exit 0.

Part 6 (`--nan-chaos`) is the numerical-integrity guard plane leg,
three proofs: (a) an injected `nan@point=train_grads` fault poisons a
train step's accumulated grads — the in-jit sentinel quarantines the
step with ZERO weight/optimizer change (bit-identical params), exactly
one batched host sync per train call, and no extra jit trace; (b) a
two-step NaN streak inside the tiny-PPO trial trips the master's
`max_consecutive_quarantines` escalation — it rolls back to the last
manifest-valid recover checkpoint and replays; asserted: exactly 2
quarantined steps, 1 quarantine rollback, and the replayed steps AND
final weights bit-identical to a fault-free baseline with flat jit
trace counters; (c) a `corrupt_push@point=weight_push` fault corrupts
an in-memory weight push in flight — the gen server's checksum rejects
it (`areal_gen_weight_push_rejected_total` moves, the serving version
stays put), the retry lands, and greedy decode is token-identical to a
control server that received the same weights cleanly.  `--bench-out`
writes the bench JSONL consumed by check_regression.py
(bench_nanchaos_cpu8_*.json).

Part 7 (`--agents`) is the agent-serving runtime leg: multi-turn
tool-use episodes on persistent KV slots.  With every even token id a
single-token stop sequence (the random model's stand-in for a tool-call
marker), three 3-turn calculator episodes run through the
EpisodeController — asserted: after turn 1
every turn prefills ONLY the tool observation (zero full-prompt
re-prefills), all turns stay on one slot, the decode program compiles
exactly once, and each assistant turn is token-identical to a
single-shot replay of its transcript prefix.  A code-RL episode runs
its tool call through the OS sandbox and is graded end-to-end by the
reward fabric's sandboxed code backend, and a mid-episode in-memory
weight push parks the slot at a chunk boundary, swaps weights, and
resumes the SAME episode to completion.

Part 8 (`--push-chaos`) is the parameter-distribution-fabric chaos leg
(system/paramstore.py): FIVE discovered gen servers receive a clean
broadcast-tree weight push (v1), then the first relay in the tree — a
node with two children — is killed mid-broadcast
(`kill@point=param_push&skip=1`) during the v2 push.  Asserted: ZERO
torn versions (every live server's params verify against the published
checksum of exactly the version it reports — laggards serve v1 = head-1,
NEVER v-2, the store retains v1 purely through the orphans' pins under
retain=1); the kill orphans exactly the victim's subtree (3 servers,
counted in `areal_param_push_orphans_total`) while the other subtree
applies v2; the victim's fault-kill flight dump exists and
`trace_report --flight` renders it; after a restart on the same port,
`BroadcastFabric.repair()` catches the laggards up to head and the next
fleet push (v3) converges all five servers with no orphans,
`areal_gen_weight_push_rejected_total` never moving.

Part 9 (`--verifier-chaos`) is the verifier-service-fleet chaos leg
(system/verifier_pool.py + data/mixture.py), three sub-legs: (a) THREE
announced verifier workers grade continuous math batches through a
VerifierPool while one worker is killed mid-grade by an injected
`AREAL_FAULTS` kill — asserted: ZERO lost grades (every batch returns a
full, correct result set), at least one batch redispatched to a
different server, the victim's circuit breaker opening, the crashed
announcement expiring by TTL, the supervisor's verifier lane REFILLING
the pool back to its minimum size (bypassing the cooldown), the
replacement re-closing the breaker via a half-open probe riding a live
grade batch, and the victim's fault-kill flight dump existing.  (b) a
mixed-task rollout smoke: a TaskMixtureStream (math 2 : code 1) feeds
the RolloutController, graded asynchronously through a 2-worker pool by
the RewardFabric (sandboxed code items included) — asserted: namespaced
collision-free qids (`task:e{epoch}:p{index}`) across dataset wraps,
per-task reward curves on the metrics plane
(`areal_mixture_task_reward{task=…}` + the `task_reward_min` /
`grade_latency_p99` / `verifier_queue_depth` fleet signals with SLO
examples evaluated), per-task replay watermarks, and per-task e2e
lineage attribution in `trace_report --lineage`.  (c) a slow-verifier
A/B: the same smoke with one backend's grade latency inflated 10x via a
`slow@point=grade` fault — asserted: rollout DISPATCH throughput is not
degraded (grading is async), while the slow backend still grades.

Exit 0 iff every check passes.  CI-friendly: CPU-only, tiny random
model, a few minutes end to end.
"""

import argparse
import asyncio
import concurrent.futures
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Paranoid page allocator: validate every allocator transition.
os.environ.setdefault("AREAL_PAGING_CHECK", "1")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def check_serving_plane(n_prompts: int, n_versions: int) -> int:
    import jax
    import numpy as np

    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        LLMAPIClient,
    )
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.replay import ReplayBuffer
    from areal_tpu.system.rollout import RolloutController

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    # max_decode_batch=2 with 6-way client concurrency forces the
    # interruptible inflight paged path (static paths drain instead);
    # an unreachable EOS keeps every decode running the full window so
    # weight pushes reliably land mid-flight.
    engine = GeneratorEngine(
        cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
        max_decode_batch=2,
    )
    server = GenerationServer(engine, max_wait_ms=20.0)
    cap = 2
    replay = ReplayBuffer(capacity=8, max_head_offpolicyness=cap)
    client = LLMAPIClient(server.url, max_inflight=6)
    # 160 new tokens = 5 decode chunks per request: a multi-wave run
    # lasts long enough that a push issued while live_slots > 0 hits a
    # chunk boundary before the run drains.
    ctl = RolloutController(
        [client],
        replay,
        GenerationHyperparameters(n=1, max_new_tokens=160),
        max_concurrency=6,
        backpressure_poll_s=0.01,
        autosize_inflight=False,
    )
    # Materialize the pushed weights up front: jitting init_params
    # inside the push loop would stall the push past the decode window.
    push_params = [
        jax.block_until_ready(tfm.init_params(cfg, jax.random.PRNGKey(100 + i)))
        for i in range(n_versions)
    ]
    rng = np.random.default_rng(0)
    prompts = [
        (f"q{i}", [int(t) for t in rng.integers(8, cfg.vocab_size, size=6)])
        for i in range(n_prompts)
    ]

    consumed = []
    staleness_seen = []
    # The trainer side gets its own executor: the controller's in-flight
    # agenerate posts park one default-executor thread each for a whole
    # decode, so asyncio.to_thread would queue the weight push behind
    # them and it would land only after the run drains — exactly the
    # interruption this check must exercise.
    trainer_pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="trainer"
    )

    async def drive():
        loop = asyncio.get_running_loop()
        pump = asyncio.create_task(ctl.run(prompts))
        pushes = 0
        try:
            while pushes < n_versions:
                # Drain most of a wave so the pump's backpressure lifts
                # and the next wave of decodes launches.
                trajs = await loop.run_in_executor(
                    trainer_pool, replay.get_batch, 4, 60.0
                )
                for t in trajs:
                    staleness_seen.append(t.staleness(replay.version))
                consumed.extend(trajs)
                # "Train step": push fresh weights in memory while decode
                # is in flight (wait for live slots so the push actually
                # interrupts something).
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    if server.health_info()["live_slots"] > 0:
                        break
                    await asyncio.sleep(0.002)
                v = await loop.run_in_executor(
                    trainer_pool, server.update_weights_inmem,
                    push_params[pushes],
                )
                replay.set_version(v)
                pushes += 1
        finally:
            ctl.stop()
            await pump

    try:
        asyncio.run(drive())
    finally:
        server.close()
        trainer_pool.shutdown(wait=False)

    failures = []
    if server.version < n_versions:
        failures.append(
            f"expected >= {n_versions} weight versions, got {server.version}"
        )
    if any(s > cap for s in staleness_seen):
        failures.append(
            f"trainer consumed staleness beyond the cap {cap}: "
            f"{sorted(set(staleness_seen))}"
        )
    if not consumed:
        failures.append("trainer consumed nothing")
    spanned = [t for t in consumed if t.version_end > t.version_start]
    if not spanned:
        failures.append(
            "no trajectory finished under a newer version than it started "
            "(no in-flight request was interrupted by a weight push)"
        )
    if engine.resume_replays < 1:
        failures.append(
            "engine never resumed an interrupted decode on existing KV "
            f"pages (resume_replays={engine.resume_replays})"
        )
    head_versions = sorted({t.version_start for t in consumed})
    if len(head_versions) < 2:
        failures.append(
            f"consumed trajectories span too few head versions: "
            f"{head_versions}"
        )
    for f in failures:
        print(f"FAIL[serving]: {f}")
    if not failures:
        print(
            f"OK[serving]: {len(consumed)} trajectories consumed across "
            f"head versions {head_versions} (server at v{server.version}); "
            f"{len(spanned)} interrupted+resumed in flight "
            f"(resume_replays={engine.resume_replays}); "
            f"staleness seen {sorted(set(staleness_seen))} <= cap {cap}; "
            f"controller stat {ctl.stat.as_dict()}"
        )
    return len(failures)


def check_trainer_plane(fileroot: str) -> int:
    import jax
    import numpy as np

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.experiments.common import (
        PPOMathConfig,
        build_ppo_math,
        run_experiment,
    )
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(16, seed=7)

    def make(mho, sub):
        return PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface_args={
                "id2info": {r["query_id"]: r for r in rows}
            },
            gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
            ppo_kwargs={"n_minibatches": 1, "kl_ctl": 0.0},
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
            max_head_offpolicyness=mho,
            batch_size=4,
            total_train_epochs=1,
            seed=1,
            ctrl=ExperimentSaveEvalControl(),
            fileroot=os.path.join(fileroot, sub),
        )

    failures = []

    # Async pipeline with a real staleness budget: decoupled-PPO stats
    # must be in the step stats and the bound must hold at every step.
    _, stats = run_experiment(
        build_ppo_math(make(1, "async"), tok), tokenizer=tok
    )
    for s in stats:
        if not np.isfinite(s.get("actor_train/behav_imp_weight", np.nan)):
            failures.append("behav_imp_weight missing from step stats")
            break
        if not 0.0 <= s.get("actor_train/behav_cap_clip", -1.0) <= 1.0:
            failures.append("behav_cap_clip missing or out of [0, 1]")
            break
        if s["replay/staleness"] > 1 or s["replay/rejected"] > 0:
            failures.append(
                f"staleness bound violated: {s['replay/staleness']} "
                f"(rejected={s['replay/rejected']})"
            )
            break
    if not any(s["replay/staleness"] == 1 for s in stats):
        failures.append("pipeline never reached steady-state staleness 1")

    # Degradation: cap=0 must equal the synchronous trial bit for bit.
    m_sync, s_sync = run_experiment(
        build_ppo_math(make(None, "sync"), tok), tokenizer=tok
    )
    m_async, s_async = run_experiment(
        build_ppo_math(make(0, "cap0"), tok), tokenizer=tok
    )
    keys = (
        "actor_train/loss", "actor_train/actor_loss",
        "actor_train/approx_kl", "actor_train/importance_weight",
        "actor_train/grad_norm", "actor_train/task_reward",
    )
    for t, (a, b) in enumerate(zip(s_sync, s_async)):
        for k in keys:
            if a[k] != b[k]:
                failures.append(
                    f"cap=0 diverged from sync at step {t}: {k} "
                    f"{a[k]} != {b[k]}"
                )
    pa = m_sync.pool.workers[0].models["actor@0"].engine.get_params()
    pb = m_async.pool.workers[0].models["actor@0"].engine.get_params()
    diff = max(
        float(
            np.abs(
                np.asarray(x, np.float32) - np.asarray(y, np.float32)
            ).max()
        )
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb))
    )
    if diff != 0.0:
        failures.append(f"cap=0 final weights differ from sync by {diff}")

    for f in failures:
        print(f"FAIL[trainer]: {f}")
    if not failures:
        print(
            f"OK[trainer]: async steps={len(stats)} with decoupled-PPO "
            f"stats (behav_imp_weight last="
            f"{stats[-1]['actor_train/behav_imp_weight']:.6f}, "
            f"behav_cap_clip last="
            f"{stats[-1]['actor_train/behav_cap_clip']:.4f}); "
            f"cap=0 == sync exactly over {len(s_sync)} steps "
            f"(max param diff {diff})"
        )
    return len(failures)


def check_chaos(n_prompts: int = 40, kill_after_s: float = 2.5) -> int:
    """Elastic-fleet chaos leg: 3 discovered servers, one killed
    mid-decode via AREAL_FAULTS, zero lost prompts.  Runs traced: the
    killed victim must leave a flight-recorder dump containing its last
    dispatch, and the merged shards must join >= 95% of the consumed
    trajectories into complete dispatch -> trained lineage timelines."""
    import json

    import jax
    import numpy as np

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.apps import trace_report
    from areal_tpu.base import name_resolve, tracer
    from areal_tpu.base.name_resolve import MemoryNameResolveRepository
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.fleet import CircuitBreaker, fleet_discovery
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.replay import ReplayBuffer
    from areal_tpu.system.rollout import RolloutController

    # The fleet subtree lives in an in-process repository: the whole
    # chaos drama — joins, the TTL'd dead window, the re-join — plays
    # out through the same name_resolve API a real deployment uses.
    name_resolve.set_default(MemoryNameResolveRepository())
    exp, trial = "chaos", "t0"
    failures = []

    # Traced run: lineage events land in shards, and AREAL_TRACE_DIR
    # gives the victim's fault-kill flight dump somewhere to go.
    trace_dir = tempfile.mkdtemp(prefix="areal_tpu_chaos_trace_")
    os.environ["AREAL_TRACE_DIR"] = trace_dir
    tracer.configure(
        role="chaos", rank=0, dir=trace_dir, enabled=True, force=True
    )

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])

    def make_engine():
        # Unreachable EOS keeps every decode running its full window, so
        # the kill reliably lands while requests are in flight.
        return GeneratorEngine(
            cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
            max_decode_batch=2,
        )

    servers = []
    victim = None
    for i in range(3):
        if i == 0:
            # The victim reads its fault spec from the environment —
            # exactly how a chaos run breaks a real server binary.
            os.environ["AREAL_FAULTS"] = f"kill@t={kill_after_s}s"
            try:
                srv = GenerationServer(
                    make_engine(), max_wait_ms=20.0, zmq_port=None
                )
            finally:
                del os.environ["AREAL_FAULTS"]
            victim = srv
        else:
            srv = GenerationServer(
                make_engine(), max_wait_ms=20.0, zmq_port=None
            )
        # Long TTL on purpose: a crashed server's announcement must
        # outlive the dead window so the controller keeps its breaker
        # state (same identity) instead of reaping + re-adding it.
        srv.announce(exp, trial, ttl=30.0)
        servers.append(srv)
    victim_sid = f"s{victim.port}"
    victim_port = victim.port
    victim_engine = victim.engine

    cap = 2
    replay = ReplayBuffer(capacity=4, max_head_offpolicyness=cap)
    ctl = RolloutController(
        replay=replay,
        gconfig=GenerationHyperparameters(n=1, max_new_tokens=64),
        discovery=fleet_discovery(exp, trial),
        max_concurrency=6,
        health_refresh_s=0.3,
        backpressure_poll_s=0.01,
        autosize_inflight=False,
        dispatch_timeout_s=60.0,
        max_dispatch_retries=4,
        retry_backoff_s=0.05,
        health_poll_timeout_s=1.0,
        breaker_threshold=2,
        breaker_cooldown_s=1.0,
    )
    push_params = jax.block_until_ready(
        tfm.init_params(cfg, jax.random.PRNGKey(100))
    )
    rng = np.random.default_rng(0)
    prompts = [
        (f"q{i}", [int(t) for t in rng.integers(8, cfg.vocab_size, size=6)])
        for i in range(n_prompts)
    ]
    consumed = []
    staleness_seen = []
    chaos_done = asyncio.Event()
    restarted = {}

    async def wait_until(cond, timeout, what) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            await asyncio.sleep(0.1)
        failures.append(f"timeout waiting for {what}")
        return False

    async def consume(pump: "asyncio.Task"):
        loop = asyncio.get_running_loop()
        while not pump.done() or len(replay) > 0:
            # Throttle the drain while the chaos choreography is still
            # playing out: backpressure keeps undispatched prompts in
            # reserve, so the breaker's close probe always has live
            # dispatch traffic (and prompts) left to ride on.
            if not chaos_done.is_set() and len(consumed) >= n_prompts // 3:
                k, pause = 1, 0.3
            else:
                k, pause = 2, 0.05
            if pump.done():
                # Tail drain: get_batch(k) raises on a partial batch, so
                # a lone leftover trajectory must be taken one at a time.
                k = 1
            try:
                trajs = await loop.run_in_executor(
                    None, replay.get_batch, k, 0.2
                )
            except TimeoutError:
                trajs = []
            for t in trajs:
                staleness_seen.append(t.staleness(replay.version))
            consumed.extend(trajs)
            await asyncio.sleep(pause)

    def restart_victim():
        # The old collector may still be finishing its last batch; the
        # engine is single-threaded, so hand it to the new server only
        # once that thread exits.
        victim._collector_thread.join(timeout=60)
        srv = GenerationServer(
            victim_engine, port=victim_port, max_wait_ms=20.0,
            zmq_port=None,
            # Rejoin at the trainer's CURRENT version: starting at 0
            # would stamp every response maximally stale.
            version=replay.version,
        )
        srv.announce(exp, trial, ttl=30.0)
        restarted["server"] = srv

    async def drive():
        pump = asyncio.create_task(ctl.run(prompts))
        consumer = asyncio.create_task(consume(pump))
        try:
            # 1. The victim kills itself mid-decode; failed/timed-out
            #    dispatches re-route and its breaker trips open.
            def breaker_open():
                st = ctl.server(victim_sid)
                return st is not None and st.breaker.opens >= 1

            if await wait_until(breaker_open, 120, "breaker to open"):
                # 2. Restart on the SAME port (same fleet identity).
                await asyncio.to_thread(restart_victim)
                # 3. The half-open health probe re-closes the breaker.
                def breaker_closed():
                    st = ctl.server(victim_sid)
                    return (
                        st is not None
                        and st.breaker.opens >= 1
                        and st.breaker.state == CircuitBreaker.CLOSED
                    )

                if await wait_until(
                    breaker_closed, 120, "breaker to re-close"
                ):
                    # 4. A weight push proves the staleness bound still
                    #    holds across the healed fleet.
                    alive = [
                        s for s in servers if s is not victim
                    ] + [restarted["server"]]
                    v = 0
                    for s in alive:
                        v = await asyncio.to_thread(
                            s.update_weights_inmem, push_params
                        )
                    if v:
                        replay.set_version(v)
        finally:
            chaos_done.set()
            await pump
            await consumer

    try:
        asyncio.run(drive())
    finally:
        for s in servers[1:]:
            s.close()
        if "server" in restarted:
            restarted["server"].close()
        if not victim._crashed:  # kill never fired: don't leak the server
            victim.close()

    stat = ctl.stat
    # Zero lost prompts: every dispatched prompt reached a terminal,
    # ACCOUNTED state — and under this fault none may end up failed.
    if stat.accepted + stat.rejected != n_prompts or stat.failed != 0:
        failures.append(
            f"prompt accounting broken: accepted {stat.accepted} + "
            f"rejected {stat.rejected} != {n_prompts} dispatched "
            f"(failed={stat.failed})"
        )
    if stat.redispatched < 1:
        failures.append(
            "kill produced no redispatch (expected failed dispatches to "
            "re-route to surviving servers)"
        )
    if any(s > cap for s in staleness_seen):
        failures.append(
            f"staleness bound violated: {sorted(set(staleness_seen))} "
            f"vs cap {cap}"
        )
    st = ctl.server(victim_sid)
    if st is None:
        failures.append(f"victim {victim_sid} lost from the fleet")
    else:
        if st.breaker.opens < 1:
            failures.append("victim breaker never opened")
        if st.breaker.state != CircuitBreaker.CLOSED:
            failures.append(
                f"victim breaker ended {st.breaker.state}, not closed"
            )
    if len(ctl.servers) != 3:
        failures.append(
            f"expected 3 fleet members, controller knows "
            f"{[s.sid for s in ctl.servers]}"
        )
    if ctl.membership_epoch < 1:
        failures.append("membership epoch never advanced")
    if victim._faults is None or victim._faults.fired.get("kill", 0) < 1:
        failures.append("the AREAL_FAULTS kill fault never fired")

    # ---- flight recorder: the victim must have dumped its ring ------
    flight_path = os.path.join(
        trace_dir, f"flightrec_gen_server_{victim_port}.json"
    )
    if not os.path.exists(flight_path):
        failures.append(
            f"killed victim left no flight-recorder dump at {flight_path}"
        )
    else:
        with open(flight_path) as f:
            dump = json.load(f)
        events = dump.get("events", [])
        if dump.get("reason") != "fault_kill":
            failures.append(
                f"flight dump reason {dump.get('reason')!r} != 'fault_kill'"
            )
        if not any(e.get("kind") == "kill" for e in events):
            failures.append("flight dump ring is missing the kill event")
        if not any(
            e.get("kind") == "dispatch" and e.get("sid") == victim_sid
            for e in events
        ):
            failures.append(
                "flight dump does not contain the victim's last dispatch"
            )
    rendered = trace_report.format_flight(trace_dir, window_s=60.0)
    if rendered.startswith("no flightrec"):
        failures.append("trace_report --flight rendered no dumps")

    # ---- lineage: >= 95% of consumed trajectories join end to end ---
    tracer.flush()
    trace = tracer.merge_shards(
        trace_dir, out_path=os.path.join(trace_dir, "trace.json")
    )
    os.environ.pop("AREAL_TRACE_DIR", None)
    errors = tracer.validate_trace(trace)
    if errors:
        failures.append(f"merged chaos trace invalid: {errors[:3]}")
    summary = trace_report.lineage_summary(trace)
    if summary["orphans"]:
        failures.append(
            f"orphan lineage traces (no dispatch root): "
            f"{summary['orphans'][:3]}"
        )
    if summary["n"] != n_prompts:
        failures.append(
            f"expected {n_prompts} lineage roots, got {summary['n']}"
        )
    if summary["complete"] < 0.95 * len(consumed):
        failures.append(
            f"lineage joined only {summary['complete']} of "
            f"{len(consumed)} consumed trajectories dispatch->trained"
        )
    accounted = (
        summary["complete"] + summary["in_flight"]
        + summary["rejected_stale"] + summary["failed"]
    )
    if accounted < summary["n"]:
        failures.append(
            f"unaccounted lineage traces: {summary['n'] - accounted} of "
            f"{summary['n']} neither complete, in-flight, rejected, nor "
            f"failed"
        )

    for f in failures:
        print(f"FAIL[chaos]: {f}")
    if not failures:
        vb = st.breaker
        print(
            f"OK[chaos]: {n_prompts} prompts, zero lost "
            f"(accepted={stat.accepted} rejected={stat.rejected} "
            f"failed={stat.failed} redispatched={stat.redispatched}); "
            f"victim {victim_sid} killed at t={kill_after_s}s, breaker "
            f"opened x{vb.opens} and re-closed x{vb.closes}; staleness "
            f"seen {sorted(set(staleness_seen))} <= cap {cap}; "
            f"membership epoch {ctl.membership_epoch}; lineage "
            f"{summary['complete']}/{summary['n']} complete "
            f"(+{summary['in_flight']} in-flight, "
            f"{summary['rejected_stale']} rejected) with 0 orphans; "
            f"victim flight dump at {flight_path}"
        )
        print()
        print("--- trace_report --flight (last 60s before the kill) ---")
        print(rendered)
    return len(failures)


def check_verifier_chaos(kill_after_s: float = 1.2) -> int:
    """Verifier-service-fleet chaos leg (module docstring, Part 9):
    killed worker -> zero lost grades + redispatch + breaker cycle +
    lane refill; mixed-task mixture smoke with per-task reward curves
    and lineage attribution; slow-verifier A/B."""
    import json

    from areal_tpu.apps import metrics_report, trace_report
    from areal_tpu.base import faults as faults_mod
    from areal_tpu.base import metrics, name_resolve, tracer
    from areal_tpu.base.name_resolve import MemoryNameResolveRepository
    from areal_tpu.system.fleet import CircuitBreaker, SupervisorLane
    from areal_tpu.system.verifier_pool import (
        VerifierPool,
        VerifierWorker,
        list_verifiers,
        verifier_discovery,
    )

    name_resolve.set_default(MemoryNameResolveRepository())
    failures = []
    trace_dir = tempfile.mkdtemp(prefix="areal_tpu_vchaos_trace_")
    os.environ["AREAL_TRACE_DIR"] = trace_dir
    tracer.configure(
        role="vchaos", rank=0, dir=trace_dir, enabled=True, force=True
    )

    def wait_until(cond, timeout, what) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if cond():
                return True
            time.sleep(0.05)
        failures.append(f"timeout waiting for {what}")
        return False

    # ---- (a) fleet chaos: kill one of three graders mid-grade --------
    exp, trial = "vchaos", "t0"
    workers = []
    victim = None
    for i in range(3):
        injector = None
        if i == 0:
            # The slow fault keeps grades in flight when the kill lands;
            # the SHORT TTL lets the reaper evict the crashed
            # announcement so the supervisor lane sees the hole.
            injector = faults_mod.FaultInjector.parse(
                f"slow@ms=100&point=grade kill@t={kill_after_s}s"
            )
        w = VerifierWorker(port=0, faults=injector)
        w.announce(exp, trial, ttl=(2.0 if i == 0 else 10.0))
        workers.append(w)
        if i == 0:
            victim = w
    victim_sid = f"v{victim.port}"
    victim_port = victim.port

    pool = VerifierPool(
        discovery=verifier_discovery(exp, trial),
        attempt_timeout_s=8.0,
        max_attempts=3,
        backoff_s=0.01,
        refresh_s=0.05,
        breaker_threshold=1,
        breaker_cooldown_s=0.4,
    )

    stop_pump = threading.Event()
    count_lock = threading.Lock()
    counts = {"items": 0, "ok": 0}
    pump_errors = []

    def math_items(k=3):
        return [
            {
                "task": "math",
                "text": r"The answer is \boxed{4}.",
                "payload": {"solutions": [r"\boxed{4}"]},
            }
            for _ in range(k)
        ]

    def pump():
        while not stop_pump.is_set():
            items = math_items()
            try:
                res = pool.verify_batch(items)
            except Exception as e:  # noqa: BLE001 — a loss is a finding
                pump_errors.append(repr(e))
                return
            if len(res) != len(items):
                pump_errors.append(
                    f"shape: sent {len(items)}, got {len(res)}"
                )
            with count_lock:
                counts["items"] += len(items)
                counts["ok"] += sum(map(bool, res))
            time.sleep(0.01)

    pumpers = [
        threading.Thread(target=pump, daemon=True) for _ in range(3)
    ]
    for t in pumpers:
        t.start()

    # The supervisor's verifier lane: refill back to 3 when the TTL
    # reaper evicts the crashed worker.  Spawn restarts on the SAME port
    # so the replacement resumes the victim's fleet identity (and the
    # pool's persisted breaker re-closes via a half-open probe).
    respawned = []

    def respawn():
        w = VerifierWorker(port=victim_port)
        w.announce(exp, trial, ttl=10.0)
        respawned.append(w)

    lane = SupervisorLane(
        name="verifier",
        list_servers=lambda: list_verifiers(exp, trial),
        spawn=respawn,
        drain=lambda sid: None,
        min_servers=3,
        max_servers=4,
        action_cooldown_s=5.0,
        idle_rounds=10**6,  # this leg proves refill, not scale-down
    )

    wait_until(lambda: victim._crashed, 30, "the verifier kill fault")
    wait_until(
        lambda: len(list_verifiers(exp, trial)) == 2,
        30,
        "TTL eviction of the crashed verifier",
    )
    wait_until(
        lambda: (
            victim_sid in pool.breakers
            and pool.breakers[victim_sid].opens >= 1
        ),
        30,
        "the victim's breaker to open",
    )
    refill = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        decision = lane.step([])
        if decision.action == "spawn":
            refill = decision
            break
        time.sleep(0.1)
    if refill is None:
        failures.append("supervisor lane never refilled the verifier pool")
    elif "refill" not in refill.reason:
        failures.append(f"unexpected refill reason {refill.reason!r}")
    wait_until(
        lambda: len(list_verifiers(exp, trial)) == 3,
        30,
        "the replacement verifier to announce",
    )
    wait_until(
        lambda: (
            pool.breakers[victim_sid].state == CircuitBreaker.CLOSED
            and pool.breakers[victim_sid].closes >= 1
        ),
        30,
        "the victim breaker to re-close on the replacement",
    )
    time.sleep(0.5)  # post-heal traffic rides the re-closed breaker
    stop_pump.set()
    for t in pumpers:
        t.join(timeout=30)

    for e in pump_errors:
        failures.append(f"grade pump error: {e}")
    if counts["ok"] != counts["items"] or counts["items"] == 0:
        failures.append(
            f"lost grades: {counts['ok']} of {counts['items']} items "
            f"came back correct"
        )
    if pool.redispatches < 1:
        failures.append(
            "kill produced no redispatch (expected a failed grade batch "
            "to retry on a different server)"
        )
    if pool.graded_local > 0:
        failures.append(
            f"pool degraded to local grading ({pool.graded_local} items) "
            f"despite live backends"
        )
    if victim._faults is None or victim._faults.fired.get("kill", 0) < 1:
        failures.append("the AREAL_FAULTS kill fault never fired")
    br = pool.breakers.get(victim_sid)
    if br is None:
        failures.append(f"no breaker tracked for victim {victim_sid}")
    else:
        if br.opens < 1:
            failures.append("victim breaker never opened")
        if br.closes < 1 or br.state != CircuitBreaker.CLOSED:
            failures.append(
                f"victim breaker ended {br.state} "
                f"(opens={br.opens} closes={br.closes}), not re-closed"
            )
    flight_path = os.path.join(
        trace_dir, f"flightrec_verifier_{victim_port}.json"
    )
    if not os.path.exists(flight_path):
        failures.append(
            f"killed verifier left no flight dump at {flight_path}"
        )
    else:
        with open(flight_path) as f:
            dump = json.load(f)
        if dump.get("reason") != "fault_kill":
            failures.append(
                f"flight dump reason {dump.get('reason')!r} != 'fault_kill'"
            )
    for w in workers[1:] + respawned:
        w.close()
    fleet_ok = not failures
    if fleet_ok:
        print(
            f"OK[verifier-chaos]: {counts['items']} grade items, zero "
            f"lost; victim {victim_sid} killed at t={kill_after_s}s, "
            f"{pool.redispatches} batch(es) redispatched, breaker opened "
            f"x{br.opens} and re-closed x{br.closes}; lane refilled the "
            f"pool to 3 ({refill.reason}); flight dump at {flight_path}"
        )

    # ---- (b)+(c) mixed-task mixture smoke + slow-verifier A/B --------
    import jax
    import numpy as np

    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.data.mixture import TaskMixtureStream, TaskSource
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.episode import RewardFabric
    from areal_tpu.system.fleet import fleet_discovery
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.replay import ReplayBuffer
    from areal_tpu.system.rollout import RolloutController

    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    rng = np.random.default_rng(0)

    def make_prompts(n):
        return [
            [int(t) for t in rng.integers(8, cfg.vocab_size, size=6)]
            for _ in range(n)
        ]

    code_text = "```python\nprint(input())\n```"
    code_payload = {
        "input_output": json.dumps({"inputs": ["5\n"], "outputs": ["5\n"]})
    }

    def mix_run(tag, slow_ms, n_mix=16):
        """One mixed-task rollout graded through a 2-worker pool; returns
        (dispatch_elapsed_s, mixture, consumed, replay, stat, vworkers)."""
        exp2, trial2 = f"vmix_{tag}", "t0"
        vworkers = []
        for i in range(2):
            # Both backends carry a base grade latency so the A/B has a
            # real baseline; the B run inflates one backend 10x.
            ms = slow_ms if i == 1 else 30
            vw = VerifierWorker(
                port=0,
                faults=faults_mod.FaultInjector.parse(
                    f"slow@ms={ms}&point=grade"
                ),
            )
            vw.announce(exp2, trial2, ttl=30.0)
            vworkers.append(vw)
        pool2 = VerifierPool(
            discovery=verifier_discovery(exp2, trial2),
            attempt_timeout_s=30.0,
            refresh_s=0.1,
        )
        mixture = TaskMixtureStream(
            [
                TaskSource("math", make_prompts(5), weight=2.0),
                TaskSource("code", make_prompts(3), weight=1.0),
            ]
        )
        fabric = RewardFabric(
            remote=pool2, max_workers=4,
            on_result=mixture.observe_reward,
        )
        srv = GenerationServer(
            GeneratorEngine(
                cfg, params, mesh, eos_token_id=cfg.vocab_size + 7,
                max_decode_batch=2,
            ),
            max_wait_ms=20.0,
            zmq_port=None,
        )
        srv.announce(exp2, trial2, ttl=30.0)
        replay = ReplayBuffer(capacity=4, max_head_offpolicyness=2)
        ctl = RolloutController(
            replay=replay,
            gconfig=GenerationHyperparameters(n=1, max_new_tokens=16),
            discovery=fleet_discovery(exp2, trial2),
            mixture=mixture,
            max_concurrency=4,
            health_refresh_s=0.3,
            backpressure_poll_s=0.01,
            autosize_inflight=False,
            dispatch_timeout_s=60.0,
        )
        consumed = []
        futs = []

        async def consume(pump_task):
            loop = asyncio.get_running_loop()
            while not pump_task.done() or len(replay) > 0:
                try:
                    trajs = await loop.run_in_executor(
                        None, replay.get_batch, 1, 0.2
                    )
                except TimeoutError:
                    trajs = []
                for t in trajs:
                    consumed.append(t)
                    # Canned grade texts (the tiny random model emits
                    # gibberish): math alternates pass/fail so the
                    # reward EMA curve moves; code runs the sandbox.
                    if t.task == "code":
                        text, payload = code_text, code_payload
                    else:
                        passing = len(consumed) % 3 != 0
                        text = r"\boxed{4}" if passing else r"\boxed{5}"
                        payload = {"solutions": [r"\boxed{4}"]}
                    futs.append(
                        fabric.submit(
                            t.task, text, payload, trace_id=t.trace_id
                        )
                    )
                await asyncio.sleep(0.01)

        async def drive():
            t0 = time.monotonic()
            pump_task = asyncio.create_task(ctl.run(max_prompts=n_mix))
            consumer = asyncio.create_task(consume(pump_task))
            await pump_task
            elapsed = time.monotonic() - t0
            await consumer
            return elapsed

        try:
            elapsed = asyncio.run(drive())
            for f in futs:
                f.result(timeout=120)
        finally:
            srv.close()
        return elapsed, mixture, consumed, replay, ctl.stat, vworkers

    elapsed_a, mix_a, consumed_a, replay_a, stat_a, vws_a = mix_run(
        "a", slow_ms=30
    )
    for w in vws_a:
        w.close()
    elapsed_b, mix_b, consumed_b, replay_b, stat_b, vws_b = mix_run(
        "b", slow_ms=300
    )

    for tag, stat, consumed in (
        ("a", stat_a, consumed_a), ("b", stat_b, consumed_b),
    ):
        if stat.accepted + stat.rejected != 16 or stat.failed != 0:
            failures.append(
                f"[mix {tag}] prompt accounting broken: "
                f"accepted {stat.accepted} + rejected {stat.rejected} "
                f"!= 16 (failed={stat.failed})"
            )
        qids = [t.qid for t in consumed]
        if len(set(qids)) != len(qids):
            failures.append(f"[mix {tag}] duplicate qids: {sorted(qids)}")
        bad = [
            q for q in qids
            if not (q.startswith("math:e") or q.startswith("code:e"))
        ]
        if bad:
            failures.append(
                f"[mix {tag}] qids not task-namespaced: {bad[:4]}"
            )
        tasks_consumed = {t.task for t in consumed}
        if tasks_consumed != {"math", "code"}:
            failures.append(
                f"[mix {tag}] consumed tasks {tasks_consumed} != both"
            )
    # The mixture cycled its datasets: epoch-stamped qids keep replay
    # dedup keys unique across wraps (the old prompt{cursor} scheme
    # collides here).
    if mix_a.state_dict()["epochs"]["math"] < 1:
        failures.append(
            "math dataset never wrapped — the epoch-stamp leg is vacuous"
        )
    for mix in (mix_a, mix_b):
        if mix.reward_ema("math") is None or mix.reward_ema("code") is None:
            failures.append("a task's reward EMA never updated")
            break
    wm = replay_a.task_watermarks()
    if set(wm) != {"math", "code"}:
        failures.append(f"replay task watermarks {sorted(wm)} != both tasks")
    else:
        mix_a.sync_replay(wm)  # curriculum <- replay plumbing holds
        if sum(v["consumed"] for v in wm.values()) != len(consumed_a):
            failures.append("per-task consumed counts do not add up")

    # (c) slow-verifier A/B: grading is async, so a 10x-slower backend
    # must not degrade rollout dispatch throughput.
    if elapsed_b > 2.0 * elapsed_a + 1.0:
        failures.append(
            f"dispatch throughput degraded under the slow verifier: "
            f"{elapsed_b:.2f}s vs baseline {elapsed_a:.2f}s"
        )
    slow_graded = vws_b[1].graded
    if slow_graded < 1:
        failures.append("the slow backend never graded anything")
    for w in vws_b:
        w.close()

    # Per-task reward curves + fleet signals on the metrics plane, with
    # the SLO examples from the metrics_report docstring evaluated.
    samples, _ = metrics_report.parse_prometheus_text(
        metrics.default_registry().expose()
    )
    task_rewards = {
        labels.get("task"): v
        for n, labels, v in samples
        if n == "areal_mixture_task_reward"
    }
    if not {"math", "code"} <= set(task_rewards):
        failures.append(
            f"per-task reward gauges missing: have {sorted(task_rewards)}"
        )
    scrape = metrics_report.RoleScrape("local", time.monotonic(), samples)
    signals, _rows = metrics_report.fleet_signals([scrape], None)
    for sig in ("grade_latency_p99", "verifier_queue_depth",
                "task_reward_min"):
        if sig not in signals:
            failures.append(f"fleet signal {sig!r} missing: {signals}")
    slo_lines = []
    for text in (
        "crit: grade_latency_p99 <= 5",
        "crit: verifier_queue_depth <= 64",
        "warn: task_reward_min >= 0.05",
    ):
        rule = metrics_report.parse_slo_rule(text)
        msg = rule.evaluate([signals])
        slo_lines.append(f"  {text!r}: {'VIOLATED: ' + msg if msg else 'holds'}")
        if msg is not None and rule.signal != "task_reward_min":
            failures.append(f"SLO example unexpectedly violated: {msg}")

    # Per-task e2e lineage attribution through trace_report --lineage.
    tracer.flush()
    trace = tracer.merge_shards(
        trace_dir, out_path=os.path.join(trace_dir, "trace.json")
    )
    os.environ.pop("AREAL_TRACE_DIR", None)
    summary = trace_report.lineage_summary(trace)
    by_task = {b["task"]: b for b in summary["by_task"]}
    if not {"math", "code"} <= set(by_task):
        failures.append(
            f"lineage by_task missing tasks: have {sorted(by_task)}"
        )
    else:
        for task in ("math", "code"):
            if by_task[task]["complete"] < 1:
                failures.append(
                    f"no complete {task} lineage timeline "
                    f"(n={by_task[task]['n']})"
                )
    rendered = trace_report.format_lineage(trace)
    if "task=math" not in rendered or "task=code" not in rendered:
        failures.append("trace_report --lineage renders no per-task rows")

    for f in failures:
        print(f"FAIL[verifier-chaos]: {f}")
    if not failures:
        print(
            f"OK[verifier-mix]: 2x16 mixed-task prompts "
            f"(math:code = 2:1), namespaced qids across dataset wraps, "
            f"reward EMAs math={mix_b.reward_ema('math'):.2f} "
            f"code={mix_b.reward_ema('code'):.2f}; dispatch elapsed "
            f"{elapsed_a:.2f}s baseline vs {elapsed_b:.2f}s with one "
            f"10x-slow backend ({slow_graded} items on it); signals "
            + ", ".join(
                f"{k}={signals[k]:.3g}"
                for k in (
                    "grade_latency_p99", "verifier_queue_depth",
                    "task_reward_min",
                )
            )
        )
        print()
        print("--- SLO examples over the scraped signals ---")
        for ln in slo_lines:
            print(ln)
        print()
        print("--- trace_report --lineage (per-task attribution) ---")
        for ln in rendered.splitlines():
            if ln.startswith("  task=") or "traces:" in ln:
                print(ln)
    return len(failures)


def check_push_chaos(n_servers: int = 5, fanout: int = 2) -> int:
    """Parameter-distribution-fabric chaos leg (see module docstring,
    Part 8): kill the first relay mid-broadcast, prove zero torn
    versions + the v-1 staleness bound, repair, converge."""
    import json

    import jax

    from areal_tpu.apps import trace_report
    from areal_tpu.base import faults, integrity, name_resolve, tracer
    from areal_tpu.base.name_resolve import MemoryNameResolveRepository
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system import paramstore
    from areal_tpu.system.fleet import fleet_discovery
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.paramstore import (
        BroadcastFabric,
        ParamStore,
        plan_tree,
        subtree_sids,
    )

    name_resolve.set_default(MemoryNameResolveRepository())
    exp, trial = "pushchaos", "t0"
    failures = []
    trace_dir = tempfile.mkdtemp(prefix="areal_tpu_push_chaos_trace_")
    os.environ["AREAL_TRACE_DIR"] = trace_dir
    tracer.configure(
        role="push_chaos", rank=0, dir=trace_dir, enabled=True, force=True
    )

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])

    def metric(m):
        return m._default().get()

    servers = []
    for i in range(n_servers):
        eng = GeneratorEngine(
            cfg,
            tfm.init_params(cfg, jax.random.PRNGKey(i)),
            mesh,
            eos_token_id=cfg.vocab_size + 7,
        )
        srv = GenerationServer(eng, max_wait_ms=2.0, zmq_port=None)
        # Long TTL: the crashed victim's announcement must outlive the
        # dead window (crash semantics skip deregistration).
        srv.announce(exp, trial, ttl=30.0)
        servers.append(srv)
    by_sid = {f"s{s.port}": s for s in servers}
    restarted = {}

    # The victim is the FIRST relay in the planned tree: with 5 sorted
    # members at fanout 2 the chunks split [3, 2], so the lowest sid
    # heads the larger subtree and relays to two children — killing it
    # orphans exactly those three servers.
    discovery = fleet_discovery(exp, trial)
    roots = plan_tree(sorted(discovery().items()), fanout)
    victim_node = roots[0]
    victim_sid = str(victim_node["sid"])
    victim_subtree = set(subtree_sids(victim_node))
    victim = by_sid[victim_sid]
    victim_port = victim.port
    victim_engine = victim.engine
    if len(victim_node["children"]) != 2 or len(victim_subtree) != 3:
        failures.append(
            f"tree plan surprise: victim {victim_sid} heads subtree "
            f"{sorted(victim_subtree)} (expected itself + 2 children)"
        )
    # Point-scoped kill, armed AFTER construction so the victim is
    # chosen from the planned tree: the first param_push applies
    # cleanly (skip=1), the second — the v2 relay hop — crashes the
    # server mid-broadcast.
    victim._faults = faults.FaultInjector.parse(
        "kill@point=param_push&skip=1"
    )

    # retain=1 on purpose: v1 surviving the v2 push below proves the
    # ORPHANS' pins (not a retention window) are what keep head-1
    # pullable for laggards.
    store = ParamStore(retain=1)
    fabric = BroadcastFabric(
        store, discovery=discovery, fanout=fanout, timeout_s=30.0,
        experiment=exp, trial=trial,
    )
    rejected0 = metric(integrity.M_PUSH_REJECTED)
    orphans0 = metric(paramstore.M_PUSH_ORPHANS)

    pushed = [
        jax.block_until_ready(
            tfm.init_params(cfg, jax.random.PRNGKey(100 + i))
        )
        for i in range(3)
    ]
    checksums = [integrity.params_checksum(p) for p in pushed]

    def verify_fleet(live, want_version_of):
        """Every live server's params must verify against the checksum
        of EXACTLY the version it reports — the zero-torn-versions
        invariant."""
        for sid, srv in live.items():
            v = srv.version
            want = want_version_of(sid)
            if v != want:
                failures.append(
                    f"{sid} serves v{v}, expected v{want}"
                )
                continue
            if v == 0:
                continue
            try:
                integrity.verify_checksum(
                    srv.engine.params, checksums[v - 1]
                )
            except integrity.WeightChecksumError as e:
                failures.append(
                    f"TORN VERSION on {sid}: serving v{v} but params "
                    f"do not verify: {e}"
                )

    try:
        # ---- push v1: a clean fleet-wide broadcast ------------------
        store.publish(pushed[0], checksums[0])
        r1 = fabric.push()
        if not r1.ok or sorted(r1.applied) != sorted(by_sid):
            failures.append(
                f"clean v1 push did not reach the whole fleet: "
                f"applied={sorted(r1.applied)} orphans={r1.orphans}"
            )
        if r1.depth < 2:
            failures.append(
                f"v1 push depth {r1.depth} < 2: the tree degenerated "
                "to a star, nothing relayed"
            )
        verify_fleet(by_sid, lambda sid: 1)

        # ---- push v2: the victim dies mid-broadcast -----------------
        store.publish(pushed[1], checksums[1])
        r2 = fabric.push()
        orphaned = {str(o["sid"]) for o in r2.orphans}
        if orphaned != victim_subtree:
            failures.append(
                f"expected the kill to orphan exactly the victim "
                f"subtree {sorted(victim_subtree)}, got "
                f"{sorted(orphaned)}"
            )
        if sorted(r2.applied) != sorted(set(by_sid) - victim_subtree):
            failures.append(
                f"v2 push applied {sorted(r2.applied)}, expected the "
                f"non-victim subtree "
                f"{sorted(set(by_sid) - victim_subtree)}"
            )
        if metric(paramstore.M_PUSH_ORPHANS) - orphans0 != len(
            victim_subtree
        ):
            failures.append(
                "areal_param_push_orphans_total moved by "
                f"{metric(paramstore.M_PUSH_ORPHANS) - orphans0}, "
                f"expected {len(victim_subtree)}"
            )
        if victim._faults.fired.get("kill", 0) != 1:
            failures.append("the param_push kill fault never fired")
        # Staleness bound: every surviving laggard serves v1 — head-1,
        # NEVER v-2 (= v0 here, the unversioned boot weights).
        live = {
            sid: srv for sid, srv in by_sid.items() if sid != victim_sid
        }
        verify_fleet(
            live,
            lambda sid: 1 if sid in victim_subtree else 2,
        )
        skew = max(s.version for s in live.values()) - min(
            s.version for s in live.values()
        )
        if skew != 1:
            failures.append(
                f"post-kill weight_version_skew {skew}, expected 1"
            )
        # The store must still retain v1 — held alive purely by the
        # orphans' pins (retain=1 would otherwise have dropped it).
        if 1 not in store.live_versions():
            failures.append(
                "store retired v1 while orphans still pin it: the "
                "v-1 pull path is gone"
            )

        # ---- the victim's black box ---------------------------------
        flight_path = os.path.join(
            trace_dir, f"flightrec_gen_server_{victim_port}.json"
        )
        if not os.path.exists(flight_path):
            failures.append(
                f"killed relay left no flight dump at {flight_path}"
            )
        else:
            with open(flight_path) as f:
                dump = json.load(f)
            if dump.get("reason") != "fault_kill":
                failures.append(
                    f"flight dump reason {dump.get('reason')!r} != "
                    "'fault_kill'"
                )
        rendered = trace_report.format_flight(trace_dir, window_s=60.0)
        if rendered.startswith("no flightrec"):
            failures.append("trace_report --flight rendered no dumps")

        # ---- restart + repair: laggards catch up to head ------------
        victim._collector_thread.join(timeout=60)
        srv = GenerationServer(
            victim_engine, port=victim_port, max_wait_ms=2.0,
            zmq_port=None, version=1,
        )
        srv.announce(exp, trial, ttl=30.0)
        restarted["server"] = srv
        by_sid[victim_sid] = srv
        repaired = fabric.repair()
        if sorted(repaired) != sorted(victim_subtree):
            failures.append(
                f"repair caught up {sorted(repaired)}, expected the "
                f"orphaned subtree {sorted(victim_subtree)}"
            )
        verify_fleet(by_sid, lambda sid: 2)

        # ---- push v3: the whole fleet converges ---------------------
        store.publish(pushed[2], checksums[2])
        r3 = fabric.push()
        if not r3.ok or sorted(r3.applied) != sorted(by_sid):
            failures.append(
                f"post-repair v3 push did not converge: "
                f"applied={sorted(r3.applied)} orphans={r3.orphans}"
            )
        verify_fleet(by_sid, lambda sid: 3)
        # Every pin moved to v3: the stale versions retire.
        if store.live_versions() != [3]:
            failures.append(
                f"store retains {store.live_versions()} after "
                "convergence, expected [3]"
            )
        if metric(integrity.M_PUSH_REJECTED) - rejected0 != 0:
            failures.append(
                "areal_gen_weight_push_rejected_total moved: a "
                "checksum rejection fired during the chaos run"
            )
    finally:
        os.environ.pop("AREAL_TRACE_DIR", None)
        for s in servers:
            if s is victim:
                continue
            s.close()
        if "server" in restarted:
            restarted["server"].close()
        elif not victim._crashed:
            victim.close()

    for f in failures:
        print(f"FAIL[push-chaos]: {f}")
    if not failures:
        print(
            f"OK[push-chaos]: v1 broadcast reached {len(by_sid)}/"
            f"{len(by_sid)} servers (depth {r1.depth}); killing relay "
            f"{victim_sid} mid-v2 orphaned exactly its subtree "
            f"{sorted(victim_subtree)} (skew 1, laggards at v1 = "
            f"head-1, store kept v1 via pins); zero torn versions "
            f"(every applied version checksum-verified, "
            f"push_rejected delta 0); repair() caught up "
            f"{len(victim_subtree)} laggards and the v3 push "
            f"converged all {len(by_sid)} (store retains [3]); "
            f"victim flight dump rendered"
        )
        print()
        print("--- trace_report --flight (the killed relay) ---")
        print(rendered)
    return len(failures)


def check_overlap(fileroot: str, bench_out: str = None) -> int:
    """Pipeline-overlapped PPO leg: barrier vs streamed executor A/B
    with a latency-bearing reward, trace-level stall attribution, and
    compile-flatness accounting (see module docstring, Part 4)."""
    import dataclasses
    import json

    import jax
    import numpy as np

    from areal_tpu.api.config import (
        ModelAbstraction,
        ModelInterfaceAbstraction,
    )
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
        register_interface,
    )
    from areal_tpu.apps import trace_report
    from areal_tpu.base import tracer
    from areal_tpu.experiments.common import (
        PPOMathConfig,
        build_ppo_math,
        run_experiment,
    )
    from areal_tpu.interfaces.reward import MultiTaskRewardInterface
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl
    from tests import fixtures

    REWARD_LATENCY_S_PER_SEQ = 0.03
    GROUP_N = 2
    MAX_NEW_TOKENS = 64

    @dataclasses.dataclass
    class OverlapCheckReward(MultiTaskRewardInterface):
        """Rewards that vary within a group (a tiny random actor gets
        every answer wrong, and GRPO's group normalization would zero
        all-equal scores — making every numerics assertion vacuous) and
        carry a per-sequence latency modeling a remote verifier: the
        serial idle the overlapped executor exists to hide.  Per
        sequence, not per call, so the barrier (one call for the whole
        batch) and the pipeline (one call per chunk) pay the same total
        — the A/B measures scheduling, not a penalty for chunking."""

        latency_s: float = 0.0

        def inference(self, model, sample, mb_spec):
            lens = [
                l
                for row in sample.seqlens["packed_input_ids"]
                for l in row
            ]
            if self.latency_s:
                time.sleep(self.latency_s * len(lens))
            out = super().inference(model, sample, mb_spec)
            data = np.asarray(sample.data["packed_input_ids"])
            scores, off = [], 0
            for L in lens:
                scores.append(
                    float(int(np.sum(data[off:off + L])) % 7) - 3.0
                )
                off += L
            out.data["rewards"] = np.asarray(scores, np.float32)
            return out

    try:
        register_interface("overlap-check-rw", OverlapCheckReward)
    except ValueError:
        pass  # second in-process invocation

    tok = fixtures.make_tokenizer()
    rows_long = fixtures.build_math_rows(48, seed=7)  # 6 steps
    rows_short = fixtures.build_math_rows(16, seed=7)  # 2 steps

    def make(sub, rows, **kw):
        return PPOMathConfig(
            actor=ModelAbstraction("random", {"config": tiny_config()}),
            dataset=DatasetAbstraction(
                "math_code_prompt",
                {"dataset_builder": lambda: rows, "max_length": 64},
            ),
            reward_interface=ModelInterfaceAbstraction(
                "overlap-check-rw",
                {
                    "id2info": {r["query_id"]: r for r in rows},
                    "latency_s": REWARD_LATENCY_S_PER_SEQ,
                },
            ),
            gconfig=GenerationHyperparameters(
                n=GROUP_N, max_new_tokens=MAX_NEW_TOKENS
            ),
            ppo_kwargs={"n_minibatches": 1, "kl_ctl": 0.0},
            optimizer=OptimizerConfig(
                lr=5e-3, warmup_steps_proportion=0.0
            ),
            batch_size=8,
            total_train_epochs=1,
            seed=1,
            ctrl=ExperimentSaveEvalControl(),
            fileroot=os.path.join(fileroot, sub),
            **kw,
        )

    def run(tag, rows, trace_dir=None, **kw):
        # Force-reconfigure the process-global tracer per leg so each
        # leg's pipe/step spans land in their own shard dir (the
        # master's own non-force configure then no-ops).
        tracer.configure(
            role="overlap_check",
            rank=0,
            dir=trace_dir,
            enabled=trace_dir is not None,
            force=True,
        )
        m, stats = run_experiment(
            build_ppo_math(make(tag, rows, **kw), tok), tokenizer=tok
        )
        trace = None
        if trace_dir is not None:
            tracer.flush()
            trace = tracer.merge_shards(
                trace_dir, out_path=os.path.join(trace_dir, "trace.json")
            )
        os.environ.pop("AREAL_TRACE_DIR", None)
        return m, stats, trace

    def compile_counts(m):
        """Jit-trace surface of a finished trial: generator decode
        compiles plus the train engine's traced-variant count (grad,
        grad-acc, apply, scaled-apply caches).  Equal counts between a
        2-step and a 4-step overlapped run == no per-step retrace."""
        out = {}
        for key, model in m.pool.workers[0].models.items():
            eng = model.engine
            if hasattr(eng, "decode_compiles"):
                out["decode_compiles"] = eng.decode_compiles
            if hasattr(eng, "_grad_fns"):
                n = 0
                for gf, gaf in eng._grad_fns.values():
                    n += gf._cache_size() + gaf._cache_size()
                for fn in (eng._apply_fn, eng._scaled_apply_fn):
                    if fn is not None:
                        n += fn._cache_size()
                out["train_traces"] = n
        return out

    failures = []

    m_bar, s_bar, _ = run("barrier", rows_long)
    m_ser, s_ser, tr_ser = run(
        "serial",
        rows_long,
        trace_dir=os.path.join(fileroot, "trace_serial"),
        pipeline_overlap=True,
        overlap_window=1,
    )
    m_ovl, s_ovl, tr_ovl = run(
        "overlap",
        rows_long,
        trace_dir=os.path.join(fileroot, "trace_overlap"),
        pipeline_overlap=True,
        overlap_window=3,
        pipeline_chunk_seqs=2,
    )
    m_short, s_short, _ = run(
        "overlap_short",
        rows_short,
        pipeline_overlap=True,
        overlap_window=3,
        pipeline_chunk_seqs=2,
    )

    # --- window=1 must reproduce the barrier scheduler bit for bit ---
    keys = (
        "actor_train/loss", "actor_train/actor_loss",
        "actor_train/approx_kl", "actor_train/importance_weight",
        "actor_train/grad_norm", "actor_train/task_reward",
    )
    for t, (a, b) in enumerate(zip(s_bar, s_ser)):
        for k in keys:
            if a[k] != b[k]:
                failures.append(
                    f"window=1 diverged from barrier at step {t}: {k} "
                    f"{a[k]} != {b[k]}"
                )
    if not any(s["actor_train/grad_norm"] > 0 for s in s_bar):
        failures.append(
            "degenerate check: every barrier grad_norm is zero"
        )
    pa = m_bar.pool.workers[0].models["actor@0"].engine.get_params()
    pb = m_ser.pool.workers[0].models["actor@0"].engine.get_params()
    diff = max(
        float(
            np.abs(
                np.asarray(x, np.float32) - np.asarray(y, np.float32)
            ).max()
        )
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb))
    )
    if diff != 0.0:
        failures.append(
            f"window=1 final weights differ from barrier by {diff}"
        )
    bit_exact = diff == 0.0 and not any(
        "diverged" in f for f in failures
    )

    # --- steady-state wall-clock: overlap must beat the barrier ---
    # Median, not mean: a single straggler step (a late retrace, a GC
    # pause) must not flip the gate in either direction.
    wall_bar = float(np.median([s["time/step_s"] for s in s_bar[2:]]))
    wall_ovl = float(np.median([s["time/step_s"] for s in s_ovl[2:]]))
    # The hidden verifier latency alone is worth ~25% of the barrier
    # step here, so demand a >= 5% win — far above CI timer noise.
    wall_improved = wall_ovl < 0.95 * wall_bar
    if not wall_improved:
        failures.append(
            f"overlapped steady step ({wall_ovl:.3f}s) is not faster "
            f"than the barrier's ({wall_bar:.3f}s)"
        )
    for s in s_ovl:
        if not np.isfinite(s["actor_train/loss"]) or not np.isfinite(
            s["actor_train/grad_norm"]
        ):
            failures.append("non-finite stats in the overlapped leg")
            break

    # --- trace-level stall attribution (the before/after A/B) ---
    def steady(rows):
        rows = [r for r in rows if r["step"] is not None]
        return [r for r in rows if r["step"] >= 3] or rows

    def idle_s(row):
        # Engine idle during the step: what the overlap exists to
        # shrink.  Sum over stages of (step window - stage busy).
        return sum(
            (row["window_us"] - st["busy_us"]) / 1e6
            for st in row["stages"]
        )

    rows_ser = steady(trace_report.pipeline_rows(tr_ser))
    rows_ovl = steady(trace_report.pipeline_rows(tr_ovl))
    idle_ser = idle_ovl = ofrac_ser = ofrac_ovl = fill_max = float("nan")
    if not rows_ser or not rows_ovl:
        failures.append(
            "pipe:* spans missing from a traced leg "
            f"(serial rows={len(rows_ser)}, overlap rows={len(rows_ovl)})"
        )
    else:
        idle_ser = float(np.median([idle_s(r) for r in rows_ser]))
        idle_ovl = float(np.median([idle_s(r) for r in rows_ovl]))
        ofrac_ser = float(
            np.median([r["overlap_frac"] for r in rows_ser])
        )
        ofrac_ovl = float(
            np.median([r["overlap_frac"] for r in rows_ovl])
        )
        fill_max = max(
            st["fill"] for r in rows_ovl for st in r["stages"]
        )
        if idle_ovl >= idle_ser:
            failures.append(
                f"per-stage idle did not shrink: serial {idle_ser:.3f}s "
                f"-> overlapped {idle_ovl:.3f}s"
            )
        if ofrac_ser > 0.02:
            failures.append(
                f"serial leg reports overlap_frac {ofrac_ser:.3f} > 0"
            )
        if ofrac_ovl < 0.05:
            failures.append(
                f"overlapped leg shows no overlap "
                f"(overlap_frac {ofrac_ovl:.3f})"
            )

    # --- compile flatness: 4 overlapped steps trace exactly what 2 do ---
    cc_long = compile_counts(m_ovl)
    cc_short = compile_counts(m_short)
    compiles_flat = cc_long == cc_short
    if not compiles_flat:
        failures.append(
            f"per-step retrace churn under overlap: 4-step counters "
            f"{cc_long} != 2-step counters {cc_short}"
        )

    for f in failures:
        print(f"FAIL[overlap]: {f}")
    if not failures:
        print(
            f"OK[overlap]: window=1 == barrier exactly over "
            f"{len(s_bar)} steps (max param diff {diff}); steady step "
            f"{wall_bar:.3f}s -> {wall_ovl:.3f}s "
            f"({100 * (1 - wall_ovl / wall_bar):.0f}% faster); stage "
            f"idle {idle_ser:.3f}s -> {idle_ovl:.3f}s; overlap_frac "
            f"{ofrac_ser:.3f} -> {ofrac_ovl:.3f} (max fill "
            f"{fill_max:.2f}); compile counters flat {cc_long}"
        )
        print()
        print("--- trace_report --pipeline, window=1 (before) ---")
        print(trace_report.format_pipeline(tr_ser))
        print("--- trace_report --pipeline, window=3 (after) ---")
        print(trace_report.format_pipeline(tr_ovl))

    if bench_out:
        base = {
            "devices": len(jax.devices()),
            "prompts": len(rows_long),
            "group_n": GROUP_N,
            "max_new_tokens": MAX_NEW_TOKENS,
            "reward_latency_s_per_seq": REWARD_LATENCY_S_PER_SEQ,
            "steps": len(s_bar),
        }
        legs = [
            dict(base, leg="overlap_off", wall_seconds=round(wall_bar, 4)),
            dict(
                base,
                leg="overlap_on",
                wall_seconds=round(wall_ovl, 4),
                pipeline_fill_max=round(fill_max, 4),
                pipeline_idle_seconds=round(idle_ovl, 4),
                overlap_frac=round(ofrac_ovl, 4),
                **cc_long,
            ),
            {
                "leg": "overlap_compare",
                "bit_exact_w1": bool(bit_exact),
                "wall_improved": bool(wall_improved),
                "idle_shrunk": bool(idle_ovl < idle_ser),
                "overlap_frac_positive": bool(ofrac_ovl >= 0.05),
                "compiles_flat": bool(compiles_flat),
            },
        ]
        with open(bench_out, "w") as f:
            for row in legs:
                f.write(json.dumps(row) + "\n")
        print(f"bench rows -> {bench_out}")

    return len(failures)


def _tiny_ppo_cfg(fileroot: str, rows, mfc_timeout_s=None):
    """Deterministic 4-step tiny-PPO config (16 rows / batch 4) with a
    recover save every step — shared by the trainer-chaos legs."""
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.data_api import DatasetAbstraction
    from areal_tpu.api.model_api import (
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.experiments.common import PPOMathConfig
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.master import ExperimentSaveEvalControl

    return PPOMathConfig(
        actor=ModelAbstraction("random", {"config": tiny_config()}),
        dataset=DatasetAbstraction(
            "math_code_prompt",
            {"dataset_builder": lambda: rows, "max_length": 64},
        ),
        reward_interface_args={"id2info": {r["query_id"]: r for r in rows}},
        gconfig=GenerationHyperparameters(n=2, max_new_tokens=8),
        ppo_kwargs={"n_minibatches": 1, "kl_ctl": 0.0},
        optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
        batch_size=4,
        total_train_epochs=1,
        seed=1,
        mfc_timeout_s=mfc_timeout_s,
        worker_heartbeat_s=1.0,
        ctrl=ExperimentSaveEvalControl(ckpt_freq_steps=1),
        fileroot=fileroot,
    )


def _trainer_chaos_victim(fileroot: str) -> int:
    """Hidden helper behind --trainer-chaos-victim: run the tiny PPO
    trial to completion (resuming from any recover checkpoint).  The
    parent process injects AREAL_FAULTS (kill@point=recover_stage) into
    run 1 and asserts on the checkpoint directories each run leaves."""
    from areal_tpu.experiments.common import build_ppo_math, run_experiment
    from tests import fixtures

    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(16, seed=7)
    _, stats = run_experiment(
        build_ppo_math(_tiny_ppo_cfg(fileroot, rows), tok), tokenizer=tok
    )
    print(f"VICTIM_OK steps={len(stats)}")
    return 0


def check_trainer_chaos(fileroot: str) -> int:
    """Crash-safe trainer plane leg (see module docstring, Part 5):
    worker hang mid-train-MFC -> deadline recovery -> bit-exact resume;
    master killed mid-recover-save -> restart from the intact
    checkpoint; torn current -> manifest fallback to .prev."""
    import glob
    import subprocess

    import jax
    import numpy as np

    from areal_tpu.base import faults, metrics, recover, tracer
    from areal_tpu.experiments.common import build_ppo_math, run_experiment
    from areal_tpu.system.master import InProcessPool, MasterWorker
    from areal_tpu.system.transfer import InProcTransfer
    from areal_tpu.system.worker import ModelWorker
    from tests import fixtures

    failures = []
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(16, seed=7)

    def metric_value(name):
        total = 0.0
        for line in metrics.default_registry().expose().splitlines():
            if line.startswith(f"{name} ") or line.startswith(f"{name}{{"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    # ---- Leg 1: worker hangs mid-train-MFC --------------------------
    # Baseline for the A/B: the identical trial with no faults.
    m_base, s_base = run_experiment(
        build_ppo_math(
            _tiny_ppo_cfg(os.path.join(fileroot, "baseline"), rows), tok
        ),
        tokenizer=tok,
    )

    # The in-process pool has no heartbeat lane (a handler thread cannot
    # beat for itself), so the deadline must clear the slowest honest
    # MFC — step 1's cold-compile train step runs several seconds.
    plan = build_ppo_math(
        _tiny_ppo_cfg(
            os.path.join(fileroot, "chaos"), rows, mfc_timeout_s=30.0
        ),
        tok,
    )
    tracer.default_dir(
        plan.fileroot, plan.experiment_name, plan.trial_name
    )
    planes = InProcTransfer.make_group(len(plan.worker_configs))
    # Env-gate the injector around worker construction ONLY: the third
    # train MFC hangs (a stuck host, not a crash), so the master's
    # deadline — not a process exit — must produce the death verdict,
    # and the master's own injector must stay empty.
    os.environ["AREAL_FAULTS"] = "hang@point=mfc_train_step&skip=2&times=1"
    try:
        workers = [
            ModelWorker(wc, tokenizer=tok, transfer=planes[i])
            for i, wc in enumerate(plan.worker_configs)
        ]
    finally:
        del os.environ["AREAL_FAULTS"]
    injectors = [w._faults for w in workers if w._faults is not None]
    pool = InProcessPool(workers, mfc_timeout_s=plan.mfc_timeout_s)
    relaunches = []

    def relauncher(dead):
        # Stand-in for a scheduler relaunch: release the hung injector
        # thread (the stranded to_thread) and revive the pool slot.
        for inj in injectors:
            inj.release()
        for wid in dead:
            pool.revive(wid)
        relaunches.append(sorted(dead))

    before = {
        n: metric_value(n)
        for n in (
            "areal_master_worker_dead_total",
            "areal_master_mfc_timeout_total",
            "areal_master_recoveries_total",
            "areal_ckpt_flips_total",
        )
    }
    master = MasterWorker(
        dfg=plan.dfg,
        pool=pool,
        model_placement=plan.model_placement,
        data_worker_ids=plan.data_worker_ids,
        ctrl=plan.ctrl,
        fileroot=plan.fileroot,
        experiment_name=plan.experiment_name,
        trial_name=plan.trial_name,
        model_groups=plan.model_groups,
        model_replicas=plan.model_replicas,
        difficulty_filter=plan.difficulty_filter,
        rollout_ahead=plan.rollout_ahead,
        max_recoveries=plan.max_recoveries,
        worker_relauncher=relauncher,
    )
    master.load_recover_info()
    t0 = time.monotonic()
    stats = asyncio.run(master.run())
    detect_wall = time.monotonic() - t0

    hangs = sum(i.fired.get("hang", 0) for i in injectors)
    if hangs != 1:
        failures.append(f"expected exactly 1 injected hang, got {hangs}")
    if relaunches != [[0]]:
        failures.append(
            f"expected one relaunch of worker 0, got {relaunches}"
        )
    if master._recoveries != 1:
        failures.append(
            f"expected 1 recovery, got {master._recoveries}"
        )
    for name, want in (
        ("areal_master_worker_dead_total", 1),
        ("areal_master_mfc_timeout_total", 1),
        ("areal_master_recoveries_total", 1),
    ):
        delta = metric_value(name) - before[name]
        if delta != want:
            failures.append(f"{name} moved by {delta}, expected {want}")
    flips = metric_value("areal_ckpt_flips_total") - before[
        "areal_ckpt_flips_total"
    ]
    if flips < 4:
        failures.append(
            f"expected >= 4 checkpoint flips (one per step), got {flips}"
        )
    if len(stats) != len(s_base):
        failures.append(
            f"chaos run produced {len(stats)} steps, baseline "
            f"{len(s_base)}"
        )
    if master.step_info.global_step != len(s_base):
        failures.append(
            f"final global_step {master.step_info.global_step} != "
            f"{len(s_base)}"
        )
    # Bit-exact resume: rollback restores weights, optimizer, model
    # versions (sampling seeds derive from them), and data cursors from
    # the end-of-step-2 checkpoint, so the replayed steps 3-4 — and the
    # final weights — must match the fault-free trial exactly.
    keys = (
        "actor_train/loss", "actor_train/actor_loss",
        "actor_train/approx_kl", "actor_train/importance_weight",
        "actor_train/grad_norm", "actor_train/task_reward",
    )
    for t, (a, b) in enumerate(zip(s_base, stats)):
        for k in keys:
            if a[k] != b[k]:
                failures.append(
                    f"chaos run diverged from baseline at step {t}: "
                    f"{k} {b[k]} != {a[k]}"
                )
    pa = m_base.pool.workers[0].models["actor@0"].engine.get_params()
    pb = pool.workers[0].models["actor@0"].engine.get_params()
    diff = max(
        float(
            np.abs(
                np.asarray(x, np.float32) - np.asarray(y, np.float32)
            ).max()
        )
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb))
    )
    if diff != 0.0:
        failures.append(
            f"post-recovery final weights differ from baseline by {diff}"
        )

    # ---- Leg 2: master killed mid-recover-save ----------------------
    vic_root = os.path.join(fileroot, "victim")
    cmd = [
        sys.executable, os.path.abspath(__file__),
        "--trainer-chaos-victim", vic_root,
    ]
    env = dict(os.environ)
    # First recover-save commits; the second is killed after staging,
    # before the flip.
    env["AREAL_FAULTS"] = "kill@point=recover_stage&skip=1&times=1"
    r1 = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=600
    )
    if r1.returncode != 42:
        failures.append(
            f"victim run 1: expected exit 42 (kill at recover_stage), "
            f"got {r1.returncode}; stderr tail: {r1.stderr[-800:]}"
        )
    bases = sorted(
        glob.glob(
            os.path.join(
                vic_root, "checkpoints", "*", "*", "*",
                "recover_checkpoint",
            )
        )
    )
    if not bases:
        failures.append("victim run 1 left no committed recover_checkpoint")
    for base in bases:
        m = recover.validate_manifest(base)
        if m is None or m["step"] != 1:
            failures.append(
                f"{base}: expected intact manifest at step 1 after the "
                f"mid-save kill, got {m and m['step']}"
            )
        staged = recover.stage_dir(base, 2)
        if not os.path.isdir(staged):
            failures.append(
                f"kill at recover_stage left no staged dir {staged}"
            )

    r2 = subprocess.run(
        cmd, env=dict(os.environ), capture_output=True, text=True,
        timeout=600,
    )
    if r2.returncode != 0:
        failures.append(
            f"victim run 2 (restart after kill): expected exit 0, got "
            f"{r2.returncode}; stderr tail: {r2.stderr[-800:]}"
        )
    roots = glob.glob(os.path.join(vic_root, "recover", "*", "*"))
    infos = [recover.load(r) for r in roots]
    if not infos or infos[0].last_step_info.global_step != 4:
        failures.append(
            f"victim run 2: expected recover_info at step 4, got "
            f"{[i.last_step_info.global_step for i in infos]}"
        )
    for base in bases:
        m = recover.validate_manifest(base)
        if m is None or m["step"] != 4:
            failures.append(
                f"{base}: expected manifest at step 4 after the resumed "
                f"run, got {m and m['step']}"
            )
        stale = glob.glob(base + recover.STAGE_PREFIX + "*")
        if stale:
            failures.append(f"stale stages left behind: {stale}")

    # ---- Leg 3: torn current checkpoint -> .prev fallback -----------
    for base in bases:
        m = recover.validate_manifest(base)
        if not m:
            continue
        torn = os.path.join(base, m["files"][0]["name"])
        with open(torn, "wb") as f:
            f.write(b"torn")
        if recover.validate_manifest(base) is not None:
            failures.append(f"{base}: torn file passed validation")
        if recover.latest_valid_checkpoint(base) != (
            base + recover.PREV_SUFFIX
        ):
            failures.append(
                f"{base}: torn current did not fall back to .prev"
            )
    r3 = subprocess.run(
        cmd, env=dict(os.environ), capture_output=True, text=True,
        timeout=600,
    )
    if r3.returncode != 0:
        failures.append(
            f"victim run 3 (torn current): expected exit 0 restoring "
            f"from .prev, got {r3.returncode}; stderr tail: "
            f"{r3.stderr[-800:]}"
        )

    for f in failures:
        print(f"FAIL[trainer-chaos]: {f}")
    if not failures:
        print(
            f"OK[trainer-chaos]: hang detected and recovered in-run "
            f"(1 recovery, wall {detect_wall:.1f}s, {flips:.0f} ckpt "
            f"flips), resumed bit-exact vs baseline over {len(stats)} "
            f"steps (max param diff {diff}); mid-save kill (exit 42) "
            f"left step-1 checkpoint intact and the restart finished at "
            f"step 4; torn current fell back to .prev and restored"
        )
    return len(failures)


def check_nan_chaos(fileroot: str, bench_out: str = None) -> int:
    """Numerical-integrity guard plane leg (module docstring, Part 6):
    NaN grads -> quarantine with zero weight change; a quarantine
    streak -> checkpoint rollback + bit-exact replay; a corrupted
    weight push -> checksum rejection, retry, token-identical decode."""
    import jax
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import (
        FinetuneSpec,
        GenerationHyperparameters,
        OptimizerConfig,
    )
    from areal_tpu.base import integrity, metrics, tracer
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.engines.train import TrainEngine
    from areal_tpu.experiments.common import build_ppo_math, run_experiment
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.gen_server import GenerationServer
    from areal_tpu.system.master import InProcessPool, MasterWorker
    from areal_tpu.system.transfer import InProcTransfer
    from areal_tpu.system.worker import ModelWorker
    from tests import fixtures

    failures = []

    def metric_value(name):
        total = 0.0
        for line in metrics.default_registry().expose().splitlines():
            if line.startswith(f"{name} ") or line.startswith(f"{name}{{"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    def host_leaves(tree):
        # copy=True: the guarded apply donates and in-place reuses its
        # input buffers; a zero-copy view captured "before" a step would
        # silently show the "after" values.
        return [np.array(x, copy=True) for x in jax.tree.leaves(tree)]

    def max_diff(a, b):
        return max(
            float(
                np.abs(
                    np.asarray(x, np.float32) - np.asarray(y, np.float32)
                ).max()
            )
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    # ---- Proof 1: NaN grads -> quarantine, zero weight change -------
    from areal_tpu.ops import functional as F

    cfg = tiny_config()
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    os.environ["AREAL_FAULTS"] = "nan@point=train_grads&times=1"
    try:
        eng = TrainEngine(
            cfg, params=tfm.init_params(cfg, jax.random.PRNGKey(0)),
            mesh=mesh,
            optimizer_config=OptimizerConfig(
                lr=1e-2, warmup_steps_proportion=0.0
            ),
            ftspec=FinetuneSpec(1, 8, 8),
        )
    finally:
        del os.environ["AREAL_FAULTS"]
    rng = np.random.default_rng(0)
    sample = fixtures.random_sample(
        rng, ids=[f"s{i}" for i in range(6)], keys=("packed_input_ids",),
        max_len=20,
    )
    masks = []
    for sl in sample.seqlens["packed_input_ids"]:
        m = np.zeros(sl[0], dtype=bool)
        m[:2] = True
        masks.append(m)
    sample.update_(
        SequenceSample(
            keys={"prompt_mask"},
            ids=sample.ids,
            seqlens={
                "prompt_mask": [
                    list(s) for s in sample.seqlens["packed_input_ids"]
                ]
            },
            data={"prompt_mask": np.concatenate(masks)},
        )
    )
    sft_kw = dict(
        loss_fn=F.sft_loss, loss_weight_fn=F.sft_label_count,
        token_key="packed_input_ids", extra_keys=("prompt_mask",),
    )
    before_p = host_leaves(eng.get_params())
    m_anom0 = metric_value("areal_train_anomaly_total")
    out = eng.train_batch(sample, MicroBatchSpec(), **sft_kw)
    quarantine_zero_weight_change = (
        out["quarantined"] == 1.0
        and int(out["anomaly_verdict"]) & integrity.NONFINITE
        and all(
            np.array_equal(a, b)
            for a, b in zip(before_p, host_leaves(eng.get_params()))
        )
    )
    if not quarantine_zero_weight_change:
        failures.append(
            f"NaN step not quarantined with zero weight change: {out}"
        )
    if metric_value("areal_train_anomaly_total") - m_anom0 != 1:
        failures.append("anomaly counter did not move by 1 on the NaN step")
    # Fault exhausted (times=1): the next step must train normally...
    out2 = eng.train_batch(sample, MicroBatchSpec(), **sft_kw)
    if out2["quarantined"] != 0.0 or not any(
        not np.array_equal(a, b)
        for a, b in zip(before_p, host_leaves(eng.get_params()))
    ):
        failures.append("clean step after the NaN fault did not train")
    # ...through the SAME guarded-apply trace, with exactly one batched
    # host sync per train call.
    if eng._apply_fn._cache_size() != 1:
        failures.append(
            f"guarded apply retraced: cache size "
            f"{eng._apply_fn._cache_size()} != 1"
        )
    if eng.host_transfers != 2:
        failures.append(
            f"expected 1 host sync per train call (2 total), got "
            f"{eng.host_transfers}"
        )

    # ---- Proof 2: quarantine streak -> rollback, bit-exact replay ---
    tok = fixtures.make_tokenizer()
    rows = fixtures.build_math_rows(16, seed=7)
    m_base, s_base = run_experiment(
        build_ppo_math(
            _tiny_ppo_cfg(os.path.join(fileroot, "baseline"), rows), tok
        ),
        tokenizer=tok,
    )

    plan = build_ppo_math(
        _tiny_ppo_cfg(os.path.join(fileroot, "chaos"), rows), tok
    )
    tracer.default_dir(
        plan.fileroot, plan.experiment_name, plan.trial_name
    )
    planes = InProcTransfer.make_group(len(plan.worker_configs))
    # Env-gate the injector around worker construction ONLY: the actor
    # train engine NaN-poisons its 3rd and 4th accumulated grad sums
    # (steps 3-4), tripping the 2-step quarantine streak.
    os.environ["AREAL_FAULTS"] = "nan@point=train_grads&skip=2&times=2"
    try:
        workers = [
            ModelWorker(wc, tokenizer=tok, transfer=planes[i])
            for i, wc in enumerate(plan.worker_configs)
        ]
    finally:
        del os.environ["AREAL_FAULTS"]
    pool = InProcessPool(workers)
    before = {
        n: metric_value(n)
        for n in (
            "areal_master_quarantined_steps_total",
            "areal_master_quarantine_rollbacks_total",
            "areal_master_recoveries_total",
        )
    }
    master = MasterWorker(
        dfg=plan.dfg,
        pool=pool,
        model_placement=plan.model_placement,
        data_worker_ids=plan.data_worker_ids,
        ctrl=plan.ctrl,
        fileroot=plan.fileroot,
        experiment_name=plan.experiment_name,
        trial_name=plan.trial_name,
        model_groups=plan.model_groups,
        model_replicas=plan.model_replicas,
        difficulty_filter=plan.difficulty_filter,
        rollout_ahead=plan.rollout_ahead,
        max_recoveries=plan.max_recoveries,
        max_consecutive_quarantines=2,
    )
    master.load_recover_info()
    stats = asyncio.run(master.run())

    def is_quarantined(s):
        return any(
            k.rsplit("/", 1)[-1] == "quarantined" and v > 0
            for k, v in s.items()
        )

    quarantined = [s for s in stats if is_quarantined(s)]
    clean = [s for s in stats if not is_quarantined(s)]
    if len(quarantined) != 2:
        failures.append(
            f"expected exactly 2 quarantined steps, got {len(quarantined)}"
        )
    for name, want in (
        ("areal_master_quarantined_steps_total", 2),
        ("areal_master_quarantine_rollbacks_total", 1),
        ("areal_master_recoveries_total", 1),
    ):
        delta = metric_value(name) - before[name]
        if delta != want:
            failures.append(f"{name} moved by {delta}, expected {want}")
    if len(master._quarantine_ledger) < 2:
        failures.append(
            f"quarantine ledger holds {len(master._quarantine_ledger)} "
            "entries, expected >= 2"
        )
    if master.step_info.global_step != len(s_base):
        failures.append(
            f"final global_step {master.step_info.global_step} != "
            f"{len(s_base)}"
        )
    # The rollback restores the end-of-step-2 checkpoint (quarantined
    # steps never checkpoint), so the replayed steps 3-4 — and the
    # final weights — must match the fault-free trial bit for bit.
    rollback_bit_exact = len(clean) == len(s_base)
    keys = (
        "actor_train/loss", "actor_train/actor_loss",
        "actor_train/approx_kl", "actor_train/importance_weight",
        "actor_train/grad_norm", "actor_train/task_reward",
    )
    for t, (a, b) in enumerate(zip(s_base, clean)):
        for k in keys:
            if a[k] != b[k]:
                rollback_bit_exact = False
                failures.append(
                    f"replay diverged from baseline at step {t}: "
                    f"{k} {b[k]} != {a[k]}"
                )
    diff = max_diff(
        m_base.pool.workers[0].models["actor@0"].engine.get_params(),
        pool.workers[0].models["actor@0"].engine.get_params(),
    )
    if diff != 0.0:
        rollback_bit_exact = False
        failures.append(
            f"post-rollback final weights differ from baseline by {diff}"
        )
    if not rollback_bit_exact and len(clean) != len(s_base):
        failures.append(
            f"chaos run produced {len(clean)} clean steps, baseline "
            f"{len(s_base)}"
        )
    # Guarded apply adds no retrace: quarantine + rollback must leave
    # the trial's jit trace surface identical to the clean baseline's.
    def train_traces(m):
        n = 0
        for model in m.pool.workers[0].models.values():
            e = model.engine
            if hasattr(e, "_grad_fns"):
                for gf, gaf in e._grad_fns.values():
                    n += gf._cache_size() + gaf._cache_size()
                for fn in (e._apply_fn, e._scaled_apply_fn):
                    if fn is not None:
                        n += fn._cache_size()
        return n

    tr_base, tr_chaos = train_traces(m_base), train_traces(master)
    compiles_flat = tr_base == tr_chaos
    if not compiles_flat:
        failures.append(
            f"quarantine/rollback changed the jit trace surface: "
            f"{tr_chaos} traces vs baseline {tr_base}"
        )

    # ---- Proof 3: corrupted weight push -> rejected, retried --------
    gen_params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    os.environ["AREAL_FAULTS"] = "corrupt_push@point=weight_push&times=1"
    try:
        victim = GenerationServer(
            GeneratorEngine(
                cfg, gen_params, mesh, eos_token_id=cfg.vocab_size + 7
            )
        )
    finally:
        del os.environ["AREAL_FAULTS"]
    control = GenerationServer(
        GeneratorEngine(
            cfg, gen_params, mesh, eos_token_id=cfg.vocab_size + 7
        )
    )
    try:
        new_params = tfm.init_params(cfg, jax.random.PRNGKey(42))
        cs = integrity.params_checksum(new_params)
        m_rej0 = metric_value("areal_gen_weight_push_rejected_total")
        v0 = victim.version
        corrupt_push_rejected = False
        try:
            victim.update_weights_inmem(new_params, checksum=cs)
        except integrity.WeightChecksumError:
            corrupt_push_rejected = True
        if not corrupt_push_rejected:
            failures.append("corrupted push was NOT rejected by checksum")
        if metric_value("areal_gen_weight_push_rejected_total") - m_rej0 != 1:
            failures.append("push-rejected counter did not move by 1")
        if victim.version != v0:
            failures.append(
                "rejected push still bumped the serving version"
            )
        # The pusher retries; the fault is exhausted, the push lands.
        victim.update_weights_inmem(new_params, checksum=cs)
        control.update_weights_inmem(new_params, checksum=cs)
        prompts = SequenceSample(
            keys={"packed_prompts"},
            ids=["p0", "p1"],
            seqlens={"packed_prompts": [[6], [9]]},
            data={
                "packed_prompts": rng.integers(
                    8, cfg.vocab_size, size=15
                ).astype(np.int32)
            },
        )
        g = GenerationHyperparameters(n=1, max_new_tokens=16, greedy=True)
        out_v = victim.engine.generate(prompts, MicroBatchSpec(), g)
        out_c = control.engine.generate(prompts, MicroBatchSpec(), g)
        if not np.array_equal(
            np.asarray(out_v.data["packed_input_ids"]),
            np.asarray(out_c.data["packed_input_ids"]),
        ):
            failures.append(
                "post-retry greedy decode differs from the control server"
            )
    finally:
        victim.close()
        control.close()

    if bench_out:
        import json

        legs = [
            {
                "leg": "nan_chaos",
                "devices": len(jax.devices()),
                "steps": len(s_base),
                "quarantined_steps": len(quarantined),
                "quarantine_rollbacks": 1,
                "train_traces": tr_chaos,
            },
            {
                "leg": "nan_chaos_compare",
                "quarantine_zero_weight_change": bool(
                    quarantine_zero_weight_change
                ),
                "rollback_bit_exact": bool(rollback_bit_exact),
                "corrupt_push_rejected": bool(corrupt_push_rejected),
                "compiles_flat": bool(compiles_flat),
            },
        ]
        with open(bench_out, "w") as f:
            for row in legs:
                f.write(json.dumps(row) + "\n")
        print(f"bench rows -> {bench_out}")

    for f in failures:
        print(f"FAIL[nan-chaos]: {f}")
    if not failures:
        print(
            f"OK[nan-chaos]: NaN grad quarantined with zero weight "
            f"change (1 host sync/step, 1 apply trace); 2-step NaN "
            f"streak rolled back and replayed bit-exact vs baseline "
            f"over {len(clean)} steps (max param diff {diff}, trace "
            f"surface flat at {tr_chaos}); corrupted push rejected by "
            f"checksum, retry landed, greedy decode token-identical"
        )
    return len(failures)


def check_agents(n_episodes: int = 3) -> int:
    """Agent-serving runtime leg (`--agents`): multi-turn tool-use
    episodes on persistent KV state, driven end to end on CPU.

    The tiny random model has no chat template, so the tool-call stop
    sequence is a token-space convention (every even token id stops a
    turn) — greedy decode then yields deterministic turn boundaries
    without a trained model.  Verified:

      - N 3-turn calculator episodes: after turn 1, every turn prefills
        ONLY the tool observation (zero full-prompt re-prefills), all
        turns stay on one slot, and the engine compiles its decode
        program exactly once across every episode;
      - greedy identity: each assistant turn is token-identical to a
        single-shot replay of its transcript prefix on a fresh engine;
      - a code-RL episode: the model's tool call runs real Python in the
        OS sandbox mid-episode, and the episode is then graded
        end-to-end through the reward fabric's sandboxed code backend;
      - a mid-episode in-memory weight push: the episode's slot parks at
        a chunk boundary, the swap lands, and the episode resumes on its
        KV pages and completes (never lost, never re-admitted);
      - the episode metrics move and drain (turns counted, active gauge
        back to zero, tool latency histogram populated).
    """
    import threading as _threading

    import jax
    import numpy as np

    from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.base import metrics
    from areal_tpu.base.topology import ParallelConfig, make_mesh
    from areal_tpu.engines.generator import GeneratorEngine
    from areal_tpu.interfaces.reward_service import grade_item
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system.episode import (
        EngineEpisodeClient,
        EpisodeController,
        ToolCall,
        ToolExecutor,
    )

    failures = []
    cfg = tiny_config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh(ParallelConfig.from_str("d1"), jax.devices()[:1])
    eos = cfg.vocab_size + 7  # unreachable: turns end on stop sequences

    def mk_engine(p):
        return GeneratorEngine(
            cfg, p, mesh, eos_token_id=eos, kv_page_size=8,
            prefill_chunk_tokens=4, max_decode_batch=2,
        )

    def sample_of(toks):
        arr = np.asarray(toks, np.int32)
        return SequenceSample(
            keys={"packed_prompts"}, ids=["p0"],
            seqlens={"packed_prompts": [[len(arr)]]},
            data={"packed_prompts": arr},
        )

    def metric_value(name):
        total = 0.0
        for line in metrics.default_registry().expose().splitlines():
            if line.startswith(f"{name} ") or line.startswith(f"{name}{{"):
                total += float(line.rsplit(" ", 1)[1])
        return total

    rng = np.random.default_rng(17)
    prompt = [int(t) for t in rng.integers(8, cfg.vocab_size, size=12)]

    # The random tiny model has no chat template, so "tool-call stop
    # sequence" is a token-space convention: every EVEN token is a
    # single-token stop.  Greedy decode over any transcript then hits a
    # stop within a couple of tokens — deterministic turn boundaries
    # without a trained model (later turns are continuations the probe
    # trick of a fixed pair can't cover).
    g = GenerationHyperparameters(
        n=1, max_new_tokens=24, greedy=True,
        stop=tuple((t,) for t in range(0, cfg.vocab_size, 2)),
    )

    class RecordingClient(EngineEpisodeClient):
        """Keeps every raw turn dict so the leg can assert prefill
        accounting the controller's Turn records don't carry."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.outs = []

        def _drive(self, fn, ep_id):
            turn = super()._drive(fn, ep_id)
            self.outs.append(dict(turn))
            return turn

    # Token-level tool-call convention for the random model: any stop
    # turn "calls" the calculator on operands read off its last tokens;
    # observations are digits re-encoded into the vocab.
    def parse_calc(toks):
        a, b = (list(toks) * 2)[-2:]  # tolerate 1-token turns
        return ToolCall("calculator", f"{a % 9} + {b % 9}")

    def encode_obs(call, text, ok):
        return [8 + (ord(c) % 16) for c in text][:6] or [8]

    tools = ToolExecutor(timeout_s=10.0)

    # ---- Leg 1: calculator episodes + prefill accounting ------------
    eng = mk_engine(params)
    turns0 = metric_value("areal_episode_turns_total")
    done0 = metric_value("areal_episode_completed_total")
    episodes = []
    clients = []
    for i in range(n_episodes):
        client = RecordingClient(eng, g, token_budget=0, seed=0)
        ctl = EpisodeController(
            client, tools, parse_calc, encode_obs, max_turns=3
        )
        ep = ctl.run_episode(f"calc-{i}", prompt)
        episodes.append(ep)
        clients.append(client)

    for ep, client in zip(episodes, clients):
        if ep.stop_reason != "max_turns" or ep.assistant_turns != 3:
            failures.append(
                f"{ep.episode_id}: expected 3 assistant turns ending "
                f"max_turns, got {ep.assistant_turns} ({ep.stop_reason})"
            )
            continue
        outs = client.outs
        # Later episodes share the first one's prompt pages via the
        # published prefix cache, so turn 1 is shared + tail prefill.
        covered = (outs[0]["prefill_tokens"]
                   + outs[0]["shared_prefix_tokens"])
        if covered != len(prompt):
            failures.append(
                f"{ep.episode_id}: turn 1 covered {covered} tokens "
                f"(prefill {outs[0]['prefill_tokens']} + shared "
                f"{outs[0]['shared_prefix_tokens']}), want {len(prompt)}"
            )
        tool_turns = [t for t in ep.turns if t.role == "tool"]
        for k, (o, tt) in enumerate(zip(outs[1:], tool_turns)):
            # The tentpole property: zero full re-prefills after turn 1
            # — each continuation prefills exactly its observation.
            if o["prefill_tokens"] != len(tt.tokens):
                failures.append(
                    f"{ep.episode_id} turn {k + 2}: prefilled "
                    f"{o['prefill_tokens']} tokens, want observation "
                    f"size {len(tt.tokens)}"
                )
        if len({o["slot"] for o in outs}) != 1:
            failures.append(
                f"{ep.episode_id}: turns hopped slots "
                f"{[o['slot'] for o in outs]}"
            )
    if eng.decode_compiles != 1:
        failures.append(
            f"decode compiled {eng.decode_compiles} times across "
            f"{n_episodes} episodes, want exactly 1"
        )
    if eng.episode_prefix_hits < n_episodes - 1:
        failures.append(
            f"same-prompt episodes missed the prefix cache "
            f"(hits={eng.episode_prefix_hits}, want >= {n_episodes - 1})"
        )
    # Ragged serving-path accounting: every episode admission and every
    # tool-observation continuation is a ragged q_len row inside the
    # serving chunk — the legacy standalone-prefill program must never
    # fire in the turn loop, and the packed stream must never compute a
    # misassigned live lane (dead lanes are eliminated, not masked).
    if eng.prefill_dispatches != 0:
        failures.append(
            f"episode turn loop dispatched {eng.prefill_dispatches} "
            f"legacy admit prefill(s), want 0: observations must ride "
            f"the ragged serving path"
        )
    if eng.dead_live_lanes != 0:
        failures.append(
            f"packed stream computed {eng.dead_live_lanes} misassigned "
            f"live lane(s), want exactly 0"
        )
    if not (eng.lanes_live > 0
            and eng.lanes_live + eng.lanes_slack == eng.lanes_dispatched):
        failures.append(
            f"lane counters do not partition the dispatched stream: "
            f"live={eng.lanes_live} slack={eng.lanes_slack} "
            f"dispatched={eng.lanes_dispatched}"
        )

    # ---- Leg 2: greedy identity vs single-shot replay ---------------
    # Every assistant turn must be token-identical to a fresh engine
    # decoding the same transcript prefix in one shot: proof the parked
    # KV pages hold exactly the state a cold prefill would build.
    ep0 = episodes[0] if episodes else None
    if ep0 is not None and not failures:
        prefix = list(ep0.prompt_ids)
        for t in ep0.turns:
            if t.role == "assistant":
                replay_eng = mk_engine(params)
                r = replay_eng.generate(
                    sample_of(prefix), MicroBatchSpec(), g, inflight=True
                )
                replayed = np.asarray(
                    r.data["packed_input_ids"]
                ).tolist()[len(prefix):]
                if replayed != t.tokens:
                    failures.append(
                        f"greedy identity broke at turn {t.index}: "
                        f"episode {t.tokens} vs replay {replayed}"
                    )
                    break
            prefix.extend(t.tokens)

    # ---- Leg 3: code-RL episode graded in the sandbox ---------------
    # The "agent" writes one canonical program; the tool executes it in
    # the OS sandbox mid-episode, and the reward fabric then grades the
    # same program end-to-end through the sandboxed code backend.
    code_text = "```python\nprint(int(input()) ** 2)\n```"

    def parse_code(toks):
        return ToolCall("python_exec", "print(3 ** 2)")

    code_client = RecordingClient(eng, g)
    code_ep = EpisodeController(
        code_client, tools, parse_code, encode_obs, max_turns=2
    ).run_episode("code-0", prompt)
    code_tool = [t for t in code_ep.turns if t.role == "tool"]
    if not code_tool or not code_tool[0].tool_ok:
        failures.append(
            f"code episode tool run failed: "
            f"{[(t.tool_name, t.tool_ok) for t in code_tool]}"
        )
    code_ep.reward = float(grade_item({
        "task": "code",
        "text": code_text,
        "payload": {
            "input_output": {"inputs": ["3\n"], "outputs": ["9"]},
            "timeout_s": 8.0,
        },
    }))
    if code_ep.reward != 1.0:
        failures.append(
            "sandboxed code grading rejected a correct solution"
        )
    traj = code_ep.to_trajectory(qid="code-0")
    if len(traj.output_ids[0]) != len(traj.output_logprobs[0]):
        failures.append("episode trajectory logprob/token length mismatch")

    # ---- Leg 4: mid-episode in-memory weight push -------------------
    # The pusher waits for the episode to go live, interrupts the
    # engine (the slot parks at a chunk boundary), swaps the weights,
    # and clears the interrupt; the client's park loop must resume the
    # SAME episode to completion — no SlotGone, no re-admission.
    params2 = jax.block_until_ready(
        tfm.init_params(cfg, jax.random.PRNGKey(101))
    )
    push_state = {"parked": False}

    def pusher():
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if eng.episode_stats()["active"] > 0:
                break
            time.sleep(0.002)
        eng.interrupt()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if eng.episode_stats()["parked_mid_turn"] >= 1:
                push_state["parked"] = True
                break
            time.sleep(0.002)
        eng.set_params(params2)
        eng.clear_interrupt()

    push_client = RecordingClient(eng, g)
    push_ctl = EpisodeController(
        push_client, tools, parse_calc, encode_obs, max_turns=4
    )
    th = _threading.Thread(target=pusher)
    th.start()
    push_ep = push_ctl.run_episode("push-0", prompt)
    th.join(timeout=120)
    if th.is_alive():
        failures.append("weight pusher never finished")
    if not push_state["parked"]:
        failures.append(
            "the weight push never parked the episode mid-turn"
        )
    if push_ep.status != "done" or push_ep.slot_lost != 0:
        failures.append(
            f"pushed-through episode not cleanly finished: "
            f"status={push_ep.status} slot_lost={push_ep.slot_lost}"
        )
    if len({o["slot"] for o in push_client.outs}) != 1:
        failures.append("weight push moved the episode off its slot")

    # ---- metrics drain ----------------------------------------------
    n_eps = n_episodes + 2  # calculator + code + push
    turns_delta = metric_value("areal_episode_turns_total") - turns0
    if turns_delta < n_episodes * 3 + 2:
        failures.append(
            f"areal_episode_turns_total moved by {turns_delta}, want "
            f">= {n_episodes * 3 + 2}"
        )
    if metric_value("areal_episode_completed_total") - done0 != n_eps:
        failures.append("areal_episode_completed_total did not track")
    if metric_value("areal_episode_active") != 0:
        failures.append("areal_episode_active did not drain to zero")
    if metric_value("areal_episode_tool_seconds_count") <= 0:
        failures.append("tool latency histogram never observed")

    for f in failures:
        print(f"FAIL[agents]: {f}")
    if not failures:
        stats = eng.episode_stats()
        print(
            f"OK[agents]: {n_episodes} calculator episodes (3 turns, "
            f"observation-only prefills, decode_compiles="
            f"{eng.decode_compiles}), greedy identity vs single-shot "
            f"replay, sandboxed code reward graded "
            f"{code_ep.reward}, mid-episode weight push parked+resumed "
            f"on one slot; engine episode stats {stats}"
        )
    return len(failures)


def main() -> int:
    p = argparse.ArgumentParser(prog="check_async")
    p.add_argument("--prompts", type=int, default=24)
    p.add_argument("--versions", type=int, default=3,
                   help="in-memory weight pushes in the serving check")
    p.add_argument("--dir", default=None,
                   help="fileroot for the trainer check (default: tempdir)")
    p.add_argument("--chaos", action="store_true",
                   help="run ONLY the elastic-fleet chaos leg (3 servers, "
                        "one killed mid-decode via AREAL_FAULTS)")
    p.add_argument("--overlap", action="store_true",
                   help="run ONLY the pipeline-overlapped PPO leg "
                        "(barrier vs streamed executor A/B)")
    p.add_argument("--bench-out", default=None,
                   help="with --overlap / --nan-chaos: also write the "
                        "bench JSONL (bench_overlap_cpu8_<UTC>.json / "
                        "bench_nanchaos_cpu8_<UTC>.json) for "
                        "check_regression.py")
    p.add_argument("--trainer-chaos", action="store_true",
                   help="run ONLY the crash-safe trainer plane leg "
                        "(worker hang mid-MFC -> deadline recovery; "
                        "master killed mid-recover-save -> manifest "
                        "fallback)")
    p.add_argument("--trainer-chaos-victim", metavar="DIR", default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--nan-chaos", action="store_true",
                   help="run ONLY the numerical-integrity guard plane "
                        "leg (NaN grads -> quarantine; streak -> "
                        "rollback + bit-exact replay; corrupt push -> "
                        "checksum rejection)")
    p.add_argument("--agents", action="store_true",
                   help="run ONLY the agent-serving runtime leg "
                        "(multi-turn tool-use episodes on persistent "
                        "KV slots, sandboxed code reward, mid-episode "
                        "weight push)")
    p.add_argument("--push-chaos", action="store_true",
                   help="run ONLY the parameter-distribution-fabric "
                        "chaos leg (5 servers, broadcast-tree push, "
                        "first relay killed mid-broadcast; zero torn "
                        "versions + v-1 staleness bound asserted)")
    p.add_argument("--verifier-chaos", action="store_true",
                   help="run ONLY the verifier-service-fleet chaos leg "
                        "(3 graders, one killed mid-grade; zero lost "
                        "grades, redispatch, breaker cycle, lane "
                        "refill; mixed-task mixture smoke with "
                        "per-task reward curves + lineage; "
                        "slow-verifier A/B)")
    args = p.parse_args()

    if args.trainer_chaos_victim:
        return _trainer_chaos_victim(args.trainer_chaos_victim)

    if args.trainer_chaos:
        fileroot = args.dir or tempfile.mkdtemp(
            prefix="areal_tpu_trainer_chaos_"
        )
        n_fail = check_trainer_chaos(fileroot)
        if n_fail:
            print(f"FAIL: {n_fail} trainer-chaos check(s) failed")
            return 1
        print("OK: crash-safe trainer plane survived the injected faults")
        return 0

    if args.nan_chaos:
        fileroot = args.dir or tempfile.mkdtemp(
            prefix="areal_tpu_nan_chaos_"
        )
        n_fail = check_nan_chaos(fileroot, bench_out=args.bench_out)
        if n_fail:
            print(f"FAIL: {n_fail} nan-chaos check(s) failed")
            return 1
        print("OK: numerical-integrity guard plane survived the "
              "injected corruption")
        return 0

    if args.agents:
        n_fail = check_agents()
        if n_fail:
            print(f"FAIL: {n_fail} agent check(s) failed")
            return 1
        print("OK: agent-serving runtime verified end to end")
        return 0

    if args.verifier_chaos:
        n_fail = check_verifier_chaos()
        if n_fail:
            print(f"FAIL: {n_fail} verifier-chaos check(s) failed")
            return 1
        print("OK: verifier service fleet survived the injected kill")
        return 0

    if args.push_chaos:
        n_fail = check_push_chaos()
        if n_fail:
            print(f"FAIL: {n_fail} push-chaos check(s) failed")
            return 1
        print("OK: parameter distribution fabric survived the killed "
              "relay")
        return 0

    if args.chaos:
        n_fail = check_chaos()
        if n_fail:
            print(f"FAIL: {n_fail} chaos check(s) failed")
            return 1
        print("OK: elastic rollout fleet survived the injected kill")
        return 0

    if args.overlap:
        fileroot = args.dir or tempfile.mkdtemp(
            prefix="areal_tpu_overlap_check_"
        )
        n_fail = check_overlap(fileroot, bench_out=args.bench_out)
        if n_fail:
            print(f"FAIL: {n_fail} overlap check(s) failed")
            return 1
        print("OK: pipeline-overlapped PPO verified against the barrier")
        return 0

    fileroot = args.dir or tempfile.mkdtemp(prefix="areal_tpu_async_check_")
    n_fail = check_serving_plane(args.prompts, args.versions)
    n_fail += check_trainer_plane(fileroot)
    if n_fail:
        print(f"FAIL: {n_fail} check(s) failed")
        return 1
    print("OK: asynchronous RL loop verified end to end")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
