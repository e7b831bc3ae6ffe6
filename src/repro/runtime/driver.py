"""Fault-tolerant training driver.

Wraps the pure train_step with the operational machinery a 1000-node run
needs:

  - checkpoint/restart: periodic async saves; on failure, restore the
    latest checkpoint and replay the data stream (deterministic per-step
    synthetic pipeline makes replay exact);
  - bounded retry with backoff: transient step failures (preemption,
    flaky interconnect - injected via ``fault_hook`` in tests) retry from
    the last checkpoint up to ``max_restarts``;
  - straggler mitigation: per-step wall time is tracked against a rolling
    median; steps slower than ``straggler_factor`` x median are counted and
    surfaced (on real multi-host deployments this signal drives backup-task
    scheduling / hot-spare swap, here it drives the metric + log path);
  - watchdog: a heartbeat thread flags hangs (no step completion within
    ``hang_timeout``) so an external supervisor can kill/restart the job;
  - elastic restart: restores onto whatever mesh is active (checkpoints
    store full arrays; see ckpt.manager);
  - elastic replan (DESIGN.md §10): a ``ClusterChange`` raised out of the
    step loop (by ``runtime.faults.FaultInjector`` or a real device-health
    monitor) routes to the ``replan`` callback, which rebuilds the plan and
    train step for the surviving devices; the live TrainState is pulled to
    its global host form (optimizer statistics untouched) and training
    continues at the same step on the new mesh - no restart, no lost
    progress;
  - fault injection: ``faults`` replays a ``runtime.faults`` schedule
    (device dropout, slowdown, step failure, mid-save writer crash,
    on-disk leaf corruption) through the exact recovery paths above.
"""
from __future__ import annotations

import dataclasses
import logging
import statistics
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import obs
from repro.ckpt.manager import CheckpointManager
from repro.runtime.faults import ClusterChange, FaultInjector

log = logging.getLogger("repro.runtime")


@dataclasses.dataclass
class DriverConfig:
    ckpt_dir: str
    ckpt_every: int = 50
    max_restarts: int = 3
    straggler_factor: float = 2.0
    hang_timeout: float = 300.0
    async_ckpt: bool = True
    log_every: int = 0               # 0 = no periodic metric logging
    resume: str = "auto"             # auto | always | never
    io_retries: int = 3              # checkpoint IO retry budget
    io_backoff: float = 0.05         # base backoff (doubles per retry)


@dataclasses.dataclass
class DriverReport:
    steps_done: int = 0
    restarts: int = 0
    replans: int = 0
    straggler_steps: int = 0
    resumed_step: Optional[int] = None   # checkpoint step resumed from
    hung: bool = False                   # the watchdog saw no step for hang_timeout
    step_times: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)   # per completed step
    last_metrics: Optional[dict] = None
    final_state: Any = None


@dataclasses.dataclass
class ServeReport:
    """Outcome of a ``run_serving`` drive (DESIGN.md §13): request counts,
    latency percentiles, dispatch-slack floor, bucket census, cache stats."""

    served: int = 0
    dispatches: int = 0
    deadline_misses: int = 0
    min_slack_s: Optional[float] = None
    p50_s: Optional[float] = None
    p99_s: Optional[float] = None
    throughput: Optional[float] = None
    bucket_census: dict = dataclasses.field(default_factory=dict)
    cache: dict = dataclasses.field(default_factory=dict)


class Watchdog:
    def __init__(self, timeout: float):
        self.timeout = timeout
        self._last = time.monotonic()
        self._hung = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def beat(self):
        self._last = time.monotonic()

    @property
    def hung(self) -> bool:
        return self._hung.is_set()

    def _run(self):
        while not self._stop.wait(min(self.timeout / 4, 5.0)):
            if time.monotonic() - self._last > self.timeout:
                self._hung.set()
                log.error("watchdog: no step completed in %.0fs", self.timeout)

    def stop(self):
        self._stop.set()


def run_serving(
    engine,
    *,
    ticks: int,
    on_tick: Optional[Callable[[int, Any], None]] = None,
    hang_timeout: float = 300.0,
    drain: bool = True,
) -> ServeReport:
    """Drive a ``serve.cnn_engine.CNNServeEngine`` under the same
    operational umbrella as ``run_training``: a watchdog heartbeats every
    engine step (a hung XLA dispatch or a wedged device surfaces as the
    same hang signal a stuck train step does), and the outcome comes back
    as a structured ``ServeReport``.

    ``on_tick(t, engine)`` is the traffic source: it submits requests
    and/or advances an injected virtual clock - keeping arrivals outside
    the driver makes the loop deterministic under test schedules and
    trivially replaceable by a socket/HTTP front-end.  Each tick runs the
    engine's admit-or-wait decision once; after ``ticks``, ``drain=True``
    ships whatever is still queued (no further arrivals expected).
    """
    watchdog = Watchdog(hang_timeout)
    try:
        for t in range(ticks):
            if on_tick is not None:
                on_tick(t, engine)
            engine.step()
            watchdog.beat()
        if drain:
            engine.drain()
            watchdog.beat()
    finally:
        watchdog.stop()
    s = engine.stats()
    return ServeReport(
        served=s["served"],
        dispatches=s["dispatches"],
        deadline_misses=s["deadline_misses"],
        min_slack_s=s["min_slack_s"],
        p50_s=s.get("p50_s"),
        p99_s=s.get("p99_s"),
        throughput=s.get("throughput"),
        bucket_census=s["bucket_census"],
        cache=s["cache"],
    )


def run_training(
    *,
    init_state: Callable[[jax.Array], Any],
    train_step: Callable[[Any, dict], tuple[Any, dict]],
    make_batch: Callable[[int], dict],
    steps: int,
    cfg: DriverConfig,
    seed: int = 0,
    fault_hook: Optional[Callable[[int], None]] = None,
    state_shardings: Any = None,
    faults: Optional[FaultInjector] = None,
    replan: Optional[Callable[[ClusterChange], tuple[Callable, Any]]] = None,
    plan: Any = None,
) -> DriverReport:
    """Run ``steps`` steps with checkpoint/restart fault tolerance.

    make_batch(step) must be deterministic so restarts replay the stream.
    fault_hook(step) may raise to inject failures (tests); ``faults`` is
    the structured form (a ``runtime.faults.FaultInjector`` replaying a
    parsed schedule - device drops arrive as ``ClusterChange``).

    ``plan`` is an optional JSON-serializable plan manifest
    (``core.fusion.plan_manifest``) stored with every checkpoint.  When a
    ``ClusterChange`` escapes the step loop it is handed to
    ``replan(event)``, which must return ``(new_train_step,
    new_plan_manifest)`` built for the changed cluster; the driver pulls
    the live TrainState to its partition-independent host form (global
    numpy leaves - optimizer statistics pass through untouched) and
    continues at the *same* step on the new mesh.  Without a ``replan``
    callback a ClusterChange is fatal (re-raised after draining saves).

    ``cfg.resume``: "auto" restores the newest loadable checkpoint when
    one exists, "always" requires one (FileNotFoundError otherwise),
    "never" ignores existing checkpoints and starts fresh.  Restores are
    fallback-aware: a corrupted newest step is skipped (ckpt.manager) and
    the replayed stream resumes from the step actually loaded.
    """
    mgr = CheckpointManager(
        cfg.ckpt_dir, io_retries=cfg.io_retries, io_backoff=cfg.io_backoff
    )
    if faults is not None:
        faults.bind(mgr)
    report = DriverReport()
    watchdog = Watchdog(cfg.hang_timeout)

    def fresh():
        return init_state(jax.random.PRNGKey(seed))

    if cfg.resume not in ("auto", "always", "never"):
        raise ValueError(f"resume must be auto|always|never; got {cfg.resume!r}")
    state = None
    start_step = 0
    if cfg.resume == "always" and mgr.latest_step() is None:
        raise FileNotFoundError(
            f"resume='always' but no checkpoint in {cfg.ckpt_dir}"
        )
    if cfg.resume != "never" and mgr.latest_step() is not None:
        abstract = jax.eval_shape(fresh)
        state, loaded = mgr.restored_step(abstract, shardings=state_shardings)
        start_step = loaded + 1
        report.resumed_step = loaded
        log.info("restored checkpoint at step %d", loaded)
    if state is None:
        state = fresh()

    step = start_step
    restarts = 0
    try:
        while step < steps:
            try:
                with StepTraceAnnotation(obs.STEP, step_num=step):
                    t0 = time.monotonic()
                    if faults is not None:
                        faults.on_step(step)
                    if fault_hook is not None:
                        fault_hook(step)
                    with TraceAnnotation(obs.MAKE_BATCH):
                        batch = make_batch(step)
                    with TraceAnnotation(obs.DISPATCH):
                        state, metrics = train_step(state, batch)
                    with TraceAnnotation(obs.WAIT):
                        jax.block_until_ready(metrics["loss"])
                    dt = time.monotonic() - t0
                    watchdog.beat()
                    with TraceAnnotation(obs.METRICS):
                        report.step_times.append(dt)
                        report.last_metrics = jax.tree.map(float, metrics)
                        report.losses.append(report.last_metrics["loss"])
                        if cfg.log_every and (step + 1) % cfg.log_every == 0:
                            log.info(
                                "step %d: %s (%.3fs)",
                                step,
                                " ".join(
                                    f"{k}={v:.5g}"
                                    for k, v in sorted(report.last_metrics.items())
                                ),
                                dt,
                            )
                        if len(report.step_times) >= 5:
                            med = statistics.median(report.step_times[-50:])
                            if dt > cfg.straggler_factor * med:
                                report.straggler_steps += 1
                                log.warning(
                                    "straggler: step %d took %.3fs (median %.3fs)",
                                    step, dt, med,
                                )
                    report.steps_done += 1
                    if (step + 1) % cfg.ckpt_every == 0 or step + 1 == steps:
                        with TraceAnnotation(obs.CHECKPOINT, step=step):
                            mgr.save(step, state, blocking=not cfg.async_ckpt, plan=plan)
                    step += 1
            except ClusterChange as ev:
                # elastic path: the device set changed - rebuild the plan
                # for the survivors and keep the live state (its leaves are
                # global arrays; the new jit re-places them).  Optimizer
                # statistics ride along untouched.
                if replan is None:
                    log.error("cluster change (%s) with no replan callback", ev)
                    mgr.wait()
                    raise
                log.warning("cluster change: %s; replanning", ev)
                with TraceAnnotation(obs.REPLAN, step=step):
                    mgr.wait()            # drain in-flight save before remap
                    train_step, plan = replan(ev)
                if isinstance(plan, dict) and plan.get("groups"):
                    # surface what the replan decided: per-group partition
                    # modes, and stage device ranges for pipeline plans
                    log.warning(
                        "replanned: grid=%sx%s modes=%s%s",
                        plan.get("n"), plan.get("m"),
                        [m for _, _, m in plan["groups"]],
                        " stages=%s" % (plan["stages"],)
                        if plan.get("stages") else "",
                    )
                state = jax.tree.map(np.asarray, state)
                report.replans += 1
                # continue at the same step: no progress lost on a replan
            except Exception as e:  # noqa: BLE001 - any step failure is retryable
                restarts += 1
                report.restarts = restarts
                log.exception("step %d failed (%s); restart %d", step, e, restarts)
                if restarts > cfg.max_restarts:
                    mgr.wait()
                    raise
                with TraceAnnotation(obs.RESTORE, step=step):
                    try:
                        mgr.wait()
                    except Exception:  # noqa: BLE001 - async save failure; disk
                        log.exception("async save failed during restart; "
                                      "restoring from last committed step")
                    if mgr.latest_step() is not None:
                        abstract = jax.eval_shape(fresh)
                        state, loaded = mgr.restored_step(
                            abstract, shardings=state_shardings
                        )
                        step = loaded + 1
                    else:
                        state = fresh()
                        step = 0
        mgr.wait()
    finally:
        watchdog.stop()
        report.hung = watchdog.hung
    report.final_state = state
    return report
