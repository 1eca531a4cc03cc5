"""Execution Pool (FlowPrefill §4, §5.1).

Manages execution tasks: runs at most one at a time, safely preserves the
state of preempted tasks until resumption, and acts ONLY on explicit commands
(submit / preempt / resume) from the Scheduler — it makes no scheduling
decisions itself.

The worker thread advances the current task segment-by-segment, performing the
cooperative preemption check (a flag read) at every operator boundary — the
exact protocol of paper Fig. 7 including the signal/ACK handshake and the
completion race (a task finishing while a signal is pending ACKs immediately
so the scheduler never stalls; the ACK is distinguishable from suspension).
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax

from repro.core.preemption import BlockingStats, PreemptionSignal
from repro.core.request import Request
from repro.core.tracing import TRACER, now_ns, on, rids

_task_ids = itertools.count()


@dataclass
class ExecTask:
    """One execution task = one (possibly batched) prefill."""
    prefill_task: object                      # models.segments.PrefillTask
    requests: List[Request]                   # batch members (H first)
    task_id: int = field(default_factory=lambda: next(_task_ids))
    submit_time: float = 0.0
    complete_time: Optional[float] = None
    # on tracing.now_ns(), whatever clock the pool was given: the
    # handoff span starts at complete_ns; a suspended task holds the
    # acknowledgement's stamp and the id of its preemption span
    complete_ns: int = 0
    suspended_ns: int = 0
    preempt_span: Optional[int] = None

    @property
    def head(self) -> Request:
        return self.requests[0]


class ExecutionPool:
    def __init__(self, step_fn: Callable[[ExecTask], bool],
                 on_complete: Callable[[ExecTask], None],
                 clock: Callable[[], float] = time.monotonic,
                 dispatch_depth: int = 2,
                 on_error: Optional[Callable[[Optional[ExecTask],
                                              Exception], None]] = None):
        """dispatch_depth bounds how many operator dispatches may be enqueued
        ahead of device completion. Without this bound JAX's async dispatch
        would let the host race to the end of the prefill, making the
        cooperative check vacuous; with it, preemption latency is
        <= (dispatch_depth + 1) x one operator — the paper's bound."""
        self._step = step_fn
        self._on_complete = on_complete
        self._on_error = on_error
        self._clock = clock
        self._dispatch_depth = max(dispatch_depth, 0)
        self.signal = PreemptionSignal()
        self.blocking = BlockingStats()
        self.healthy = True             # False after a worker exception
        self.last_step = clock()        # watchdog progress signal: stamped
                                        # at every operator boundary
        self._cv = threading.Condition()
        self._current: Optional[ExecTask] = None
        self._preempted: Dict[int, ExecTask] = {}
        self._shutdown = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="execution-pool")
        self._thread.start()

    # ------------------------------------------------------------------ API
    def submit(self, task: ExecTask) -> None:
        with self._cv:
            assert self._current is None, "pool executes at most one task"
            task.submit_time = self._clock()
            self._current = task
            self._cv.notify_all()

    def resume(self, task_id: int) -> ExecTask:
        with self._cv:
            task = self._preempted.pop(task_id)
        if on():
            TRACER.record("prefill.suspended", task.suspended_ns, now_ns(),
                          rid=rids(task.requests), parent=task.preempt_span)
        self.submit(task)
        return task

    def preempt_current(self, timeout: float = 10.0) -> Optional[ExecTask]:
        """Scheduler-side preemption (Fig. 7). Returns the suspended task, or
        None if nothing was running / the task completed concurrently."""
        with self._cv:
            task = self._current
        if task is None:
            return None
        self.signal.request_preemption()
        acked = self.signal.wait_ack(timeout)
        with self._cv:
            if acked and task.task_id in self._preempted:
                if on():
                    signal_ns, ack_ns = self.signal.stamps
                    task.preempt_span = TRACER.record(
                        "prefill.preempt", signal_ns, ack_ns,
                        rid=rids(task.requests), parent=TRACER.current())
                return task
        # completed before the boundary check could suspend it
        self.signal.cancel()
        return None

    def preempted_tasks(self) -> List[ExecTask]:
        with self._cv:
            return list(self._preempted.values())

    def clear_preempted(self) -> List[ExecTask]:
        """Drop all suspended tasks (supervised recovery: their requests are
        being re-dispatched elsewhere, so keeping the device state would only
        leak memory and invite zombie resumes)."""
        with self._cv:
            dropped = list(self._preempted.values())
            self._preempted.clear()
        return dropped

    def restart(self) -> None:
        """Mark the pool serviceable again after a worker exception (the
        worker thread survives errors, so this is just the health flip)."""
        with self._cv:
            self.healthy = True
            self.last_step = self._clock()

    def current(self) -> Optional[ExecTask]:
        with self._cv:
            return self._current

    def idle(self) -> bool:
        with self._cv:
            return self._current is None

    def shutdown(self, timeout: float = 5.0) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        self._thread.join(timeout)

    # --------------------------------------------------------------- worker
    def _run(self) -> None:
        while True:
            with self._cv:
                t_wait = 0
                while self._current is None and not self._shutdown:
                    t_wait = t_wait or now_ns()
                    self._cv.wait()
                if t_wait and on():
                    TRACER.record("prefill.wait", t_wait, now_ns())
                if self._shutdown:
                    return
                task = self._current

            try:
                self._run_task(task)
            except Exception as exc:        # supervised worker: a failing
                # operator (OOM, bad kernel, injected chaos) must not strand
                # the task forever — mark unhealthy, errback, keep the thread
                # alive so restart() can revive the instance
                with self._cv:
                    self.healthy = False
                    self._current = None
                if self.signal.check():
                    # unblock a racing preemption request (the scheduler
                    # would otherwise stall its full ack timeout)
                    self.signal.consume_and_ack()
                if self._on_error is not None:
                    self._on_error(task, exc)

    @staticmethod
    def _sync_span(task: ExecTask, what: str):
        """Open the `prefill.sync` span of a wait on the device."""
        return TRACER.begin("prefill.sync", rid=rids(task.requests),
                            parent=task.head.trace_id, wait_for=what)

    def _run_task(self, task: ExecTask) -> None:
        window: List = []                      # dispatched, maybe unfinished
        while True:
            # cooperative preemption check at the operator boundary
            if self.signal.check():
                # drain the in-flight operators (bounded by dispatch_depth)
                sp = self._sync_span(task, "drain") if on() else None
                jax.block_until_ready(task.prefill_task.state)
                if sp is not None:
                    TRACER.end(sp)
                dt = self.signal.consume_and_ack()
                self.blocking.record(dt)
                task.suspended_ns = self.signal.stamps[1]
                with self._cv:
                    self._preempted[task.task_id] = task
                    self._current = None
                return

            done = self._step(task)
            self.last_step = self._clock()
            # flow control: keep at most dispatch_depth segments in flight
            tok = task.prefill_task.sync_token
            if tok is not None:
                window.append(tok)
                if len(window) > self._dispatch_depth:
                    sp = self._sync_span(task, "window") if on() else None
                    jax.block_until_ready(window.pop(0))
                    if sp is not None:
                        TRACER.end(sp)

            if done:
                if task.prefill_task.logits is not None:
                    sp = self._sync_span(task, "logits") if on() else None
                    jax.block_until_ready(task.prefill_task.logits)
                    if sp is not None:
                        TRACER.end(sp)
                task.complete_time = self._clock()
                task.complete_ns = now_ns()
                with self._cv:
                    self._current = None
                # unblock a racing preemption request (scheduler will see
                # the task is NOT in the preempted set -> completed)
                if self.signal.check():
                    self.signal.consume_and_ack()
                self._on_complete(task)
                return
