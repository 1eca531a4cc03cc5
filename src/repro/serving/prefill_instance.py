"""Prefill instance (FlowPrefill §4/§5): Request Queue + Scheduler + Execution
Pool, wired event-driven. The Scheduler thread blocks on the Event Monitor;
each ARRIVAL/COMPLETION event triggers exactly one SchedulerCore round whose
Decision is enacted as submit / preempt / resume commands on the pool.

Prefix sharing (``prefix_share=True``): the instance owns a prefix-sharing
`PagedKVCache` holding completed prompts' KV. On ARRIVAL the prompt's block
hash chain probes the trie and the sequence is allocated with the cached
prefix pinned (only the suffix gets fresh blocks); at SUBMIT the pinned
prefix KV is gathered from the pool and `SegmentedPrefill.start` resumes at
operator offset ``prefix_len`` — a hit is pure skipped compute. On
COMPLETION the computed suffix KV is scattered into the fresh blocks, the
full blocks are registered in the trie, and the sequence is released
(refcount decrement: its blocks stay CACHED for the next matching prompt,
LRU-evicted only under capacity pressure).

Device: the instance lives on the device that holds its executor's params.
Its prefill state and prefix pool are allocated there, and both worker
threads run `on_device` of it, so instances on different chips of one host
keep their arrays apart.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.events import Event, EventKind, EventMonitor
from repro.core.prefixcache import block_keys
from repro.core.request import Request, RequestState
from repro.core.scheduler import Action, SchedulerCore
from repro.core.tracing import TRACER, now_ns, on, rids
from repro.models.model import on_device, params_device
from repro.models.segments import SegmentedPrefill
from repro.serving.kvcache import PagedKVCache
from repro.serving.pool import ExecTask, ExecutionPool


class PrefillInstance:
    def __init__(self, params, cfg, scheduler: SchedulerCore, *, max_seq: int,
                 granularity: str = "op", chunk_tokens: int = 0,
                 attn_impl: str = "xla",
                 clock: Callable[[], float] = time.monotonic,
                 on_prefill_done: Optional[Callable] = None,
                 executor: Optional[SegmentedPrefill] = None,
                 dispatch_depth: int = 2,
                 prefix_share: bool = False,
                 prefix_cache_blocks: int = 512,
                 kv_block_size: int = 128,
                 host_cache_blocks: int = 0,
                 disk_cache_blocks: int = 0,
                 promote_wait_s: float = 10.0):
        self.cfg = cfg
        self.scheduler = scheduler
        self.clock = clock
        self.max_seq = max_seq
        self.on_prefill_done = on_prefill_done
        # a pre-built (warm-compiled) executor may be shared across instances
        self.executor = executor or SegmentedPrefill(
            params, cfg, max_seq=max_seq, granularity=granularity,
            chunk_tokens=chunk_tokens, attn_impl=attn_impl)
        self.device = params_device(self.executor.params)

        # prefix-sharing prompt KV cache (None = disabled, the default)
        self.kv: Optional[PagedKVCache] = None
        self.kv_block_size = kv_block_size
        if prefix_share:
            with on_device(self.device):
                self.kv = PagedKVCache(
                    cfg.num_layers, prefix_cache_blocks, kv_block_size,
                    cfg.num_kv_heads, cfg.resolved_head_dim,
                    dtype=self.executor.cache_dtype, prefix_share=True,
                    host_cache_blocks=host_cache_blocks,
                    disk_cache_blocks=disk_cache_blocks)
        # guards self.kv: the scheduler thread mutates it on every
        # arrival/completion while the Proxy probes it for affinity routing
        self._kv_lock = threading.Lock()
        self._prefix: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
        # rid -> (pool hit tokens, hash chain) for sequences holding blocks
        self.prefix_hits = 0                 # requests with a nonzero hit
        self.prefix_hit_tokens = 0           # prompt tokens served cached
        # tiered promotion: rid -> in-flight PromotionTicket, settled by
        # _make_task before the prefill that depends on the blocks starts
        self._tickets: Dict[int, object] = {}
        self.promote_wait_s = promote_wait_s
        self.prefix_promotions = 0           # blocks re-warmed from a tier
        self.prefix_promoted_tokens = 0      # hit tokens gained by promotion

        self.monitor = EventMonitor()
        self.pool = ExecutionPool(step_fn=self._step, on_complete=self._complete,
                                  clock=clock, dispatch_depth=dispatch_depth,
                                  on_error=self._on_pool_error)

        # supervised-worker health (docs/ARCHITECTURE.md failure model):
        # a crash in either worker thread strands the queued + in-flight
        # requests back to `on_fault` (the Proxy re-dispatches them) and
        # flips healthy=False until restart(). last_progress feeds the
        # Proxy's watchdog (hang detection).
        self.healthy = True
        self.on_fault: Optional[Callable] = None   # (requests, exc) -> None
        self.last_error: Optional[BaseException] = None
        self.last_progress = clock()

        # request bookkeeping (owned by the scheduler thread)
        self._tokens: Dict[int, np.ndarray] = {}
        self._arrived_ns: Dict[int, int] = {}      # rid -> ARRIVAL stamp
        self._waiting: List[Request] = []
        self._running: Optional[ExecTask] = None
        self._preempted: Dict[int, ExecTask] = {}   # head rid -> task
        self.completed: List[Request] = []
        self.completed_tasks: List[ExecTask] = []
        self._lock = threading.Lock()
        # drain() waits here; the scheduler thread notifies after any event
        # that may have emptied the instance (no polling — PR 4's
        # DecodeInstance.drain fix applied to the prefill side)
        self._idle_cv = threading.Condition(self._lock)

        self._shutdown = False
        self._thread = threading.Thread(target=self._scheduler_loop,
                                        daemon=True, name="scheduler")
        self._thread.start()

    # ------------------------------------------------------------- frontend
    def submit_request(self, req: Request, tokens: np.ndarray) -> None:
        with self._lock:
            self._tokens[req.rid] = np.asarray(tokens)
            self._arrived_ns[req.rid] = now_ns()
        self.monitor.publish(Event(time=self.clock(), kind=EventKind.ARRIVAL,
                                   payload=req))

    def probe_prefix(self, tokens: np.ndarray) -> int:
        """Cached-prefix tokens this instance's pool holds for `tokens` —
        the affinity signal the Proxy's prefix-affinity dispatch routes on.
        0 without prefix sharing. Capped at len-1: the last position is
        always computed live (first-token logits)."""
        if self.kv is None:
            return 0
        tokens = np.asarray(tokens)
        return self.probe_keys(block_keys(tokens, self.kv_block_size),
                               int(tokens.size))

    def probe_keys(self, keys, num_tokens: int) -> int:
        """`probe_prefix` for a pre-hashed chain: the Proxy hashes the
        prompt ONCE per dispatch and probes every instance with the same
        chain — only the trie walk runs under each instance's lock."""
        if self.kv is None:
            return 0
        with self._kv_lock:
            hit = self.kv.probe(keys)
        return min(hit, max(num_tokens - 1, 0))

    def probe_keys_tiers(self, keys, num_tokens: int) -> Tuple[int, int, int]:
        """`probe_keys` with tier-tagged lengths: (warm, host, disk) cached
        tokens, jointly capped at num_tokens - 1. Warm tokens are free;
        cold ones cost `promote_seconds` — the Proxy prices both into one
        net ttft_saved so dispatch sees warm/cold/absent as three prices."""
        if self.kv is None:
            return (0, 0, 0)
        with self._kv_lock:
            warm, host, disk = self.kv.probe_tiers(keys)
        cap = max(num_tokens - 1, 0)
        warm = min(warm, cap)
        host = min(host, cap - warm)
        disk = min(disk, cap - warm - host)
        return warm, host, disk

    def promote_seconds(self, host_tokens: int, disk_tokens: int = 0) -> float:
        """Predicted copy time to promote that many cold tokens (0 when
        this instance has no cold tiers)."""
        if self.kv is None or not getattr(self.kv, "tiered", False):
            return 0.0
        return self.kv.promote_seconds(host_tokens, disk_tokens)

    def drain(self, timeout: float = 60.0) -> bool:
        """Wait until all submitted requests completed. Waits on the
        instance condition variable — the scheduler thread notifies after
        every processed event — instead of the old 2 ms busy-wait poll."""
        def idle() -> bool:
            # unhealthy => never drained: the strand sweep clears these
            # queues BEFORE on_fault hands the victims to the supervisor,
            # and "drained" in that gap would let the proxy settle on work
            # that is mid-flight to the recovery path
            return self.healthy and not (
                self._waiting or self._preempted
                or self._running is not None
                or self.monitor.qsize() > 0)
        with self._idle_cv:
            return self._idle_cv.wait_for(idle, timeout)

    def shutdown(self) -> None:
        self._shutdown = True
        self.monitor.publish(Event(time=self.clock(), kind=EventKind.SHUTDOWN))
        self._thread.join(5.0)
        self.pool.shutdown()
        if self.kv is not None:
            # settle any promotion that never reached a SUBMIT (its request
            # is abandoned): drain the copy engine and abort the in-flight
            # reservations so the pool accounting stays leak-free
            for rid, ticket in list(self._tickets.items()):
                del self._tickets[rid]
                with self._kv_lock:
                    self.kv.promote_settle(ticket)
            self.kv.close()

    # ---------------------------------------------------------------- worker
    def _step(self, task: ExecTask) -> bool:
        sp = TRACER.begin("prefill.segment", rid=rids(task.requests),
                          parent=task.head.trace_id,
                          **self.executor.describe(task.prefill_task)) \
            if on() else None
        try:
            with on_device(self.device):
                return self.executor.step(task.prefill_task)
        finally:
            if sp is not None:
                TRACER.end(sp)

    def _complete(self, task: ExecTask) -> None:
        if not self.healthy:
            # zombie completion: the instance already stranded this task's
            # requests to the Proxy — mutating them now would race their
            # re-dispatch (the Proxy's _completed_rids dedupe is the second
            # line of defense for the narrow flag-read window)
            return
        now = task.complete_time
        for r in task.requests:
            r.first_token_time = now
            r.state = RequestState.DONE
            r.ops_done = r.ops_total
        self.monitor.publish(Event(time=now, kind=EventKind.COMPLETION,
                                   payload=task))

    # ------------------------------------------------------------- scheduler
    def _scheduler_loop(self) -> None:
        with on_device(self.device):
            self._schedule_events()

    def _schedule_events(self) -> None:
        while not self._shutdown:
            ev = self.monitor.next_event(timeout=1.0)
            if ev is None:
                continue
            if ev.kind == EventKind.SHUTDOWN:
                return
            try:
                if ev.kind == EventKind.FAULT:
                    inj = ev.payload
                    if isinstance(inj, tuple) and inj and inj[0] == "hang":
                        # simulated hang: stall OUTSIDE the lock so the
                        # watchdog can still strand the queues
                        time.sleep(float(inj[1]))
                        continue
                    raise inj if isinstance(inj, BaseException) \
                        else RuntimeError(str(inj))
                if not self.healthy:
                    if ev.kind == EventKind.ARRIVAL:
                        # a dispatch that raced the failure: the request was
                        # not yet queued when the strand swept, so bounce it
                        # straight back to the recovery path (silently
                        # dropping it would break no-request-lost)
                        cb = self.on_fault
                        if cb is not None:
                            cb([ev.payload], self.last_error
                               or RuntimeError("instance down"))
                    continue        # stranded: drop zombies until restart()
                with self._lock:
                    self._handle_event(ev)
                    sp = self._round_span(ev) if on() else None
                    try:
                        self._round(sp)
                    finally:
                        if sp is not None:
                            TRACER.end(sp)
                    if not (self._waiting or self._preempted
                            or self._running is not None
                            or self.monitor.qsize() > 0):
                        self._idle_cv.notify_all()
                self.last_progress = self.clock()
            except Exception as exc:
                self._on_worker_failure(exc)

    # ------------------------------------------------ supervised recovery
    def _on_worker_failure(self, exc: Exception) -> None:
        """Strand everything back to the proxy layer: idempotent (first
        failure wins), callable from the scheduler thread, the pool worker,
        or the Proxy's watchdog. Queued, suspended, and running requests are
        all returned — their partial prefill state died with the instance
        (the KV-lost convention the simulator shares)."""
        with self._lock:
            if not self.healthy:
                return
            self.healthy = False
            self.last_error = exc
            stranded: List[Request] = list(self._waiting)
            for task in self._preempted.values():
                stranded.extend(task.requests)
            if self._running is not None:
                stranded.extend(self._running.requests)
            self._waiting = []
            self._preempted = {}
            self._running = None
            self._idle_cv.notify_all()
        # stop the pool's in-flight task too: left running, it would still
        # occupy the pool after restart() and collide with the first
        # post-revive submit. From the pool worker's own error path _current
        # is already None and this returns immediately.
        self.pool.preempt_current(timeout=5.0)
        self.pool.clear_preempted()
        cb = self.on_fault
        if cb is not None:
            cb(stranded, exc)       # outside the lock: the Proxy re-enters

    def _on_pool_error(self, task, exc: Exception) -> None:
        # the failed ExecTask is still referenced from self._running /
        # self._preempted, so _on_worker_failure strands its requests too
        self._on_worker_failure(exc)

    def inject_fault(self, fault) -> None:
        """Chaos-harness entry (core/faults.py): an Exception crashes the
        scheduler loop at its next event; ("hang", seconds) stalls it."""
        self.monitor.publish(Event(time=self.clock(), kind=EventKind.FAULT,
                                   payload=fault))

    def restart(self) -> None:
        """Rejoin after a failure: both worker threads survive exceptions,
        so recovery is a state reset, not a thread respawn."""
        with self._lock:
            self.healthy = True
            self.last_error = None
            self.last_progress = self.clock()
        self.pool.restart()

    @property
    def progress_ts(self) -> float:
        """Latest liveness signal across both worker threads (scheduler
        event processed, or pool operator boundary crossed) — what the
        Proxy's hang watchdog compares against its deadline."""
        return max(self.last_progress, self.pool.last_step)

    def _acquire_prefix(self, req: Request, tokens: np.ndarray) -> None:
        """ARRIVAL-time trie probe + allocation: pin the cached prefix and
        reserve fresh suffix blocks, so eviction cannot touch the hit while
        the request waits/executes. A full pool (even after LRU eviction)
        just means this prompt goes uncached — never an error."""
        n = len(tokens)
        keys = block_keys(tokens, self.kv_block_size)
        with self._kv_lock:
            try:
                table = self.kv.allocate(req.rid, n, keys=keys)
            except MemoryError:
                return
            hit = min(table.length, max(n - 1, 0))
            ticket = self._begin_promotion(keys, n, table.length)
        self._prefix[req.rid] = (hit, keys)
        req.prefix_hit = hit
        if ticket is not None and ticket.blocks:
            self._tickets[req.rid] = ticket
        if hit:
            self.prefix_hits += 1
            self.prefix_hit_tokens += hit

    def _begin_promotion(self, keys, n: int, warm: int):
        """Under _kv_lock at ARRIVAL: if the prompt's chain extends into a
        cold tier, start promoting it — but only when the predicted copy
        time beats the recompute the promotion would save (the scheduler's
        TTFT predictor prices the save, exactly the transfer-vs-recompute
        gate decode migration uses). Returns a PromotionTicket or None."""
        if not getattr(self.kv, "tiered", False):
            return None
        _, host_t, disk_t = self.kv.probe_tiers(keys)
        cap = max(n - 1, 0) - warm         # useful tokens beyond the warm run
        cold = min(host_t + disk_t, cap)
        if cold <= 0:
            return None
        pred = getattr(self.scheduler, "predictor", None)
        if pred is not None:
            saved = max(float(pred.predict(n - warm))
                        - float(pred.predict(n - warm - cold)), 0.0)
            host_use = min(host_t, cold)
            cost = self.kv.promote_seconds(host_use, cold - host_use)
            if cost >= saved:
                return None                # cheaper to recompute than copy
        bs = self.kv_block_size
        return self.kv.promote_async(keys, max_blocks=(cold + bs - 1) // bs)

    def _publish_prefix(self, task: ExecTask) -> None:
        """COMPLETION-time insert: scatter each member's computed suffix KV
        into its fresh blocks, register the full blocks in the trie, release
        the sequence (refcount decrement — blocks stay cached, LRU-ordered).
        The prefill state's cache rows are fully valid (< prefix seeded,
        >= prefix computed), so the slice is always well-defined."""
        st = task.prefill_task.state
        with self._kv_lock:
            for i, req in enumerate(task.requests):
                entry = self._prefix.pop(req.rid, None)
                if entry is None:
                    continue                      # pool was full at arrival
                _, keys = entry
                table = self.kv.table(req.rid)
                start = table.prefix_blocks * self.kv_block_size
                n = int(st["lens"][i])
                if start < n:
                    self.kv.write_prompt(
                        req.rid, st["k_cache"][:, i, start:n],
                        st["v_cache"][:, i, start:n], start=start)
                self.kv.insert(req.rid, keys)
                self.kv.free(req.rid)

    def _handle_event(self, ev: Event) -> None:
        if ev.kind == EventKind.ARRIVAL:
            req: Request = ev.payload
            req.state = RequestState.WAITING
            if self.kv is not None:
                self._acquire_prefix(req, self._tokens[req.rid])
            self._waiting.append(req)
        elif ev.kind == EventKind.COMPLETION:
            task: ExecTask = ev.payload
            if self._running is not None and task.task_id == self._running.task_id:
                self._running = None
            if self.kv is not None:
                self._publish_prefix(task)
            self.completed.extend(task.requests)
            self.completed_tasks.append(task)
            if self.on_prefill_done is not None:
                self.on_prefill_done(task)

    @staticmethod
    def _round_span(ev: Event):
        """The `sched.round` span of the round an event triggers, caused by
        the arriving request or the completed task's head."""
        reqs = [ev.payload] if ev.kind == EventKind.ARRIVAL \
            else ev.payload.requests
        return TRACER.begin("sched.round", rid=rids(reqs),
                            parent=reqs[0].trace_id, event=ev.kind.value)

    def _round(self, sp=None) -> None:
        """One scheduling round (Alg. 2) + command execution."""
        now = self.clock()
        running_req = self._running.head if self._running is not None else None
        preempted_reqs = [t.head for t in self._preempted.values()]
        decision = self.scheduler.schedule_round(
            now, self._waiting, preempted_reqs, running_req)
        if sp is not None:
            sp.set(action=decision.action.value,
                   preempt=decision.preempt is not None)
        if decision.is_noop:
            return

        if decision.preempt is not None and self._running is not None:
            suspended = self.pool.preempt_current()
            if suspended is not None:
                head = suspended.head
                for r in suspended.requests:
                    r.state = RequestState.PREEMPTED
                head.ops_total = suspended.prefill_task.total_segments
                head.ops_done = suspended.prefill_task.cursor
                self._preempted[head.rid] = suspended
                self._running = None
            else:
                # completed concurrently; the COMPLETION event will arrive.
                self._running = None

        if decision.action == Action.SUBMIT:
            batch = decision.batch
            task = self._make_task(batch)
            for r in batch:
                r.state = RequestState.RUNNING
                r.ops_total = task.prefill_task.total_segments
                r.ops_done = 0
            waiting_ids = {r.rid for r in batch}
            self._waiting = [r for r in self._waiting
                             if r.rid not in waiting_ids]
            self._running = task
            self.pool.submit(task)
            arrived = [self._arrived_ns.pop(r.rid, 0) for r in batch]
            if on():
                t = now_ns()
                for r, t0 in zip(batch, arrived):
                    TRACER.record("prefill.queue", t0 or t, t, rid=r.rid,
                                  parent=r.trace_id)
        elif decision.action == Action.RESUME:
            head = decision.target
            task = self._preempted.pop(head.rid)
            for r in task.requests:
                r.state = RequestState.RUNNING
            self._running = task
            self.pool.resume(task.task_id)

    def _settle_promotion(self, req: Request, ticket) -> None:
        """SUBMIT-time settle for one batch member: wait for the copies
        OUTSIDE the kv lock (workers never take it — the prefill BLOCKS on a
        copy still in flight, it never crashes into one), then commit under
        the lock and re-pin the now-longer prefix. Every failure mode
        degrades to the pre-promotion hit: a timed-out copy aborts back to
        its tier, a corrupt one is dropped (recompute — never stale KV),
        and a full pool on re-pin just leaves the prompt uncached."""
        ticket.wait(self.promote_wait_s)
        entry = self._prefix.get(req.rid)
        gained = 0
        with self._kv_lock:
            committed = self.kv.promote_settle(ticket)
            if committed > 0 and entry is not None:
                old_hit, keys = entry
                n = int(self._tokens[req.rid].size)
                self.kv.free(req.rid)
                try:
                    table = self.kv.allocate(req.rid, n, keys=keys)
                except MemoryError:
                    self._prefix.pop(req.rid, None)
                    req.prefix_hit = 0
                    return
                hit = min(table.length, max(n - 1, 0))
                self._prefix[req.rid] = (hit, keys)
                req.prefix_hit = hit
                gained = max(hit - old_hit, 0)
                self.prefix_promotions += committed
                self.prefix_promoted_tokens += gained
                if old_hit == 0 and hit > 0:
                    self.prefix_hits += 1
                self.prefix_hit_tokens += gained

    def _make_task(self, batch: List[Request]) -> ExecTask:
        if self.kv is not None:
            for r in batch:
                ticket = self._tickets.pop(r.rid, None)
                if ticket is not None:
                    self._settle_promotion(r, ticket)
        toks = [self._tokens[r.rid] for r in batch]
        lens = [len(t) for t in toks]
        S = max(lens)
        arr = np.zeros((len(batch), S), dtype=np.int32)
        for i, t in enumerate(toks):
            arr[i, :len(t)] = t
        # prefix-cache resumption: the batch shares one operator offset, so
        # it starts at the MINIMUM member hit (rows with longer hits just
        # recompute a little — single-request tasks, the common case, use
        # their full hit). Capped at min(lens) - 1: the head needs a live
        # last position.
        P = 0
        if self.kv is not None and batch:
            P = min(self._prefix.get(r.rid, (0, ()))[0] for r in batch)
            P = min(P, min(lens) - 1)
        if P > 0:
            with self._kv_lock:
                ks, vs = [], []
                for r in batch:
                    k, v, _ = self.kv.gather(r.rid)
                    ks.append(k[:, :P])
                    vs.append(v[:, :P])
            pk = jnp.stack(ks, axis=1)           # (L, B, P, K, hd)
            pv = jnp.stack(vs, axis=1)
            pt = self.executor.start(jnp.asarray(arr), lens=jnp.asarray(lens),
                                     prefix_len=P, prefix_k=pk, prefix_v=pv)
        else:
            pt = self.executor.start(jnp.asarray(arr), lens=jnp.asarray(lens))
        return ExecTask(prefill_task=pt, requests=list(batch))

    # ------------------------------------------------------------- metrics
    @property
    def blocking_stats(self):
        return self.pool.blocking

    @property
    def scheduling_rounds(self) -> int:
        return self.monitor.rounds
