"""Decode instance (FlowPrefill §4, extended): continuous-batching
autoregressive decode of handed-over prefills (the PD-disaggregation KV
transfer), with pluggable batch-admission scheduling.

The runtime mirrors the simulator's decode model (`DecodeSim`,
docs/SCHEDULING.md — evaluated-is-deployed): an instance owns up to
``decode_max_batch`` resident SLOTS backed by `PagedKVCache` block tables and
runs ONE jitted decode step per token over the whole resident batch
(`repro.models.model.decode_step_ragged`): decode is bandwidth-bound, so
weights are streamed once per step regardless of how many streams share it —
tokens/s scales near-linearly with the batch (benchmarks/fig21).

Scheduling (`repro.core.scheduler.DecodeSchedulerCore`, shared verbatim with
the simulator):

  * ``policy="fcfs"``  — arrival-order admission into free slots; residents
    are never displaced (the paper's deliberately-plain decode stage).
  * ``policy="s-edf"`` — admission ranked by TBT-deadline slack; with
    ``preempt`` a near-deadline waiting stream displaces the most slack-rich
    resident at the next TOKEN boundary. Preemption is slot *eviction*:
    progress, KV blocks (kept resident in the pool), and the next token all
    survive, exactly like the old single-stream suspend — the decode analogue
    of the paper's operator-level prefill preemption.

Batch shapes are BUCKETED (``batch_buckets``, KV width padded to
power-of-two block multiples) so jit recompilations are bounded by the
bucket-pair count, not by the number of distinct resident populations
(asserted in tests/test_decode_batched.py).

``decode_max_batch=1`` (the default) keeps the original single-stream worker
byte-for-byte: one dense `decode_step` per token on the job's own handoff
cache, so the B=1 path bit-matches the pre-batching runtime.

Slack needs a per-token latency estimate: a `DecodeStepPredictor` (analytic
or profiled `step_time(B, ctx)` prior, EMA-calibrated from this instance's
own measured TBT samples) or, without one, a plain EMA of observed TBT.

Queued (not yet resident) jobs can be handed to another instance by the Proxy
(decode migration): `snapshot_load`/`snapshot_candidates` feed the shared
cost-gated planner in `repro.core.dispatch`, `take` removes the chosen jobs
(evicted pool-resident streams are gathered back into a dense handoff cache).

SPECULATIVE DECODING (``spec_decode=True``): decode is bandwidth-bound, so
the jitted step leaves most of the device's compute idle — spend it on a
draft-then-verify scheme. Each step every resident row proposes up to
``draft_k`` tokens (default: the self-drafting n-gram drafter
`_ngram_draft`, suffix-matching the stream's own generated tokens; a custom
``draft_fn(rid, history, k)`` can be injected), then ONE batched
`decode_verify_ragged` pass scores all k+1 positions per row. Greedy
acceptance (longest draft prefix matching the argmax chain) makes the
output BIT-IDENTICAL to plain greedy decoding — speculation only changes
how many tokens a step commits (1..k+1, per row). Rejected draft KV is
rolled back by committed-length truncation (`PagedKVCache.write_token_span`)
— never readable, never stale. Per-stream accept-rate EMAs
(`DecodeStepPredictor.observe_accept`) keep S-EDF slack, migration gating
and hybrid token budgets priced in per-ACCEPTED-token terms, and an
adaptive throttle drops low-accept streams back to drafting nothing (with a
periodic re-probe); a step in which no row drafts runs the PLAIN jitted
step, so the adversarial low-accept regime degrades to ~plain cost
(benchmarks/fig27_spec_decode.py gates both regimes). ``spec_decode=False``
(the default) leaves every code path byte-identical to before.

Device: the instance lives on the device that holds its params. `submit`
moves a handed-off KV cache onto it (a prefill on another chip hands over
across chips), the paged pool is allocated there, and the worker thread runs
`on_device` of it.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import DecodeCandidate, DecodeLoad
from repro.core.predictor import DecodeStepPredictor
from repro.core.request import Request
from repro.core.scheduler import DecodeEntry, DecodeSchedulerCore
from repro.core.tracing import TRACER, jit_named, now_ns, on
from repro.models.model import (decode_step, decode_step_ragged,
                                decode_verify_ragged, on_device,
                                params_device, supports_ragged_decode)
from repro.serving.kvcache import PagedKVCache

# sequence id of the pool slot padding rows write into / gather from — never
# a real request rid (rids are non-negative)
_SCRATCH_SEQ = -1

# per-stream drafter corpus cap: the n-gram drafter scans this many recent
# generated tokens (host memory + host-CPU bound, not device state)
_SPEC_HISTORY_CAP = 512


def _ngram_draft(history: Sequence[int], k: int) -> List[int]:
    """Self-drafting n-gram proposal: find the most recent EARLIER occurrence
    of the stream's current suffix (3-gram first, then 2-gram) in its own
    generated tokens and draft the k tokens that followed it. Costs zero
    model weights and zero device work — repetitive streams (agentic loops,
    templated output) hit constantly, low-reuse chat simply drafts nothing
    and the step falls back to plain decoding."""
    n = len(history)
    if k <= 0 or n < 3:
        return []
    for m in (3, 2):
        if n < m + 1:
            continue
        suffix = tuple(history[-m:])
        for i in range(n - m - 1, -1, -1):
            if tuple(history[i:i + m]) == suffix:
                cont = history[i + m:i + m + k]
                if cont:
                    return [int(t) for t in cont]
    return []


@dataclass
class DecodeJob:
    request: Request
    cache: Dict                     # model.decode_step cache (B=1 slice);
                                    # None while the stream's KV lives in the
                                    # instance's paged pool (batched path)
    first_token: int
    tokens_done: int = 0            # tokens already decoded (preemption state)
    next_token: Optional[int] = None  # resume point after a suspension
    enqueued: float = 0.0           # first submit (fixes the decode deadline)
    order: int = 0                  # FCFS order / deterministic tiebreak
    target: int = 0                 # tokens to decode for THIS job (set at
                                    # submit: request.output_tokens, or the
                                    # instance default) — deadlines and
                                    # remaining-work MUST use the same count
    base_len: int = 0               # prompt tokens in the pool (batched path):
                                    # kv position = base_len + tokens_done
    history: Optional[List[int]] = None   # generated tokens (speculative
                                    # drafter corpus; None until the stream's
                                    # first spec step — plain decoding never
                                    # materializes it)
    probe_in: int = 0               # steps until a throttled stream re-probes
                                    # the drafter (spec_decode)
    queued_ns: int = 0              # last submit on tracing.now_ns(),
                                    # until the stream is next resident


class DecodeInstance:
    def __init__(self, params, cfg, *, decode_tokens: int = 8,
                 clock: Callable[[], float] = time.monotonic,
                 policy: str = "fcfs", preempt: Optional[bool] = None,
                 step_predictor: Optional[DecodeStepPredictor] = None,
                 decode_max_batch: int = 1,
                 batch_buckets: Sequence[int] = (1, 2, 4, 8),
                 kv_block_size: int = 128,
                 attn_impl: str = "naive",
                 prefix_share: bool = False,
                 kv_max_blocks: int = 0,
                 spec_decode: bool = False,
                 draft_k: int = 4,
                 draft_fn: Optional[Callable[
                     [int, Sequence[int], int], Sequence[int]]] = None,
                 spec_probe_period: int = 8,
                 spec_throttle: float = 1.15):
        if decode_max_batch > 1 and not supports_ragged_decode(cfg):
            raise ValueError(
                f"decode_max_batch={decode_max_batch} needs the batched "
                f"ragged decode step, unsupported for family "
                f"{cfg.family!r}; use decode_max_batch=1")
        if spec_decode and not supports_ragged_decode(cfg):
            raise ValueError(
                f"spec_decode needs the batched verify step, unsupported "
                f"for family {cfg.family!r}")
        self.params = params
        self.device = params_device(params)
        self.cfg = cfg
        self.decode_tokens = decode_tokens
        self.decode_max_batch = max(decode_max_batch, 1)
        self.clock = clock
        self.sched = DecodeSchedulerCore(
            policy=policy, preempt=(policy == "s-edf") if preempt is None
            else preempt)
        self.step_pred = step_predictor
        self.attn_impl = attn_impl
        self.kv_block_size = kv_block_size
        self.prefix_share = prefix_share   # pool created in share mode:
                                           # free() decrements refcounts and
                                           # parks trie-registered blocks in
                                           # the LRU cache instead of eagerly
                                           # freeing
        self.kv_max_blocks = kv_max_blocks  # admission-growth cap (0 = un-
                                            # bounded, the pre-cap behavior):
                                            # a leak then surfaces as
                                            # declined admissions instead of
                                            # unbounded pool doubling
        # batch-size buckets: padded shapes the jitted step may see — bounds
        # recompiles to len(buckets) x len(width buckets)
        self._b_buckets = sorted(
            {min(b, self.decode_max_batch) for b in batch_buckets if b >= 1}
            | {self.decode_max_batch})
        self._tbt_ema = 0.0             # fallback t_step estimate (no prior)
        self._waiting: List[DecodeJob] = []
        self._resident: Dict[int, DecodeJob] = {}   # rid -> job (slots)
        self._admitting = 0             # jobs mid-ingestion: in NEITHER list
                                        # (keeps drain/idle from lying)
        self._in_pool: set = set()      # rids whose KV lives in self.kv
        self.kv: Optional[PagedKVCache] = None      # lazily sized on first use
        # serializes ALL self.kv access: the worker's per-step gather/scatter
        # runs outside _cv (write_tokens DONATES the pool buffers), while
        # take() extracts evicted streams from other threads — unguarded
        # overlap would read a deleted/torn pool. Lock order: _cv -> _kv_lock.
        self._kv_lock = threading.Lock()
        self._cv = threading.Condition()
        self._order = 0
        self._shutdown = False
        self.finished: List[Request] = []
        self.tbt_samples: List[float] = []   # per-ACCEPTED-token TBT: a step
                                             # committing a tokens appends a
                                             # samples of dt/a (a=1 keeps the
                                             # plain path's values bit-equal)
        self.step_samples: List[float] = []  # per-STEP wall latency — the
                                             # satellite metric that stays
                                             # meaningful when tokens/step > 1
        self.preemptions = 0
        self.steps = 0                  # batched decode steps executed
        self.row_steps = 0              # (stream, step) pairs: per-row
                                        # tokens/step = len(tbt_samples)/this
        # --- speculative decoding (spec_decode=False leaves all of this
        # inert: plain paths never read it) ---------------------------------
        self.spec_decode = spec_decode
        # drafts must fit the scratch block (span writes at positions
        # 0..draft_k of the 1-block scratch sequence) and leave room for the
        # +1 verified token
        self.draft_k = max(1, min(int(draft_k), kv_block_size - 1))
        self.draft_fn = draft_fn        # None = self-drafting n-gram drafter
        self.spec_probe_period = max(int(spec_probe_period), 1)
        self.spec_throttle = float(spec_throttle)
        self.spec_steps = 0             # steps that ran the k+1 verify shape
        self.draft_proposed = 0         # draft tokens sent to verification
        self.draft_accepted = 0         # draft tokens committed
        self._accept_tps = 0.0          # aggregate tokens/step EMA fallback
                                        # (no step_pred attached)
        self._step = jit_named(
            "decode_step_dense", lambda p, t, c: decode_step(p, cfg, t, c))
        self._step_ragged = jit_named(
            "decode_step", lambda p, t, kg, vg, kl: decode_step_ragged(
                p, cfg, t, kg, vg, kl, attn_impl=attn_impl))
        self._step_verify = jit_named(
            "decode_verify", lambda p, t, kg, vg, kl: decode_verify_ragged(
                p, cfg, t, kg, vg, kl, attn_impl=attn_impl))
        # supervised-worker health (docs/ARCHITECTURE.md failure model): a
        # worker exception strands queued + resident jobs' REQUESTS back to
        # `on_fault` (the Proxy re-runs them from prefill — their pool KV
        # died with the instance) and flips healthy until restart().
        self.healthy = True
        self.on_fault: Optional[Callable] = None   # (requests, exc) -> None
        self.last_error: Optional[BaseException] = None
        self.last_progress = clock()
        self._inject: Optional[object] = None      # chaos: raise in worker
        # incarnation counter, bumped at every strand: a worker that wakes
        # from a hang AFTER restart() sees healthy=True again, so the flag
        # alone cannot tell it its job was re-dispatched — the epoch can
        # (the runtime analog of the simulator's killed_seq)
        self._epoch = 0
        # speculation lives in the batched worker (the verify pass IS a
        # batched ragged step), so spec_decode routes there even at slot cap 1
        run = self._run_batched \
            if self.decode_max_batch > 1 or self.spec_decode else self._run
        self._thread = threading.Thread(target=lambda: self._supervised(run),
                                        daemon=True, name="decode-instance")
        self._thread.start()

    # ------------------------------------------------------------- frontend
    def submit(self, job: DecodeJob) -> None:
        """Enqueue a decode job (fresh handoff or a migrated-in stream)."""
        req = job.request
        if req.decode_start is None:
            now = self.clock()
            job.enqueued = now
            req.decode_start = now      # fixes Request.decode_deadline
            if req.output_tokens <= 0:
                # the instance decodes exactly this many tokens; record it so
                # TBT accounting (decode_deadline / tbt_met) is well-defined
                req.output_tokens = self.decode_tokens
        if job.target <= 0:
            # deadline (output_tokens x tbt_slo) and remaining work must
            # count the SAME tokens, or slack estimates skew by their ratio
            job.target = req.output_tokens if req.output_tokens > 0 \
                else self.decode_tokens
        if job.cache is not None:
            # the handoff: a no-op for a cache already on this device
            job.cache = jax.device_put(job.cache, self.device)
        job.queued_ns = now_ns()
        with self._cv:
            job.order = self._order
            self._order += 1
            self._waiting.append(job)
            # notify_all: drain() waits on the same cv — a single notify
            # could wake the drain waiter (predicate now false) instead of
            # the worker, costing a wait-timeout of first-token latency
            self._cv.notify_all()

    def pending(self) -> int:
        """Decode jobs waiting in this instance's queue (the backlog signal
        decode-aware dispatch prices via DecodeCostModel.step_time)."""
        with self._cv:
            return len(self._waiting)

    def resident(self) -> int:
        """Streams currently occupying batch slots."""
        with self._cv:
            return len(self._resident)

    def idle(self) -> bool:
        """No queued work and nothing decoding. NOTE: a job being migrated
        is momentarily in NO instance, so cross-instance quiescence must be
        checked under the owner's migration lock (Proxy.drain does).

        An unhealthy instance is never idle: the strand sweep empties the
        queues BEFORE `on_fault` hands the victims to the supervisor, and in
        that gap an "idle" answer would let a drain settle on work that is
        mid-flight to the recovery path."""
        with self._cv:
            return self.healthy and not self._waiting \
                and not self._resident and self._admitting == 0

    def compile_cache_size(self) -> int:
        """Compiled-shape count of the batched step families — the recompile
        budget the shape buckets bound (tests assert <= |B buckets| x |KV
        widths| per family: the plain S=1 step and, under spec_decode, the
        fixed S=k+1 verify step — at most a factor of 2, never per-draft-
        length shapes)."""
        total, found = 0, False
        for fn in (self._step_ragged, self._step_verify):
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                found = True
                total += int(size())
        return total if found else -1

    # ------------------------------------------------- migration (the Proxy)
    def snapshot_load(self, instance_id: int,
                      step_time: Callable[[int, float], float]) -> DecodeLoad:
        """Planner view of this instance: the real slot cap (continuous batch
        width) plus the admission queue, so `DecodeLoad.effective_step` prices
        time-sharing beyond the cap exactly as the simulator does."""
        with self._cv:
            jobs = list(self._waiting)
            res = list(self._resident.values())
        ctx = sum(j.request.num_tokens + j.tokens_done for j in jobs) \
            + sum(j.request.num_tokens + j.tokens_done for j in res)
        if self.spec_decode:
            # migration gating prices per-ACCEPTED-token time: a step here
            # commits E[tokens/step] tokens, so the honest service rate is
            # the raw step time divided by the observed accept surface
            e = self._e_tokens()
            if e > 1.0:
                raw = step_time
                step_time = lambda b, c, _f=raw, _e=e: _f(b, c) / _e  # noqa: E731
        return DecodeLoad(instance_id=instance_id,
                          n_resident=len(res),
                          n_waiting=len(jobs), ctx_tokens=float(ctx),
                          max_batch=self.decode_max_batch,
                          step_time=step_time)

    def snapshot_candidates(self) -> List[DecodeCandidate]:
        """Queued (not resident) jobs as migration candidates."""
        with self._cv:
            jobs = list(self._waiting)
        return [DecodeCandidate(
            key=j.request.rid,
            context_tokens=float(j.request.num_tokens + j.tokens_done),
            remaining_tokens=float(j.target - j.tokens_done),
            deadline=j.request.decode_deadline,
            migrations=j.request.decode_migrations) for j in jobs]

    def take(self, rids: Sequence[int]) -> List[DecodeJob]:
        """Remove and return queued jobs by request id (migration departure).
        Jobs that became resident meanwhile are silently skipped — their KV
        is hot on this instance. An EVICTED stream whose KV still lives in
        the paged pool is gathered back into a dense handoff cache first."""
        want = set(rids)
        with self._cv:
            taken = [j for j in self._waiting if j.request.rid in want]
            self._waiting = [j for j in self._waiting
                             if j.request.rid not in want]
        # pool extraction waits on _kv_lock (up to one decode step) — do it
        # AFTER releasing _cv so the Proxy's submit/snapshot path never
        # stalls behind it; the popped jobs are invisible to the worker
        for job in taken:
            if job.request.rid in self._in_pool:
                self._extract_cache(job)
        return taken

    # ------------------------------------------------------------ lifecycle
    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify_all()
        self._thread.join(10.0)

    # ------------------------------------------------ supervised recovery
    def _supervised(self, loop: Callable[[], None]) -> None:
        """Worker wrapper: exceptions strand the instance instead of
        silently killing the thread; the thread survives for restart()."""
        with on_device(self.device):
            while True:
                try:
                    loop()
                    return                  # clean shutdown exit
                except Exception as exc:
                    self._on_worker_failure(exc)

    def _check_inject(self) -> None:
        """Chaos hook, called at the token boundary: raise a pending
        injected fault, or stall for a simulated hang."""
        inj = self._inject
        if inj is None:
            return
        self._inject = None
        if isinstance(inj, tuple) and inj and inj[0] == "hang":
            time.sleep(float(inj[1]))
            return
        raise inj if isinstance(inj, BaseException) \
            else RuntimeError(str(inj))

    def inject_fault(self, fault) -> None:
        with self._cv:
            self._inject = fault
            self._cv.notify_all()

    def _on_worker_failure(self, exc: Exception) -> None:
        """Idempotent strand: queued + resident jobs' requests return to
        `on_fault`; the paged pool is considered dead (recovery re-prefills
        from scratch, the simulator's KV-lost convention)."""
        with self._cv:
            if not self.healthy:
                return
            self.healthy = False
            self.last_error = exc
            self._epoch += 1
            victims = [j.request for j in self._resident.values()]
            victims += [j.request for j in self._waiting]
            self._resident.clear()
            self._waiting = []
            self._admitting = 0
            with self._kv_lock:
                self.kv = None              # pool died with the worker
            self._in_pool.clear()
            self._cv.notify_all()
        cb = self.on_fault
        if cb is not None:
            cb(victims, exc)                # outside _cv: Proxy re-enters

    def restart(self) -> None:
        with self._cv:
            self.healthy = True
            self.last_error = None
            self.last_progress = self.clock()
            self._cv.notify_all()

    @property
    def progress_ts(self) -> float:
        return self.last_progress

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until the instance is idle. Waits on the instance condition
        variable (the worker notifies on every completion) instead of the old
        5 ms busy-wait poll."""
        with self._cv:
            return self._cv.wait_for(
                lambda: not self._waiting and not self._resident
                and self._admitting == 0, timeout)

    # -------------------------------------------------------------- shared
    def _t_step(self, b: int, ctx: float) -> float:
        if self.step_pred is not None:
            return self.step_pred.step_time(b, ctx)
        return self._tbt_ema

    def _e_tokens(self, key: Optional[int] = None) -> float:
        """E[tokens committed per step] for S-EDF/budget pricing: the
        per-stream accept EMA when `key` has history, else the aggregate;
        exactly 1.0 with speculation off (all pricing unchanged)."""
        if not self.spec_decode:
            return 1.0
        if self.step_pred is not None:
            return self.step_pred.expected_tokens_per_step(key)
        return self._accept_tps if self._accept_tps > 0.0 else 1.0

    def _t_token(self, b: int, ctx: float,
                 key: Optional[int] = None) -> float:
        """Per-ACCEPTED-token service time — what TBT-deadline slack must be
        computed from: raw step time over expected tokens/step. Identical to
        `_t_step` without speculation."""
        return self._t_step(b, ctx) / self._e_tokens(key)

    def _observe_accept(self, rid: int, advance: int) -> None:
        """Record that one step committed `advance` tokens for stream rid."""
        if self.step_pred is not None:
            self.step_pred.observe_accept(rid, advance)
        a = 0.25 if self._accept_tps > 0.0 else 1.0
        self._accept_tps += a * (advance - self._accept_tps)

    def _entry(self, job: DecodeJob) -> DecodeEntry:
        return DecodeEntry(key=job.request.rid,
                           remaining_tokens=float(
                               job.target - job.tokens_done),
                           deadline=job.request.decode_deadline,
                           order=job.order)

    def _observe(self, b: int, ctx: float, tbt: float) -> None:
        a = 0.1 if self._tbt_ema > 0 else 1.0
        self._tbt_ema += a * (tbt - self._tbt_ema)
        if self.step_pred is not None:
            self.step_pred.observe(b, ctx, tbt)

    def _finish(self, job: DecodeJob, now: float) -> None:
        req = job.request
        req.finish_time = now
        req.mean_tpot = (now - job.enqueued) / max(job.target, 1)
        self.finished.append(req)
        if on() and req.submit_ns is not None:
            TRACER.record("request", req.submit_ns, now_ns(), rid=req.rid,
                          span_id=req.trace_id)

    @staticmethod
    def _took_slot(job: DecodeJob) -> None:
        """The stream just took a slot: close its `decode.queue` span."""
        if job.queued_ns and on():
            TRACER.record("decode.queue", job.queued_ns, now_ns(),
                          rid=job.request.rid, parent=job.request.trace_id)
        job.queued_ns = 0

    # ------------------------------------- single-stream worker (slot cap 1)
    def _pick_next_locked(self, now: float) -> DecodeJob:
        # caller holds _cv; _waiting is non-empty
        if len(self._waiting) == 1:
            return self._waiting.pop(0)
        ctx = sum(j.request.num_tokens + j.tokens_done
                  for j in self._waiting) / len(self._waiting)
        ranked = self.sched.rank([self._entry(j) for j in self._waiting],
                                 now, self._t_token(1, ctx))
        best = ranked[0].key
        for i, j in enumerate(self._waiting):
            if j.request.rid == best:
                return self._waiting.pop(i)
        return self._waiting.pop(0)       # unreachable; defensive

    def _should_yield(self, job: DecodeJob, now: float) -> bool:
        """Token-boundary preemption check: a strictly-higher-priority queued
        job displaces the running one."""
        if not (self.sched.policy == "s-edf" and self.sched.preempt):
            return False
        with self._cv:
            if not self._waiting:
                return False
            queued = list(self._waiting)
        ctx = job.request.num_tokens + job.tokens_done
        t_step = self._t_token(1, float(ctx), job.request.rid)
        own = self.sched.priority(self._entry(job), now, t_step)
        best = max(self.sched.priority(self._entry(j), now, t_step)
                   for j in queued)
        return best > own

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._waiting and not self._shutdown \
                        and self._inject is None:
                    self._cv.wait(0.1)
                if not self._waiting and self._inject is None:
                    return                     # shutdown with an empty queue
            self._check_inject()
            with self._cv:
                if not self._waiting:
                    continue
                job = self._pick_next_locked(self.clock())
                self._resident[job.request.rid] = job
                self._took_slot(job)
                epoch = self._epoch
            start = job.first_token if job.next_token is None \
                else job.next_token
            tok = jnp.asarray([start], jnp.int32)
            cache = job.cache
            last = self.clock()
            while job.tokens_done < job.target:
                logits, cache = self._step(self.params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                now = self.clock()
                self.tbt_samples.append(now - last)
                self.step_samples.append(now - last)  # 1 token/step: equal
                self.row_steps += 1
                self._observe(
                    1, float(job.request.num_tokens + job.tokens_done),
                    now - last)
                last = now
                self.last_progress = now
                job.tokens_done += 1
                job.cache = cache
                job.next_token = int(tok[0])
                self._check_inject()           # chaos: token-boundary fault
                if self._epoch != epoch:
                    # stranded mid-decode (the hang injection sleeps right
                    # above, and the watchdog may strand AND restart() may
                    # run before we wake): this job was already re-dispatched
                    # — finishing it here would complete the request twice
                    break
                if job.tokens_done < job.target and \
                        self._should_yield(job, now):
                    job.request.decode_preemptions += 1
                    self.preemptions += 1
                    with self._cv:
                        self._waiting.append(job)
                        self._resident.pop(job.request.rid, None)
                        self._cv.notify_all()
                    break
            else:
                self._finish(job, self.clock())
                with self._cv:
                    self._resident.pop(job.request.rid, None)
                    self._cv.notify_all()

    # --------------------------------- continuous-batching worker (slots > 1)
    def _ensure_pool_locked(self, job: DecodeJob, need_blocks: int) -> None:
        """Create the paged pool on first admission (sized for 2x the slot
        cap at this stream's footprint) or grow it when a larger stream
        arrives while nothing can be freed."""
        k = job.cache["k"]
        L_, K, hd = k.shape[0], k.shape[-2], k.shape[-1]
        if self.kv is None:
            blocks = max((2 * self.decode_max_batch + 1) * need_blocks + 1, 8)
            self.kv = PagedKVCache(L_, blocks, self.kv_block_size, K, hd,
                                   dtype=k.dtype,
                                   prefix_share=self.prefix_share,
                                   max_blocks=self.kv_max_blocks)
            # scratch sequence: the slot padding rows of the batched step
            # write into / gather from (never read through a kv_len mask)
            self.kv.allocate(_SCRATCH_SEQ, 1)

    def _ingest(self, job: DecodeJob, force: bool = False) -> bool:
        """Move a stream's KV into the paged pool (no-op for an evicted
        stream whose blocks stayed resident). False = pool genuinely cannot
        hold it right now; the job goes back to the queue. ``force`` grows
        the pool instead of declining — the no-resident deadlock guard,
        where waiting for another stream's completion to free blocks can
        never succeed. Takes only _kv_lock (prompt ingestion is device I/O);
        the caller owns the job exclusively while it is neither waiting nor
        resident (`_admitting` keeps drain/idle honest meanwhile)."""
        rid = job.request.rid
        if rid in self._in_pool:
            return True
        pos = int(job.cache["pos"])
        remaining = job.target - job.tokens_done
        need_tokens = pos + max(remaining, 1)
        if self.spec_decode:
            # draft headroom: a verify step scatters the FULL k+1 span (the
            # jit shape is static even when only part of it commits), so the
            # last step may touch positions up to final_len + draft_k
            need_tokens += self.draft_k
        need_blocks = (need_tokens + self.kv_block_size - 1) \
            // self.kv_block_size
        with self._kv_lock:
            self._ensure_pool_locked(job, need_blocks)
            if not self.kv.can_allocate(need_tokens):
                # stay queued only if the pool COULD fit this stream once
                # residents complete; a footprint larger than the whole pool
                # (minus the scratch block) would starve forever under
                # continuous load — grow for it now. Growth is geometric
                # (doubling at least) so pool-shape recompiles of the
                # jitted scatters stay O(log): see kvcache._scatter_prompt
                can_ever_fit = need_blocks <= self.kv.num_blocks - 1
                if can_ever_fit and self._in_pool and not force:
                    return False
                try:
                    # capped doubling (same growth as before when no
                    # kv_max_blocks is set)
                    self.kv.grow_for(need_blocks)
                except MemoryError:
                    if not force:
                        return False    # cap reached: stream stays queued
                                        # (visible backlog, not silent OOM)
                    # the no-resident deadlock guard must make progress:
                    # exceed the cap rather than wedge the instance
                    self.kv.grow(max(need_blocks, self.kv.num_blocks))
            self.kv.allocate(rid, need_tokens)
            sp = TRACER.begin("decode.ingest", rid=rid,
                              parent=job.request.trace_id, tokens=pos) \
                if on() else None
            self.kv.write_prompt(rid, job.cache["k"][:, 0, :pos],
                                 job.cache["v"][:, 0, :pos])
            if sp is not None:
                TRACER.end(sp)
        # the handoff cache's pos covers prompt + already-decoded tokens
        # (a migrated-in mid-stream job has tokens_done > 0), while the kv
        # position is computed as base_len + tokens_done — subtract so the
        # two bookkeepings agree
        job.base_len = pos - job.tokens_done
        job.cache = None                # the pool is now authoritative
        self._in_pool.add(rid)
        return True

    def _extract_cache(self, job: DecodeJob) -> None:
        """Gather an evicted stream's KV out of the pool back into the dense
        handoff-cache format (migration departure; caller owns the job —
        it is in neither the waiting list nor a slot). The dense view is
        padded to cover the REMAINING decode so a slot-cap-1 receiver (dense
        `decode_step`, which writes at `pos`) never runs off the cache."""
        # runs on the migrating thread: keep the pool's arrays on their device
        with on_device(self.device):
            rid = job.request.rid
            with self._kv_lock:
                k, v, length = self.kv.gather(rid)
                k = jax.block_until_ready(k)     # copy out before the worker's
                v = jax.block_until_ready(v)     # next donated scatter runs
                self.kv.free(rid)
            kv_len = job.base_len + job.tokens_done
            need = kv_len + max(job.target - job.tokens_done, 0) + 1
            keep = max(kv_len, int(length))
            k, v = k[:, None, :keep], v[:, None, :keep]
            if keep < need:
                pad = [(0, 0)] * k.ndim
                pad[2] = (0, need - keep)
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            job.cache = {"k": k, "v": v, "pos": jnp.asarray(kv_len, jnp.int32)}
            self._in_pool.discard(rid)

    def _plan_locked(self, now: float) -> List[DecodeJob]:
        """Token-boundary admission + eviction DECISIONS (caller holds _cv):
        one `select_batch` over residents + waiting picks the new resident
        set (the simulator's `DecodeSim._rebatch`, verbatim policy core).
        Pool-resident streams (evicted earlier) are admitted in place —
        free. NEW streams are popped from the queue, counted in
        `_admitting`, and returned for the caller to ingest OUTSIDE the
        condition variable: prompt ingestion is device I/O, and holding _cv
        across it would stall the Proxy's submit/snapshot/migration path."""
        everyone: Dict[int, DecodeJob] = dict(self._resident)
        for j in self._waiting:
            everyone[j.request.rid] = j
        if not everyone:
            return []
        total = len(everyone)
        b_eff = min(self.decode_max_batch, total)
        ctx = sum(j.request.num_tokens + j.tokens_done
                  for j in everyone.values())
        # per-accepted-token pricing: S-EDF slack compares deadline headroom
        # against remaining_tokens * t, so t must be time-per-COMMITTED-token
        t_step = self._t_token(b_eff, ctx / total)
        entries = [self._entry(j) for j in everyone.values()]
        batch, preempted = self.sched.select_batch(
            entries, set(self._resident), self.decode_max_batch, now, t_step)
        for rid in preempted:
            # slot eviction: progress, pool blocks, and next token all kept
            job = self._resident.pop(rid)
            job.request.decode_preemptions += 1
            self.preemptions += 1
            self._waiting.append(job)
        to_ingest: List[DecodeJob] = []
        claimed = set()
        for rid in batch:
            if rid in self._resident:
                continue
            job = everyone[rid]
            claimed.add(rid)
            if rid in self._in_pool:
                self._resident[rid] = job          # re-admission is free
            else:
                self._admitting += 1
                to_ingest.append(job)
        if claimed:
            self._waiting = [j for j in self._waiting
                             if j.request.rid not in claimed]
        return to_ingest

    def _bucket(self, n: int, buckets: Sequence[int]) -> int:
        for b in buckets:
            if b >= n:
                return b
        return buckets[-1]

    def _step_batch(self, jobs: List[DecodeJob]) -> None:
        """One jitted decode step over the whole resident batch: gather the
        resident KV views, run `decode_step_ragged` at the padded bucket
        shape, scatter the new K/V back in one batched write."""
        n = len(jobs)
        bb = self._bucket(n, self._b_buckets)
        seq_ids = [j.request.rid for j in jobs] + \
            [_SCRATCH_SEQ] * (bb - n)
        kv_lens = np.zeros(bb, np.int32)
        tokens = np.zeros(bb, np.int32)
        for i, j in enumerate(jobs):
            kv_lens[i] = j.base_len + j.tokens_done
            tokens[i] = j.first_token if j.next_token is None else j.next_token
        t0 = self.clock()
        sp = TRACER.begin("decode.step", rid=tuple(seq_ids[:n]), rows=n,
                          padded_rows=bb) if on() else None
        with self._kv_lock:
            # KV width bucket: power-of-two over the widest row's ALLOCATED
            # block count — gather_batch pads to at least the table width,
            # so bucketing the current kv_len instead would let per-stream
            # allocation sizes leak into the jitted shape (unbounded
            # recompiles)
            need_blocks = max(
                (len(self.kv.table(j.request.rid).blocks) for j in jobs),
                default=1)
            width = 1
            while width < need_blocks:
                width *= 2
            if sp is not None:
                part = TRACER.begin("decode.gather", rid=sp.span.rid,
                                    parent=sp.id)
            k_g, v_g, _ = self.kv.gather_batch(seq_ids, width)
            if sp is not None:
                TRACER.end(part)
                TRACER.count("decode.gather_bytes", k_g.nbytes + v_g.nbytes)
                part = TRACER.begin("decode.compute", rid=sp.span.rid,
                                    parent=sp.id)
            logits, k_new, v_new = self._step_ragged(
                self.params, jnp.asarray(tokens), k_g, v_g,
                jnp.asarray(kv_lens))
            next_tokens = np.asarray(jnp.argmax(logits, -1))
            if sp is not None:
                TRACER.end(part)
                part = TRACER.begin("decode.scatter", rid=sp.span.rid,
                                    parent=sp.id)
            self.kv.write_tokens(seq_ids, kv_lens.tolist(), k_new, v_new)
            if sp is not None:
                TRACER.end(part)
                TRACER.end(sp, width=width, kv_tokens=int(kv_lens[:n].sum()))
        # the next token cannot start before the scatter completes: stamp the
        # step AFTER write_tokens so observed dt matches what
        # profile_step_times measures (the prior the EMA calibrates against)
        now = self.clock()
        self.steps += 1
        self.last_progress = now
        dt = now - t0
        self.step_samples.append(dt)
        mean_ctx = float(kv_lens[:n].mean())
        self._observe(n, mean_ctx, dt)
        done: List[DecodeJob] = []
        self.row_steps += len(jobs)
        for i, j in enumerate(jobs):
            self.tbt_samples.append(dt)
            j.tokens_done += 1
            j.next_token = int(next_tokens[i])
            if self.spec_decode:
                # drafter corpus + accept surface stay current through the
                # plain-step fallback, or throttled streams would never see
                # their tokens/step settle to 1
                if j.history is None:
                    j.history = [int(tokens[i])]
                j.history.append(j.next_token)
                del j.history[:-_SPEC_HISTORY_CAP]
                self._observe_accept(j.request.rid, 1)
            if j.tokens_done >= j.target:
                done.append(j)
        self._retire_done(done, now)

    def _retire_done(self, done: List[DecodeJob], now: float) -> None:
        """Finish completed streams and release their pool blocks (shared
        tail of the plain and speculative batched steps)."""
        if not done:
            return
        with self._cv:
            for j in done:
                rid = j.request.rid
                if rid not in self._resident:
                    # stranded mid-step (watchdog fired while the jitted
                    # step compiled/ran): the request was re-dispatched —
                    # finishing it here would complete it twice
                    continue
                self._finish(j, now)
                self._resident.pop(rid, None)
                with self._kv_lock:
                    # a refcount DECREMENT, not an eager free: on a
                    # prefix-sharing pool blocks other streams still
                    # reference stay live, and trie-registered prompt
                    # blocks stay cached for the next matching prompt
                    if self.kv is not None:
                        self.kv.free(rid)
                self._in_pool.discard(rid)
                if self.step_pred is not None and self.spec_decode:
                    self.step_pred.forget_stream(rid)
            self._cv.notify_all()

    # -------------------------------------- speculative draft -> verify step
    def _draft_for(self, job: DecodeJob) -> List[int]:
        """Propose this step's draft for one stream (possibly empty).

        Adaptive throttle: when the stream's observed tokens/step EMA sits
        below `spec_throttle`, verification costs more latency than the
        committed tokens repay — draft nothing (the step then runs at plain
        shape) and re-probe every `spec_probe_period` steps in case the
        stream turned repetitive."""
        k = min(self.draft_k, job.target - job.tokens_done - 1)
        if k <= 0:
            return []
        rid = job.request.rid
        if self._e_tokens(rid) < self.spec_throttle:
            job.probe_in -= 1
            if job.probe_in > 0:
                return []
            job.probe_in = self.spec_probe_period
        if self.draft_fn is not None:
            d = [int(t) for t in self.draft_fn(rid, job.history, k)][:k]
        else:
            d = _ngram_draft(job.history, k)[:k]
        self.draft_proposed += len(d)
        return d

    def _spec_step_batch(self, jobs: List[DecodeJob]) -> None:
        """One speculative decode step: draft per row, ONE jitted k+1-wide
        verify pass (`decode_verify_ragged`) over the batch, greedy
        acceptance, multi-token commit with rejected-KV rollback by length
        truncation. When EVERY row drafts empty (throttled / no n-gram
        match) the step delegates to the plain `_step_batch` — graceful
        degradation to plain cost is what the fig27 low-accept gate holds."""
        for j in jobs:
            start = j.first_token if j.next_token is None else j.next_token
            if j.history is None:
                j.history = [start]
        drafts = [self._draft_for(j) for j in jobs]
        if not any(drafts):
            self._step_batch(jobs)
            return
        n = len(jobs)
        S = self.draft_k + 1
        bb = self._bucket(n, self._b_buckets)
        seq_ids = [j.request.rid for j in jobs] + [_SCRATCH_SEQ] * (bb - n)
        kv_lens = np.zeros(bb, np.int32)
        tokens = np.zeros((bb, S), np.int32)
        for i, (j, d) in enumerate(zip(jobs, drafts)):
            kv_lens[i] = j.base_len + j.tokens_done
            tokens[i, 0] = j.first_token if j.next_token is None \
                else j.next_token
            # short/empty drafts leave zero-padding in the tail columns:
            # their logits are computed but the acceptance scan below stops
            # at len(d), so they are never committed
            for s, t in enumerate(d):
                tokens[i, 1 + s] = t
        t0 = self.clock()
        with self._kv_lock:
            # pre-extend each row's block table to cover the FULL span the
            # verify step scatters (kv_len + S tokens) BEFORE gathering, so
            # the gathered width includes the draft positions (ingestion
            # already reserves draft_k headroom; this is the cheap invariant
            # check that keeps a migrated-in table safe)
            for i, j in enumerate(jobs):
                rid = j.request.rid
                need = int(kv_lens[i]) + S
                table = self.kv.table(rid)
                if len(table.blocks) * self.kv_block_size < need:
                    self.kv.extend(rid, need - table.length)
            need_blocks = max(
                (len(self.kv.table(j.request.rid).blocks) for j in jobs),
                default=1)
            width = 1
            while width < need_blocks:
                width *= 2
            k_g, v_g, _ = self.kv.gather_batch(seq_ids, width)
            logits, k_new, v_new = self._step_verify(
                self.params, jnp.asarray(tokens), k_g, v_g,
                jnp.asarray(kv_lens))
            greedy = np.asarray(jnp.argmax(logits, -1))       # (bb, S)
            # greedy acceptance: commit the longest draft prefix that
            # matches the argmax chain, plus the one token the verify pass
            # proves — bit-identical to plain greedy decoding by the
            # decode_verify_ragged column contract
            counts = [0] * bb              # scratch rows commit nothing
            advances = [1] * n
            for i, (j, d) in enumerate(zip(jobs, drafts)):
                a = 0
                while a < len(d) and d[a] == int(greedy[i, a]):
                    a += 1
                advances[i] = min(a + 1, j.target - j.tokens_done)
                counts[i] = advances[i]
            self.kv.write_token_span(seq_ids, kv_lens.tolist(), counts,
                                     k_new, v_new)
        now = self.clock()
        self.steps += 1
        self.spec_steps += 1
        self.last_progress = now
        dt = now - t0
        self.step_samples.append(dt)
        self._observe(n, float(kv_lens[:n].mean()), dt)
        done: List[DecodeJob] = []
        self.row_steps += n
        for i, (j, d) in enumerate(zip(jobs, drafts)):
            adv = advances[i]
            emitted = [int(greedy[i, s]) for s in range(adv)]
            j.history.extend(emitted)
            del j.history[:-_SPEC_HISTORY_CAP]
            j.tokens_done += adv
            j.next_token = emitted[-1]
            self.draft_accepted += adv - 1
            self._observe_accept(j.request.rid, adv)
            # per-accepted-token TBT: one sample per committed token so
            # percentile TBT gates stay meaningful at tokens/step > 1
            for _ in range(adv):
                self.tbt_samples.append(dt / adv)
            if j.tokens_done >= j.target:
                done.append(j)
        self._retire_done(done, now)

    def _run_batched(self) -> None:
        while True:
            with self._cv:
                t_wait = 0
                while not self._waiting and not self._resident \
                        and not self._shutdown and self._inject is None:
                    t_wait = t_wait or now_ns()
                    self._cv.wait(0.1)
                if t_wait and on():
                    TRACER.record("decode.wait", t_wait, now_ns())
                if self._shutdown and not self._waiting \
                        and not self._resident:
                    return
            self._check_inject()
            with self._cv:
                to_ingest = self._plan_locked(self.clock())
            for job in to_ingest:                  # device I/O: no _cv held
                ok = self._ingest(job)
                with self._cv:
                    self._admitting -= 1
                    if ok:
                        self._resident[job.request.rid] = job
                        self._took_slot(job)
                    else:
                        self._waiting.append(job)
            with self._cv:
                force_job = None
                if not self._resident and self._waiting:
                    # deadlock guard: nothing is decoding, so no completion
                    # can ever free blocks for the declined admissions above
                    # — force the top-ranked stream in (grows the pool)
                    force_job = self._pick_next_locked(self.clock())
                    self._admitting += 1
            if force_job is not None:
                self._ingest(force_job, force=True)
                with self._cv:
                    self._admitting -= 1
                    self._resident[force_job.request.rid] = force_job
                    self._took_slot(force_job)
            with self._cv:
                batch = sorted(self._resident.values(), key=lambda j: j.order)
            if not batch:
                time.sleep(0.001)
                continue
            if self.spec_decode:
                self._spec_step_batch(batch)
            else:
                self._step_batch(batch)


def profile_step_times(params, cfg, *, batch_sizes: Sequence[int] = (1, 2, 4, 8),
                       ctx: int = 256, decode_tokens: int = 16,
                       warmup: int = 2, kv_block_size: int = 128,
                       attn_impl: str = "naive",
                       clock: Callable[[], float] = time.monotonic,
                       ) -> List[Tuple[int, float, float]]:
    """Measure the REAL batched decode step over a sweep of batch sizes.

    Drives `decode_step_ragged` + `PagedKVCache` directly (no threads): for
    each B, B synthetic streams with `ctx` prompt tokens decode
    `decode_tokens` tokens; the MEDIAN per-token wall time after `warmup`
    steps is recorded (robust to host scheduler jitter — decode steps are
    milliseconds, one descheduling would dominate a mean).
    Returns ``[(B, mean_context, seconds_per_step)]`` —
    the samples `DecodeStepPredictor.from_profile` fits its measured
    step-time prior from (the profiled replacement for the analytic
    `DecodeCostModel.step_time` seed), and the data behind
    benchmarks/fig21_decode_batching.py.
    """
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    L_ = cfg.num_layers
    step = jax.jit(lambda p, t, kg, vg, kl: decode_step_ragged(
        p, cfg, t, kg, vg, kl, attn_impl=attn_impl))
    samples: List[Tuple[int, float, float]] = []
    rng = np.random.default_rng(0)
    for bsz in batch_sizes:
        tokens_cap = ctx + decode_tokens + warmup + 1
        blocks_per = (tokens_cap + kv_block_size - 1) // kv_block_size
        kv = PagedKVCache(L_, bsz * blocks_per + 1, kv_block_size, K, hd,
                          dtype=jnp.bfloat16)
        for s in range(bsz):
            kv.allocate(s, tokens_cap)
            kprompt = jnp.asarray(
                rng.standard_normal((L_, ctx, K, hd)), jnp.bfloat16)
            vprompt = jnp.asarray(
                rng.standard_normal((L_, ctx, K, hd)), jnp.bfloat16)
            kv.write_prompt(s, kprompt, vprompt)
        width = 1
        while width * kv_block_size < tokens_cap:
            width *= 2
        seq_ids = list(range(bsz))
        toks = np.asarray(rng.integers(0, cfg.vocab_size, bsz), np.int32)
        lens = np.full(bsz, ctx, np.int32)
        elapsed: List[float] = []
        ctx_timed: List[float] = []
        for it in range(decode_tokens + warmup):
            t0 = clock()
            k_g, v_g, _ = kv.gather_batch(seq_ids, width)
            logits, k_new, v_new = step(params, jnp.asarray(toks), k_g, v_g,
                                        jnp.asarray(lens))
            toks = np.asarray(jnp.argmax(logits, -1), np.int32)
            kv.write_tokens(seq_ids, lens.tolist(), k_new, v_new)
            t1 = clock()
            if it >= warmup:
                elapsed.append(t1 - t0)
                ctx_timed.append(float(lens.mean()))   # ctx the step RAN at
            lens += 1
        samples.append((bsz, float(np.mean(ctx_timed)),
                        float(np.median(elapsed))))
    return samples
