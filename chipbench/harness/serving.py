"""The system under test, built as the launcher deploys it, warmed, and
driven by the traffic.

A `Proxy` in front of one `PrefillInstance` (S-EDF, operator-granularity
segmented prefill in 512-token chunks, Pallas kernel, bfloat16 KV state,
TTFT predictor fitted from `SegmentedPrefill.profile` in set-up) and one
`DecodeInstance` (batched ragged decode step, Pallas kernel), all on the one
chip, shedding off. Prefill batching is off: the program compiles one
prefill program per (rows, padded length) pair, and with it on the pairs a
run forms are not known before the window.

The harness observes the program from outside. The program exposes no
served tokens (a `Request` carries times, not tokens), so the harness wraps
what it must, and these names are the yardstick's dependencies on the
program:

- `DecodeInstance._step_ragged` (private) and `PagedKVCache.write_tokens`:
  which token each decode step fed at which position, and when;
- `ExecutionPool.preempt_current`: which requests were suspended
  mid-prompt;
- `PrefillInstance.completed_tasks`: emptied as prefills complete, since
  the program keeps every completed prefill's whole KV state there, which
  would exhaust the chip's memory within a window;
- in set-up only, `DecodeInstance._kv_lock`, `_b_buckets` and
  `PagedKVCache.gather_batch`, to warm each decode step shape.

`Recorder.install` fails loudly when a wrapped name is gone, rather than
letting a run record nothing.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Request, SchedulerCore, TTFTPredictor
from repro.models.segments import SegmentedPrefill
from repro.serving.decode_instance import DecodeInstance
from repro.serving.prefill_instance import PrefillInstance
from repro.serving.proxy import Proxy

SCRATCH_SEQ = -1            # the decode pool's padding sequence id
BATCH_BUDGET = 4096


@dataclass
class Recorder:
    """What the harness sees of the program while it serves."""
    steps: List[tuple] = field(default_factory=list)  # (t, seq_ids, pos, tokens)
    preempted: set = field(default_factory=set)
    _fed: Optional[jax.Array] = None

    def install(self, proxy: Proxy) -> None:
        dec = proxy.decode_instances[0]
        for obj, name in ((dec, "_step_ragged"), (dec.kv, "write_tokens"),
                          *((i.pool, "preempt_current")
                            for i in proxy.prefill_instances)):
            if not callable(getattr(obj, name, None)):
                raise AttributeError(f"{type(obj).__name__}.{name} is gone: "
                                     f"the harness records served tokens "
                                     f"and preemptions through it")
        step = dec._step_ragged

        def recording_step(p, tokens, kg, vg, kv_lens):
            self._fed = tokens
            return step(p, tokens, kg, vg, kv_lens)
        dec._step_ragged = recording_step
        write = dec.kv.write_tokens

        def recording_write(seq_ids, positions, k, v):
            self.steps.append((time.monotonic(), tuple(seq_ids),
                               tuple(positions), self._fed))
            return write(seq_ids, positions, k, v)
        dec.kv.write_tokens = recording_write
        for inst in proxy.prefill_instances:
            pool = inst.pool
            preempt = pool.preempt_current

            def recording_preempt(timeout=10.0, preempt=preempt):
                task = preempt(timeout)
                if task is not None:
                    self.preempted.update(r.rid for r in task.requests)
                return task
            pool.preempt_current = recording_preempt

    def served_tokens(self) -> Dict[int, Dict[int, int]]:
        """rid -> {position: token fed there} over every recorded step."""
        out: Dict[int, Dict[int, int]] = {}
        for _, seq_ids, pos, fed in self.steps:
            toks = np.asarray(fed)
            for i, rid in enumerate(seq_ids):
                if rid != SCRATCH_SEQ:
                    out.setdefault(rid, {})[pos[i]] = int(toks[i])
        return out


def build(params, pcfg, cell: dict, attn: str, profile_lens) -> Proxy:
    executor = SegmentedPrefill(params, pcfg, max_seq=int(cell["max_seq"]),
                                granularity="op",
                                chunk_tokens=int(cell["chunk"]),
                                attn_impl=attn, cache_dtype=jnp.bfloat16)
    xs = list(profile_lens)
    pred = TTFTPredictor.fit(xs, executor.profile(xs))
    core = SchedulerCore(predictor=pred, policy="s-edf",
                         batch_budget=BATCH_BUDGET, enable_batching=False)
    inst = PrefillInstance(params, pcfg, core, max_seq=int(cell["max_seq"]),
                           granularity="op", chunk_tokens=int(cell["chunk"]),
                           attn_impl=attn, executor=executor)
    dmb = int(cell["decode_max_batch"])
    buckets = tuple(b for b in (1, 2, 4, 8, 16, 32) if b <= dmb)
    dec = DecodeInstance(params, pcfg, decode_max_batch=dmb,
                         batch_buckets=buckets, attn_impl=attn)
    return Proxy([inst], [dec])


def reap(proxy: Proxy) -> None:
    """Drop the prefill states the program keeps after each handoff (one
    `list.clear`, atomic against the program's append)."""
    for inst in proxy.prefill_instances:
        inst.completed_tasks.clear()


def serve_blocking(proxy: Proxy, reqs, timeout: float) -> bool:
    """Submit (request, tokens) pairs at once and wait for all of them."""
    for r, toks in reqs:
        proxy.submit(r, toks)
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        reap(proxy)
        if all(r.finish_time is not None for r, _ in reqs):
            return True
        time.sleep(0.01)
    return False


def warm_decode(proxy: Proxy, batches, widths) -> None:
    """Run the decode instance's step programs once at every (rows bucket,
    width bucket) pair the traffic can form, on the pool's padding
    sequence, as `DecodeInstance._step_batch` calls them."""
    dec = proxy.decode_instances[0]
    with dec._kv_lock:
        for bb in batches:
            for w in widths:
                seq = [SCRATCH_SEQ] * bb
                k_g, v_g, _ = dec.kv.gather_batch(seq, w)
                logits, k_new, v_new = dec._step_ragged(
                    dec.params, jnp.asarray(np.zeros(bb, np.int32)), k_g, v_g,
                    jnp.asarray(np.zeros(bb, np.int32)))
                np.asarray(jnp.argmax(logits, -1))
                dec.kv.write_tokens(seq, [0] * bb, k_new, v_new)
    jax.block_until_ready(dec.kv.k_pool)


def make_request(prompt_len, output_len, ttft_slo, tbt_slo, task, due):
    return Request(num_tokens=prompt_len, slo=ttft_slo, arrival=due,
                   task_type=task, output_tokens=output_len, tbt_slo=tbt_slo)


class CompileCounter:
    """Counts executables JAX obtains (compiled or loaded from the
    persistent cache) after `arm()`: each is a program the window had not
    warmed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self.cache_loads = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name, secs, **kw):
        if self.armed and name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1

    def _on_event(self, name, **kw):
        if self.armed and name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_loads += 1

    def arm(self):
        self.armed = True
