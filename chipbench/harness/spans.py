"""The program's own spans and counters (`repro.core.tracing`), as the
per-layer readers use them: the spans of the traced window, the offset that
puts them on the device trace's clock, and the device's idle time split by
whether requests were in hand.

The program records spans only while a profiler session is on, which in a
`--trace 1` run is the measured window. Spans are on the host's
`time.monotonic_ns()`; the device trace's events are on the profiler's
clock, one constant offset away within a session. A program that has no
tracer gives nothing to read: every function here then returns None.
"""
from __future__ import annotations

import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from harness import trace

Interval = Tuple[int, int]

ALIGN_SPAN = "decode.compute"        # dispatch of the decode step to argmax
ALIGN_PROGRAM = "jit_decode_step"    # the decode step's device program
MAX_WIDTH_NS = 1_000_000             # widest offset bracket still usable
# spans that open before a program is dispatched bound the offset from
# above ("start"); spans that close after the host waited for a program to
# finish bound it from below ("end"): (span, its wait_for arg, program)
BOUNDS = (("decode.gather", None, "jit__gather_blocks", "start"),
          ("decode.scatter", None, "jit__scatter_tokens", "start"),
          ("prefill.sync", "logits", "jit_prefill_head", "end"))
MAX_SHIFT = 64                       # events either side of k-th to k-th


def ns(t: float) -> int:
    """Host seconds on `time.monotonic()` -> nanoseconds."""
    return int(round(t * 1e9))


def tracer():
    """The program's process-wide tracer, or None without one."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing.TRACER


def window(run) -> Optional[list]:
    """Spans overlapping the run's window; None without a tracer or when
    its buffer dropped spans (then any reading would be partial)."""
    tr = tracer()
    if tr is None or tr.dropped:
        return None
    return tr.spans(ns(run.start), ns(run.end))


def named(spans, name: str) -> list:
    return sorted((s for s in spans if s.name == name),
                  key=lambda s: s.start_ns)


def started_in(spans, run) -> list:
    lo, hi = ns(run.start), ns(run.end)
    return [s for s in spans if lo <= s.start_ns < hi]


def _events(spans, dt, span, program, wait_for=None):
    """(host, dev): the spans' and the programs' (start, end) rows."""
    host = [(s.start_ns, s.end_ns) for s in named(spans, span)
            if wait_for is None or s.args.get("wait_for") == wait_for]
    dev = sorted((s, e) for n, s, e in dt.modules
                 if trace.module_key(n) == program)
    return (np.array(host, np.int64).reshape(-1, 2),
            np.array(dev, np.int64).reshape(-1, 2))


def _paired(host, dev):
    """(host rows, dev rows) paired k-th to k-th under each shift of the
    device's index, smallest shifts first."""
    for shift in sorted(range(-MAX_SHIFT, MAX_SHIFT + 1), key=abs):
        k0, k1 = max(0, -shift), min(len(host), len(dev) - shift)
        if k1 > k0:
            yield host[k0:k1], dev[k0 + shift:k1 + shift]


def align(spans,
          dt: trace.DeviceTrace) -> Optional[Tuple[float, int, float]]:
    """(offset, bracket width, share of pairs agreeing), offset and width
    in ns, device time = program time + offset.

    Each `decode.compute` span dispatches one `decode_step` program and
    ends after that program's result reached the host, so its program ran
    inside it: the pair allows offsets in [dev_end - host_end, dev_start -
    host_start]. Spans and programs are paired k-th to k-th, both being
    recorded in one session; the device may trace a few programs the
    session's spans miss at its edges, so shifted pairings are tried too.
    The pairing kept is the one with the most pairs agreeing on one
    offset, and the bracket is the intersection of the agreeing pairs'
    ranges. `BOUNDS` then narrow it from either side. The bracket's
    midpoint is returned; None when under half the spans agree or the
    bracket is wider than 1 ms. A bound that would leave no offset at all
    contradicts one already taken, and is not taken."""
    host, dev = _events(spans, dt, ALIGN_SPAN, ALIGN_PROGRAM)
    best = None
    for h, d in _paired(host, dev):
        got = _stab(d[:, 1] - h[:, 1], d[:, 0] - h[:, 0])
        if got is not None and (best is None or got[0] > best[0]):
            best = got
    if best is None or best[0] * 2 < len(host):
        return None
    n, lo, hi = best
    agree = n / len(host)
    # A one-sided pairing that matches a span with the wrong program is
    # valid only in the loose direction (a later program for an upper
    # bound, an earlier one for a lower bound): a wrong match the other way
    # moves the bound by a whole gap between programs, past the bracket.
    # So every pairing consistent with the bracket is valid; the tightest
    # is kept.
    lo0, hi0 = lo, hi
    for name, wait_for, program, side in BOUNDS:
        for h, d in _paired(*_events(spans, dt, name, program, wait_for)):
            if side == "start":
                b = d[:, 0] - h[:, 0]
                if (b >= lo0).all():
                    hi = _upper(lo, hi, int(b.min()))
            else:
                b = d[:, 1] - h[:, 1]
                if (b <= hi0).all():
                    lo = _lower(lo, hi, int(b.max()))
    lo, hi = _prefill_bounds(spans, dt, lo, hi, lo0, hi0)
    if hi - lo > MAX_WIDTH_NS:
        return None
    return (lo + hi) / 2, int(hi - lo), agree


def _prefill_bounds(spans, dt, lo, hi, lo0, hi0) -> Tuple[int, int]:
    """Narrow [lo, hi] with the prefill path, whose segments are many and
    often dispatched to an idle device. The pool thread dispatches one
    `jit_prefill_<op>` program per `prefill.segment` span (the embed at a
    chunk's start aside), so in order the programs' names match the spans'
    `op`s; of the pairings whose names all match and whose upper bounds
    agree with [lo0, hi0] (a wrong one is off by a whole layer), the
    tightest is kept. The `prefill.sync` of the dispatch window that
    follows segment j waits for segment j-2's program, which bounds the
    offset from below."""
    segs = named(spans, "prefill.segment")
    mods = sorted((s, e, trace.module_key(n)) for n, s, e in dt.modules
                  if trace.module_key(n).startswith("jit_prefill_")
                  and trace.module_key(n) != "jit_prefill_embed")
    if not segs or not mods:
        return lo, hi
    names = sorted({m[2] for m in mods} | {f"jit_prefill_{s.args['op']}"
                                            for s in segs})
    host = np.array([(s.start_ns, s.end_ns) for s in segs], np.int64)
    h_name = np.array([names.index(f"jit_prefill_{s.args['op']}")
                       for s in segs])
    dev = np.array([(m[0], m[1]) for m in mods], np.int64)
    d_name = np.array([names.index(m[2]) for m in mods])
    best = None
    for shift in range(-MAX_SHIFT, MAX_SHIFT + 1):
        k0, k1 = max(0, -shift), min(len(host), len(dev) - shift)
        if k1 <= k0 or (h_name[k0:k1] != d_name[k0 + shift:k1 + shift]).any():
            continue
        b = dev[k0 + shift:k1 + shift, 0] - host[k0:k1, 0]
        if (b >= lo0).all() and (best is None or b.min() < best[0]):
            best = (int(b.min()), shift)
    if best is None:
        return lo, hi
    hi = _upper(lo, hi, best[0])
    syncs = [s for s in named(spans, "prefill.sync")
             if s.args.get("wait_for") == "window"]
    j = np.searchsorted(host[:, 1], [s.start_ns for s in syncs],
                        "right") - 3 + best[1]
    ok = (j - best[1] >= 0) & (j >= 0) & (j < len(dev))
    b = dev[j[ok], 1] - np.array([s.end_ns for s in syncs], np.int64)[ok]
    if len(b) and (b <= hi0).all():
        lo = _lower(lo, hi, int(b.max()))
    return lo, hi


def _upper(lo: int, hi: int, bound: int) -> int:
    return min(hi, bound) if bound >= lo else hi


def _lower(lo: int, hi: int, bound: int) -> int:
    return max(lo, bound) if bound <= hi else lo


def _stab(lo: np.ndarray, hi: np.ndarray):
    """(count, lo, hi): the most ranges [lo_i, hi_i] holding one common
    point, and the intersection of those ranges."""
    ok = lo <= hi
    lo, hi = lo[ok], hi[ok]
    if not len(lo):
        return None
    pts = np.concatenate([lo, hi])
    step = np.concatenate([np.ones(len(lo), np.int64),
                           -np.ones(len(hi), np.int64)])
    order = np.lexsort((-step, pts))          # opens before closes at a tie
    depth = np.cumsum(step[order])
    i = int(np.argmax(depth))
    p = pts[order][i]
    held = (lo <= p) & (p <= hi)
    return int(depth[i]), int(lo[held].max()), int(hi[held].min())


# ---------------------------------------------------------- intervals

def clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals `a` outside those of `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def total(iv: Sequence[Interval]) -> int:
    return sum(e - s for s, e in iv)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    return total(a) - total(subtract(a, b))


def idle_with_work(run) -> Optional[Tuple[float, dict]]:
    """(percent of the window, what the host was doing) for the time the
    device ran no op while a request was in hand: between `Proxy.submit`
    and its finish (its `request` span), or from submit to the window's
    close for a request still open then. None without the spans or the
    offset."""
    spans = window(run)
    if spans is None:
        return None
    al = align(spans, run.device_trace)
    if al is None:
        return None
    off, width, agree = al
    lo, hi = ns(run.start), ns(run.end)
    done = {s.rid for s in spans if s.name == "request"}
    work = [(s.start_ns, s.end_ns) for s in spans if s.name == "request"]
    for _, r, _ in run.sent:
        sub = getattr(r, "submit_ns", None)
        if sub is not None and r.rid not in done and sub < hi and \
                (r.finish_time is None or r.finish_time >= run.end):
            work.append((sub, hi))
    work = clip(trace.union(work), lo, hi)
    # on the device's clock from here on
    shift = int(round(off))
    work = [(s + shift, e + shift) for s, e in work]
    busy = trace.union([(s, e) for _, s, e in run.device_trace.ops])
    idle = subtract(work, busy)
    by_name: Dict[str, List[Interval]] = defaultdict(list)
    for s in spans:
        if s.name != "request":
            by_name[s.name].append((s.start_ns + shift, s.end_ns + shift))
    covered = {n: overlap(idle, trace.union(iv)) * 1e-9
               for n, iv in by_name.items()}
    every = trace.union([iv for ivs in by_name.values() for iv in ivs])
    table = {"bracket_ms": width * 1e-6, "pairs_agreeing": agree,
             "idle_with_work_s": total(idle) * 1e-9,
             "by_span_s": dict(sorted(covered.items(),
                                      key=lambda kv: -kv[1])),
             "no_span_s": total(subtract(idle, every)) * 1e-9}
    return 100.0 * total(idle) / (hi - lo), table


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
