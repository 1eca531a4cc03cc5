"""Reduction of a profiler trace (`*.xplane.pb`) to the numbers the
benchmark reports.

The device planes are `/device:TPU:<n>`. Their `XLA Ops` line holds one
event per HLO instruction executed, named by its HLO text
(`%flash_prefill_attention.1 = bf16[...] custom-call(...)`); the `XLA
Modules` line one event per program run (`jit_seg(<hash>)`). Busy time is
the union of the op intervals; a kernel's time is the sum of its events'
durations; an idle gap is a stretch between two op intervals, named by the
programs on either side of it.
"""
from __future__ import annotations

import glob
import gzip
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]          # (start_ns, end_ns)

_INSTR = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)?\s*=\s*([^{\s]*)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclass
class DeviceTrace:
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Tuple[str, int, int]] = field(default_factory=list)


def load(path: str) -> Dict[str, DeviceTrace]:
    """Device plane name -> its op and module events (name, start, end)."""
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, DeviceTrace] = {}
    for i in range(64):
        plane = pd.find_plane_with_name(f"/device:TPU:{i}")
        if plane is None:
            break
        dt = DeviceTrace()
        for line in plane.lines:
            if line.name == "XLA Ops":
                dt.ops = [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events]
            elif line.name == "XLA Modules":
                dt.modules = [(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                              for e in line.events]
        out[plane.name] = dt
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {files}")
    return files[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dt: DeviceTrace) -> int:
    return sum(e - s for s, e in union([(s, e) for _, s, e in dt.ops]))


def op_key(name: str) -> str:
    """`<instruction> <result type>` of an HLO op event, numbering dropped:
    `%copy.10 = bf16[28,1,8192,2,128]{...} copy(...)` ->
    `copy bf16[28,1,8192,2,128]`."""
    m = _INSTR.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def module_key(name: str) -> str:
    return _MODULE.match(name).group(1)


def kernel_ns(dt: DeviceTrace, kernel: str) -> Tuple[int, int]:
    """(summed duration, event count) of the ops of one Pallas kernel,
    matched by its instruction name."""
    pat = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)?\s*=")
    hits = [e - s for n, s, e in dt.ops if pat.match(n)]
    return sum(hits), len(hits)


def top_ops(dt: DeviceTrace, n: int = 10) -> List[Tuple[str, float]]:
    """The n op kinds that took most device time, in seconds."""
    tot: Dict[str, int] = defaultdict(int)
    for name, s, e in dt.ops:
        tot[op_key(name)] += e - s
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in best]


def idle_gaps(dt: DeviceTrace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle time between op intervals, summed by the programs that ran
    before and after the gap (`prev -> next`), the n largest, in seconds."""
    mods = sorted((s, e, module_key(nm)) for nm, s, e in dt.modules)
    busy = union([(s, e) for _, s, e in dt.ops])
    tot: Dict[str, int] = defaultdict(int)
    j = 0
    for (s0, e0), (s1, _) in zip(busy, busy[1:]):
        while j + 1 < len(mods) and mods[j + 1][0] <= e0:
            j += 1
        prev = mods[j][2] if mods and mods[j][0] <= e0 else "start"
        k = j
        while k < len(mods) and mods[k][0] < s1:
            k += 1
        nxt = mods[k][2] if k < len(mods) else "end"
        tot[f"{prev} -> {nxt}"] += s1 - e0
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in best]
