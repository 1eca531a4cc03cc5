"""The work the window served, counted from shapes, for the per-layer
metric readers: prompts whose first token came in the window, and every
decode row the window's steps ran."""
from __future__ import annotations

from typing import List, Tuple

from harness import flops


def prompts(run) -> List[int]:
    """Prompt lengths of the requests whose first token came in the window."""
    return [it.prompt_len for it, r, _ in run.sent
            if r.first_token_time is not None
            and run.start <= r.first_token_time < run.end]


def decode_rows(run) -> List[Tuple[int, List[int]]]:
    """Per decode step in the window: the positions its real rows fed."""
    return [(t, [p for _, p in rows]) for t, rows in run.steps
            if run.start <= t < run.end]


def step_mfu(run) -> float:
    """Model FLOPs of the window's served work over the device's busy time
    at the bf16 peak, in percent."""
    total = sum(flops.prefill_flops(run.family, run.cfg, n)
                for n in prompts(run))
    total += sum(flops.decode_flops(run.family, run.cfg, p)
                 for _, pos in decode_rows(run) for p in pos)
    if total == 0 or run.busy_s <= 0:
        return None
    return 100.0 * total / (run.busy_s * run.peaks["bf16_flops_per_s"])


def kernel_roofline(run, kernel: str, least_s: float):
    """Least time the work needs over the kernel's summed device time, in
    percent; None when the trace holds none of the kernel's events."""
    from harness import trace
    ns, n = trace.kernel_ns(run.device_trace, kernel)
    if n == 0 or least_s <= 0:
        return None
    return 100.0 * least_s / (ns * 1e-9)
