"""90th percentile of the handoff time of the prefills completed in the
traced window: from the pool's completion of a request's prefill (the
start of the program's `handoff` span) to the start of the first
`decode.step` span that holds the request, in milliseconds. Neither the
TTFT stamp nor the TBT clock counts this time."""
import numpy as np

from harness import spans


def read(run):
    sp = spans.window(run)
    if sp is None:
        return None
    steps = spans.named(sp, "decode.step")
    out = []
    for h in spans.started_in(spans.named(sp, "handoff"), run):
        first = next((s for s in steps if s.start_ns >= h.start_ns
                      and h.rid in s.rid), None)
        if first is not None:
            out.append((first.start_ns - h.start_ns) * 1e-6)
    if not out:
        return None
    return float(np.percentile(out, 90.0))
