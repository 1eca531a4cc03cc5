"""Median duration of the batched decode steps started in the traced
window (the program's `decode.step` span: gather the rows' K/V, run the
step, sync its argmax, scatter the new K/V), in milliseconds."""
import numpy as np

from harness import spans


def read(run):
    sp = spans.window(run)
    if sp is None:
        return None
    out = [s.duration_ns * 1e-6
           for s in spans.started_in(spans.named(sp, "decode.step"), run)]
    if not out:
        return None
    return float(np.median(out))
