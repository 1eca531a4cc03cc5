"""95th percentile of the prefill execution pool's preemption blocking time
in the window: from a preemption signal to its acknowledgement at the next
operator boundary (`PrefillInstance.blocking_stats`), in milliseconds."""
import numpy as np


def read(run):
    if not run.blocking:
        return None
    return float(np.percentile(np.asarray(run.blocking) * 1e3, 95.0))
