"""90th percentile of the prefill queue wait of the requests due in the
traced window: from the prefill instance taking the request (ARRIVAL) to
the pool starting its task (the program's `prefill.queue` span), in
milliseconds. A request due in the window and still queued at its close
counts its wait up to the close."""
import numpy as np

from harness import spans


def read(run):
    sp = spans.window(run)
    if sp is None:
        return None
    waits = {s.rid: s.duration_ns * 1e-6
             for s in spans.named(sp, "prefill.queue")}
    out = [waits.get(r.rid, (run.end - r.arrival) * 1e3)
           for _, r, _ in run.sent if run.start <= r.arrival < run.end]
    if not out:
        return None
    return float(np.percentile(out, 90.0))
