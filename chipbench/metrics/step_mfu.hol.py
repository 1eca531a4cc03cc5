"""Model FLOPs the window's served tokens need (prefill and decode, causal
attention included, from shapes) over the device's busy seconds in the
traced window times the chip's bf16 peak, in percent. Open-loop cells."""
from harness.work import step_mfu


def read(run):
    return step_mfu(run)
