"""K/V bytes the decode steps of the traced window copied in their dense
gather (the program's `decode.gather_bytes` counter, from the gathered
arrays' shapes and dtype), per `decode.step` span, in MB (1e6 bytes)."""
from harness import spans


def read(run):
    sp = spans.window(run)
    if sp is None:
        return None
    steps = len(spans.named(sp, "decode.step"))
    moved = spans.tracer().counters().get("decode.gather_bytes")
    if not steps or moved is None:
        return None
    return moved / steps * 1e-6
