"""Share of the traced window in which the device ran no op while some
request was in hand (between `Proxy.submit` and its finish, the program's
`request` spans; a request still open at the close counts until the
close), in percent. The spans are put on the device trace's clock by the
offset `harness.spans.align` brackets. Prints, once, the bracket's width
and the idle-with-work seconds each span name overlaps, and the part no
span covers: what the host was doing while the device waited."""
from harness import spans


def read(run):
    got = spans.idle_with_work(run)
    if got is None:
        spans.log("idle with work: no spans, or no offset to the device "
                  "clock")
        return None
    pct, table = got
    spans.log(f"idle with work: {table['idle_with_work_s']!r}s of "
              f"{run.seconds!r}s; offset bracket {table['bracket_ms']!r}ms, "
              f"{table['pairs_agreeing']!r} of the decode steps agreeing")
    for name, s in table["by_span_s"].items():
        spans.log(f"  under {name}: {s!r}s")
    spans.log(f"  under no span: {table['no_span_s']!r}s")
    return pct
