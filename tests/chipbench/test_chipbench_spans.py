"""The readers of the program's spans (`harness.spans` and the metrics
that use it), on a hand-built window: known spans, a known device trace at
a known clock offset."""
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH  # noqa: F401  (puts chipbench/ on the path)
from harness import spans, spec, trace
from repro.core import tracing

S = 1_000_000_000                       # ns per second
MS = 1_000_000
OFF = 5_000_000_123                     # device clock - program clock


def _at(t):
    return int(t * S)


def _window():
    """Spans of a 10 s window (10 s to 20 s on the program's clock), the
    device trace of it, and the Run a reader sees."""
    tr = tracing.Tracer()
    rec = tr.record
    # request 1: in and out inside the window; 2: still open at the close;
    # 3: decodes beside 1; 0: due before the window
    rec("request", _at(11.0), _at(12.0), rid=1, span_id=101)
    rec("request", _at(12.5), _at(13.5), rid=3, span_id=103)
    rec("prefill.queue", _at(8.0), _at(8.2), rid=0)
    rec("prefill.queue", _at(11.0), _at(11.05), rid=1)
    rec("prefill.queue", _at(12.5), _at(12.6), rid=3)
    rec("handoff", _at(11.5), _at(11.51), rid=1)
    rec("handoff", _at(13.0), _at(13.001), rid=3)
    rec("decode.step", _at(11.52), _at(11.53), rid=(1,))
    rec("decode.step", _at(13.004), _at(13.02), rid=(1, 3))
    rec("decode.step", _at(16.0), _at(16.12), rid=(2,))
    # the decode step programs ran 20 us after each compute span opened and
    # ended 30 us before it closed: the offset lies in [OFF-30us, OFF+20us]
    rec("decode.compute", _at(11.3), _at(11.4), rid=(1,))
    rec("decode.compute", _at(16.0), _at(16.1), rid=(2,))
    rec("prefill.sync", _at(11.6), _at(11.8), rid=1)
    dev = trace.DeviceTrace(
        ops=[("%fusion.1 = bf16[8]", _at(11.2) + OFF, _at(11.6) + OFF),
             ("%fusion.2 = bf16[8]", _at(15.5) + OFF, _at(19.0) + OFF)],
        modules=[("jit_decode_step(7)", _at(11.3) + OFF + 20_000,
                  _at(11.4) + OFF - 30_000),
                 ("jit_prefill_attn(8)", _at(15.5) + OFF, _at(15.9) + OFF),
                 ("jit_decode_step(7)", _at(16.0) + OFF + 25_000,
                  _at(16.1) + OFF - 40_000)])

    def req(rid, due, finish):
        return (None, SimpleNamespace(rid=rid, arrival=due, submit_ns=_at(due),
                                      finish_time=finish), due)
    sent = [req(0, 8.0, 9.0), req(1, 11.0, 12.0), req(3, 12.5, 13.5),
            req(2, 15.0, None)]
    run = SimpleNamespace(start=10.0, end=20.0, seconds=10.0, sent=sent,
                          device_trace=dev)
    return tr, run


@pytest.fixture
def window(monkeypatch):
    tr, run = _window()
    monkeypatch.setattr(tracing, "TRACER", tr)
    monkeypatch.setattr(tracing, "on", lambda: True)
    tr.count("decode.gather_bytes", 9_000_000)
    return tr, run


def test_align_recovers_the_offset(window):
    tr, run = window
    assert spans.align(tr.spans(), run.device_trace) == \
        (OFF - 5_000, 50_000, 1.0)
    # a session that caught extra programs at its start: the pairing
    # shifts and the bracket is the same
    extra = [("jit_decode_step(7)", _at(t) + OFF, _at(t + 0.01) + OFF)
             for t in (9.0, 9.5, 10.0)]
    dev = trace.DeviceTrace(ops=run.device_trace.ops,
                            modules=extra + run.device_trace.modules)
    assert spans.align(tr.spans(), dev) == (OFF - 5_000, 50_000, 1.0)
    # a bracket wider than 1 ms is no offset
    wide = trace.DeviceTrace(ops=[], modules=[
        ("jit_decode_step(7)", _at(11.3) + OFF + 2 * MS,
         _at(11.4) + OFF - 2 * MS)])
    assert spans.align(tr.spans(), wide) is None


def test_align_outvotes_a_stray_pair():
    """Of three spans, one's program reads 2 ms early (a clock sync step
    on the device, say): the other two fix the offset."""
    tr = tracing.Tracer()
    mods = []
    for k, t in enumerate((1.0, 2.0, 3.0)):
        tr.record("decode.compute", _at(t), _at(t + 0.01), rid=(k,))
        early = 2 * MS if k == 1 else 0
        mods.append(("jit_decode_step(1)", _at(t) + OFF + 10_000 - early,
                     _at(t + 0.01) + OFF - 10_000 - early))
    off, width, agree = spans.align(tr.spans(),
                                    trace.DeviceTrace(modules=mods))
    assert (off, width) == (OFF, 20_000)
    assert agree == pytest.approx(2 / 3)


def test_one_sided_bounds_narrow_the_bracket():
    """Decode steps bracket the offset to +-10 us; gathers that start 3 us
    after their span opened and a prefill head whose sync returned 2 us
    after it finished narrow it to [-2 us, +3 us]. An extra gather program
    at the session's start shifts that pairing, and does not loosen it."""
    tr = tracing.Tracer()
    mods = [("jit__gather_blocks(2)", _at(0.5) + OFF, _at(0.51) + OFF)]
    for k, t in enumerate((1.0, 2.0, 3.0)):
        tr.record("decode.gather", _at(t), _at(t + 0.001), rid=(k,))
        mods.append(("jit__gather_blocks(2)", _at(t) + OFF + 3_000 + k,
                     _at(t + 0.002) + OFF))
        tr.record("decode.compute", _at(t + 0.001), _at(t + 0.01), rid=(k,))
        mods.append(("jit_decode_step(1)", _at(t + 0.001) + OFF + 10_000,
                     _at(t + 0.01) + OFF - 10_000))
    tr.record("prefill.sync", _at(4.0), _at(4.1), rid=9, wait_for="logits")
    mods.append(("jit_prefill_head(3)", _at(4.05) + OFF,
                 _at(4.1) + OFF - 2_000))
    off, width, agree = spans.align(tr.spans(),
                                    trace.DeviceTrace(modules=mods))
    assert (off - OFF, width, agree) == (500, 5_000, 1.0)


def test_a_contradicting_bound_is_not_taken():
    """A lower bound above the upper bound already taken would leave no
    offset: the bracket keeps the first and ignores it."""
    tr = tracing.Tracer()
    mods = []
    for k, t in enumerate((1.0, 2.0)):
        tr.record("decode.gather", _at(t), _at(t + 0.001), rid=(k,))
        mods.append(("jit__gather_blocks(2)", _at(t) + OFF + 3_000,
                     _at(t + 0.002) + OFF))
        tr.record("decode.compute", _at(t + 0.001), _at(t + 0.01), rid=(k,))
        mods.append(("jit_decode_step(1)", _at(t + 0.001) + OFF + 10_000,
                     _at(t + 0.01) + OFF - 10_000))
    tr.record("prefill.sync", _at(4.0), _at(4.1), rid=9, wait_for="logits")
    mods.append(("jit_prefill_head(3)", _at(4.05) + OFF,
                 _at(4.1) + OFF + 6_000))
    off, width, _ = spans.align(tr.spans(), trace.DeviceTrace(modules=mods))
    assert (off - OFF, width) == (-3_500, 13_000)


def test_prefill_segments_narrow_the_bracket():
    """Two decode steps bracket the offset to +-50 us. Prefill segments
    (qkv_proj, attn, ..., head) dispatch one program each every 10 ms to a
    device that takes 15 ms a program: the first starts 5 us after its
    span opened, and each window sync returns when the program two
    segments back ends, one of them 3 us after it. The device also traced
    one program before the session's first span. The bracket narrows to
    [-3 us, +5 us]."""
    tr = tracing.Tracer()
    mods = [("jit_prefill_attn(4)", _at(4.0) + OFF, _at(4.001) + OFF)]
    for k, t in enumerate((1.0, 2.0)):
        tr.record("decode.compute", _at(t), _at(t + 0.01), rid=(k,))
        mods.append(("jit_decode_step(1)", _at(t) + OFF + 50_000,
                     _at(t + 0.01) + OFF - 50_000))
    ops = ["qkv_proj", "attn"] * 3 + ["head"]
    dev_end = []
    for k, op in enumerate(ops):
        t0 = _at(5.0) + k * 10 * MS
        tr.record("prefill.segment", t0, t0 + 200_000, rid=7, op=op)
        start = max(t0 + OFF + (5_000 if k == 0 else 100_000),
                    dev_end[-1] if dev_end else 0)
        dev_end.append(start + 15 * MS)
        mods.append((f"jit_prefill_{op}(5)", start, dev_end[-1]))
        if k >= 2:
            wake = 3_000 if k == 4 else 40_000
            tr.record("prefill.sync", t0 + 300_000,
                      dev_end[k - 2] - OFF + wake, rid=7, wait_for="window")
    off, width, _ = spans.align(tr.spans(), trace.DeviceTrace(modules=mods))
    assert (off - OFF, width) == (1_000, 8_000)


def test_interval_arithmetic():
    assert spans.subtract([(0, 10), (20, 30)], [(2, 3), (5, 22), (29, 40)]) \
        == [(0, 2), (3, 5), (22, 29)]
    assert spans.subtract([(0, 10)], []) == [(0, 10)]
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def _read(name, run):
    return spec.metric_reader(name)(run)


def test_readers_on_a_known_window(window, capsys):
    _, run = window
    # queue waits of the requests due in the window: 50 ms, 100 ms, and
    # request 2, never started, counted to the close (5 s)
    assert _read("prefill_queue_p90_ms", run) == \
        pytest.approx(float(np.percentile([50.0, 100.0, 5000.0], 90)))
    # handoff to the first decode step holding the request: 20 ms, 4 ms
    assert _read("handoff_p90_ms", run) == \
        pytest.approx(float(np.percentile([20.0, 4.0], 90)))
    assert _read("decode_step_p50_ms", run) == pytest.approx(16.0)
    assert _read("decode_gather_mb_per_step", run) == pytest.approx(3.0)
    # in hand: [11, 12] and [12.5, 13.5] and request 2 from 15 s to the
    # close; busy [11.2, 11.6] and [15.5, 19]: idle 0.6 + 1 + 1.5 = 3.1 s
    assert _read("idle_with_work_pct", run) == pytest.approx(31.0)
    err = capsys.readouterr().err
    assert "offset bracket" in err and "under no span" in err
    # what the host was doing in those 3.1 s (each span shifted 5 us by
    # the bracket's midpoint): waiting on prefill syncs 0.2 s, queued
    # prefills 0.15 s, a handoff 1 ms, a decode step 16 ms
    _, table = spans.idle_with_work(run)
    assert table["bracket_ms"] == pytest.approx(0.05)
    got = table["by_span_s"]
    assert got["prefill.sync"] == pytest.approx(0.2, abs=1e-5)
    assert got["prefill.queue"] == pytest.approx(0.15, abs=1e-5)
    assert got["handoff"] == pytest.approx(0.001, abs=1e-5)
    assert got["decode.step"] == pytest.approx(0.016, abs=1e-5)
    assert table["no_span_s"] == pytest.approx(3.1 - 0.367, abs=1e-4)


NEW = ["prefill_queue_p90_ms", "handoff_p90_ms", "decode_step_p50_ms",
       "decode_gather_mb_per_step", "idle_with_work_pct"]


@pytest.mark.parametrize("name", NEW)
def test_readers_refuse_a_buffer_that_dropped_spans(window, name):
    tr, run = window
    assert _read(name, run) is not None
    tr.dropped = 1
    assert _read(name, run) is None


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_in_a_program_without_spans(monkeypatch, name):
    """A program without the tracer (as before spans existed) reads None
    and does not raise."""
    _, run = _window()
    monkeypatch.setattr(spans, "tracer", lambda: None)
    assert _read(name, run) is None


def test_new_metrics_are_listed_for_the_cell():
    bench = spec.benchmark(BENCH.parent)
    listed = spec.metrics_for(bench, "qwen2-1.5b.hol-mix", "per_layer")
    for name in NEW:
        assert name in listed
        assert listed[name]["source"] in ("program_span", "program_counter")
