"""Spans and counters of the serving path (`repro.core.tracing`): recorded
while, and only while, a JAX profiler session is on; each carries its rid
and the span that caused it; each appears in the profiler's host plane at
one constant offset from its in-memory stamps; the device programs carry
stable names."""
import collections
import dataclasses
import glob
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_tiny_config
from repro.core import Request, SchedulerCore, TTFTPredictor
from repro.core import tracing
from repro.core.tracing import TRACER
from repro.models import init_params
from repro.models.segments import CACHE, SegmentedPrefill
from repro.serving.decode_instance import DecodeInstance
from repro.serving.prefill_instance import PrefillInstance
from repro.serving.proxy import Proxy

CFG = dataclasses.replace(get_tiny_config("llama3_8b"), num_layers=2,
                          d_model=64, d_ff=128)
MAX_SEQ, LONG, SHORT = 1024, 1024, 64

# every span the served path records, and the spans that a batched step or
# an idle worker records with no request as their cause
NAMES = {"request", "prefill.queue", "sched.round", "prefill.preempt",
         "prefill.suspended", "prefill.segment", "prefill.sync", "handoff",
         "decode.queue", "decode.ingest", "decode.step", "decode.gather",
         "decode.compute", "decode.scatter", "prefill.wait", "decode.wait"}
NO_RID = {"prefill.wait", "decode.wait"}
NO_CAUSE = {"request", "decode.step", "prefill.wait", "decode.wait"}
# spans added whole after the fact (`Tracer.record`); the others are timed
# around their body (`Tracer.begin` / `end`)
RECORDED = {"request", "prefill.queue", "prefill.preempt",
            "prefill.suspended", "handoff", "decode.queue", "prefill.wait",
            "decode.wait"}


def _scenario(proxy, inst):
    """A long prompt, then a short one with an earlier deadline once the
    long one has dispatched a few segments: S-EDF preempts the long one,
    serves the short one, resumes the long one; both decode batched."""
    rng = np.random.default_rng(0)
    long = Request(num_tokens=LONG, slo=100.0, arrival=time.monotonic(),
                   output_tokens=4, tbt_slo=1.0)
    proxy.submit(long, rng.integers(0, CFG.vocab_size, LONG))
    t_end = time.monotonic() + 60
    while time.monotonic() < t_end:
        cur = inst.pool.current()
        if cur is not None and cur.prefill_task.cursor >= 5:
            break
        time.sleep(0.0005)
    short = Request(num_tokens=SHORT, slo=10.0, arrival=time.monotonic(),
                    output_tokens=6, tbt_slo=1.0)
    proxy.submit(short, rng.integers(0, CFG.vocab_size, SHORT))
    reqs = [long, short]
    while time.monotonic() < t_end:
        inst.completed_tasks.clear()
        if all(r.finish_time is not None for r in reqs):
            break
        time.sleep(0.005)
    assert all(r.finish_time is not None for r in reqs)
    return reqs


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The scenario once with no profiler session (it also compiles every
    shape), then once inside one. Returns what each left in the tracer."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, CFG, max_seq=MAX_SEQ, granularity="op",
                          chunk_tokens=64)
    pred = TTFTPredictor.fit([64, 512, 1024], [0.01, 0.1, 0.2])
    core = SchedulerCore(predictor=pred, policy="s-edf",
                         enable_batching=False)
    inst = PrefillInstance(params, CFG, core, max_seq=MAX_SEQ,
                           attn_impl="xla", executor=ex)
    dec = DecodeInstance(params, CFG, decode_max_batch=4,
                         batch_buckets=(1, 2, 4))
    proxy = Proxy([inst], [dec])
    try:
        TRACER.clear()
        _scenario(proxy, inst)
        untraced = (TRACER.spans(), TRACER.counters())
        blocking0 = len(inst.blocking_stats.samples)
        d = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(d)
        try:
            reqs = _scenario(proxy, inst)
        finally:
            jax.profiler.stop_trace()
        spans, counters = TRACER.spans(), TRACER.counters()
        dropped = TRACER.dropped
        blocking = inst.blocking_stats.samples[blocking0:]
    finally:
        proxy.shutdown()
        TRACER.clear()
    files = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1
    return dict(untraced=untraced, spans=spans, counters=counters,
                dropped=dropped, reqs=reqs, blocking=blocking,
                xplane=files[0])


def test_no_session_records_nothing(served):
    spans, counters = served["untraced"]
    assert spans == [] and counters == {}
    assert not tracing.on()


def test_session_records_every_span_with_rid_and_cause(served):
    spans = served["spans"]
    assert served["dropped"] == 0
    assert {s.name for s in spans} == NAMES
    ids = {s.id for s in spans}
    assert len(ids) == len(spans)
    rids = {r.rid for r in served["reqs"]}
    for s in spans:
        assert s.start_ns <= s.end_ns, s
        if s.name in NO_RID:
            continue
        got = set(s.rid) if isinstance(s.rid, tuple) else {s.rid}
        assert got and got <= rids, s
        if s.name not in NO_CAUSE:
            assert s.parent in ids, s
    # each request's spans hang from its own `request` span
    roots = {s.rid: s.id for s in spans if s.name == "request"}
    assert {r.rid: r.trace_id for r in served["reqs"]} == roots
    for s in spans:
        if s.name in ("prefill.queue", "handoff", "decode.queue",
                      "decode.ingest", "prefill.segment"):
            assert s.parent == roots[s.rid], s
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name in ("decode.gather", "decode.compute", "decode.scatter"):
            step = by_id[s.parent]
            assert step.name == "decode.step"
            assert step.start_ns <= s.start_ns <= s.end_ns <= step.end_ns
        if s.name == "prefill.preempt":
            assert by_id[s.parent].name == "sched.round"
            assert by_id[s.parent].args["preempt"] is True
        if s.name == "prefill.suspended":
            assert by_id[s.parent].name == "prefill.preempt"
    segs = [s for s in spans if s.name == "prefill.segment"]
    assert {s.args["op"] for s in segs} == \
        {"qkv_proj", "attn", "o_proj", "gate_up_proj", "down_proj", "head"}
    steps = [s for s in spans if s.name == "decode.step"]
    assert all(s.args["rows"] <= s.args["padded_rows"] for s in steps)
    assert served["counters"]["decode.gather_bytes"] > 0


def test_preemption_span_is_what_blocking_stats_records(served):
    """`prefill.preempt` and `BlockingStats` come from the same signal and
    ACK stamps."""
    pre = [s for s in served["spans"] if s.name == "prefill.preempt"]
    assert len(pre) == len(served["blocking"]) >= 1
    for s, dt in zip(pre, served["blocking"]):
        assert (s.end_ns - s.start_ns) * 1e-9 == pytest.approx(dt, abs=1e-12)


def _host_events(path):
    pd = jax.profiler.ProfileData.from_file(path)
    out = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in NAMES:
                    out[e.name].append((int(e.start_ns),
                                        int(e.start_ns + e.duration_ns),
                                        dict(e.stats)))
    return out


def test_spans_are_in_the_host_plane_at_one_offset(served):
    """Each span is also a profiler host event. Timed spans sit at one
    offset from their in-memory stamps, at both ends; a recorded span's
    event carries its stamps. A thread switch between the annotation's
    stamp and the tracer's can move one span by a scheduling quantum, so
    a few in a hundred may lie outside 0.2 ms."""
    host = _host_events(served["xplane"])
    diffs = []
    for name in NAMES:
        mine = sorted((s for s in served["spans"] if s.name == name),
                      key=lambda s: s.start_ns)
        theirs = sorted(host[name])
        assert len(theirs) == len(mine), name
        if name in RECORDED:
            assert [(int(st["start_ns"]), int(st["end_ns"]))
                    for _, _, st in sorted(theirs, key=lambda e:
                                           int(e[2]["start_ns"]))] == \
                [(s.start_ns, s.end_ns) for s in mine], name
            continue
        for s, (h0, h1, st) in zip(mine, theirs):
            assert str(st["rid"]) == str(s.rid)
            diffs += [h0 - s.start_ns, h1 - s.end_ns]
    off = statistics.median(diffs)
    near = sum(abs(d - off) <= 200_000 for d in diffs)
    assert near >= 0.98 * len(diffs), (near, len(diffs))


def test_buffer_is_bounded_and_counts_what_it_drops(tmp_path):
    tr = tracing.Tracer(capacity=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(6):
            with tr.span("x", rid=i) as sp:
                sp.set(i=i)
        tr.count("c", 3)
        tr.count("c")
    finally:
        jax.profiler.stop_trace()
    tr.count("c")                                   # no session: ignored
    assert [s.rid for s in tr.spans()] == [2, 3, 4, 5]
    assert [s.args["i"] for s in tr.spans()] == [2, 3, 4, 5]
    assert tr.dropped == 2 and tr.counters() == {"c": 4}
    assert tr.span("x") is tracing.NOOP


def test_device_programs_carry_stable_names():
    params = init_params(CFG, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, CFG, max_seq=128, granularity="op",
                          chunk_tokens=64)
    task = ex.start(jnp.zeros((1, 64), jnp.int32))
    cache = {k: task.state[k] for k in CACHE}
    act = {"h": ex._start_fn(params, task.state["tokens"]), "tmp": None}
    for seg, op in zip(ex._segments, ex.ops):
        text = seg.lower(params["layers"], cache, act, 0, 0).as_text()
        assert f"@jit_prefill_{op}" in text
        cache, act = seg(params["layers"], cache, act, 0, 0)
    assert "@jit_prefill_embed" in ex._start_fn.lower(
        params, task.state["tokens"]).as_text()
    assert "@jit_prefill_head" in ex._head_fn.lower(
        params, cache["h_full"], task.state["lens"]).as_text()
    assert SegmentedPrefill(params, CFG, max_seq=128, granularity="layer") \
        ._segments[0].__name__ == "prefill_layer"
    dec = DecodeInstance(params, CFG, decode_max_batch=2,
                         batch_buckets=(1, 2))
    try:
        assert dec._step_ragged.__name__ == "decode_step"
        assert dec._step.__name__ == "decode_step_dense"
        assert dec._step_verify.__name__ == "decode_verify"
        kv = jnp.zeros((CFG.num_layers, 1, 128, CFG.num_kv_heads,
                        CFG.resolved_head_dim), jnp.float32)
        text = dec._step_ragged.lower(
            params, jnp.zeros((1,), jnp.int32), kv, kv,
            jnp.ones((1,), jnp.int32)).as_text()
        assert "@jit_decode_step(" in text or "@jit_decode_step " in text
    finally:
        dec.shutdown()
