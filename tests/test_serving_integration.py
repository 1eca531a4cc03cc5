"""End-to-end real-execution serving tests (threads + jitted segments on CPU).

Covers: the Fig. 7 signal/ACK protocol under the real Execution Pool, the
Fig. 8 two-request scenario (submit -> preempt -> submit -> resume), blocking
time bounded by one operator, event-driven round counting (<= 2 per request),
and FlowPrefill vs FCFS SLO attainment on a heterogeneous mini-trace.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_tiny_config
from repro.core import Request, RequestState, SchedulerCore, TTFTPredictor
from repro.models import init_params
from repro.models.segments import SegmentedPrefill
from repro.serving.decode_instance import DecodeInstance
from repro.serving.prefill_instance import PrefillInstance
from repro.serving.proxy import Proxy

# A model big enough that a long prefill takes O(seconds) on one CPU core,
# so preemption effects are unambiguous.
import dataclasses

CFG = dataclasses.replace(get_tiny_config("llama3_8b"),
                          num_layers=2, d_model=128, d_ff=256)
MAX_SEQ = 4096
LONG, SHORT = 4096, 128


@pytest.fixture(scope="module")
def served_model():
    params = init_params(CFG, jax.random.PRNGKey(0))
    # offline TTFT profile fit (the paper's predictor methodology, §6.4);
    # doubles as compile warm-up for the shapes the tests serve
    ex = SegmentedPrefill(params, CFG, max_seq=MAX_SEQ, granularity="op",
                          chunk_tokens=512)
    xs = [128, 512, 1024, 2048, 4096]
    pred = TTFTPredictor.fit(xs, ex.profile(xs), degree=2)
    return params, pred, ex


def make_instance(params, pred, executor, policy="s-edf", **kw):
    core = SchedulerCore(predictor=pred, policy=policy,
                         batch_budget=kw.pop("batch_budget", 200),
                         enable_batching=kw.pop("enable_batching", False))
    return PrefillInstance(params, CFG, core, max_seq=MAX_SEQ,
                           attn_impl="xla", executor=executor)


def rand_tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)


def test_fig8_two_request_scenario(served_model):
    """Request A (long, relaxed SLO) starts; B (short, strict SLO) arrives
    mid-prefill; FlowPrefill must preempt A, serve B within its SLO, then
    resume and complete A.

    DEFLAKED: the bounds are calibrated from THIS machine's fitted prefill
    profile (the fixture's predictor) rather than hard-coded seconds — under
    full-suite CPU contention the old 1.0s/1.2s constants tripped even
    though the scheduling behaviour (B served operator-bounded, far before
    A's remaining prefill) was correct. The logical claims are unchanged:
    B's TTFT is its own compute plus operator-bounded blocking, NOT A's
    remaining prefill time."""
    params, pred, ex = served_model
    # machine-calibrated scale: the fitted uncontended 4096-token prefill
    # and the per-operator slice of it (blocking is bounded by in-flight
    # operators, so the tolerance must scale with operator cost)
    t_long = float(pred.predict(LONG))
    op_time = t_long / ex.start(jnp.zeros((1, LONG), jnp.int32)).total_segments
    # B's SLO: generous contention headroom over its own compute + a few
    # operators of blocking — but never looser than the paper's 1s scenario
    # on a fast machine
    slo_b = max(1.0, 6 * float(pred.predict(SHORT)) + 12 * op_time)
    inst = make_instance(params, pred, ex)
    try:
        A = Request(num_tokens=LONG, slo=60.0, arrival=time.monotonic(),
                    task_type="file")
        inst.submit_request(A, rand_tokens(LONG, 1))
        time.sleep(0.3)                      # let A start prefilling
        B = Request(num_tokens=SHORT, slo=slo_b, task_type="text",
                    arrival=time.monotonic())
        inst.submit_request(B, rand_tokens(SHORT, 2))
        assert inst.drain(120.0), "instance did not drain"

        b_ttft, a_ttft = B.ttft, A.ttft
        assert B.state == RequestState.DONE and A.state == RequestState.DONE
        assert b_ttft < slo_b, \
            f"B TTFT {b_ttft:.3f}s missed its {slo_b:.2f}s SLO"
        assert a_ttft > b_ttft, "A (preempted) must finish after B"
        # preemption actually happened and blocking was bounded
        assert len(inst.blocking_stats.samples) >= 1
        # bound: (dispatch_depth + 1) in-flight operators, with contention
        # headroom — scaled by the measured operator cost, floored at the
        # old absolute bound so a fast machine still enforces it
        assert inst.blocking_stats.max < max(1.2, 15 * op_time), \
            f"blocking {inst.blocking_stats.max:.3f}s not operator-bounded"
    finally:
        inst.shutdown()


def test_event_driven_round_count(served_model):
    """Scheduling rounds <= 2 per request (arrival + completion), regardless
    of operator granularity — the decoupling claim (§6.4)."""
    params, pred, ex = served_model
    inst = make_instance(params, pred, ex)
    try:
        n = 6
        for i in range(n):
            r = Request(num_tokens=SHORT, slo=30.0,
                        arrival=time.monotonic())
            inst.submit_request(r, rand_tokens(SHORT, i))
        assert inst.drain(120.0)
        # rounds = arrivals + completions; batching can only reduce completions
        assert inst.scheduling_rounds <= 2 * n
    finally:
        inst.shutdown()


def test_preempted_task_result_unchanged(served_model):
    """A preempted-and-resumed prefill must produce the same first-token
    logits as an uninterrupted run (through the full threaded runtime).

    DEFLAKED (the test_fig8 pattern): B's SLO and the warm-up wait are
    calibrated from THIS machine's fitted prefill profile instead of
    hard-coded (slo=1.0, sleep 0.3s). Under full-suite CPU contention the
    1.0s SLO could rank B as doomed — and a doomed B never preempts A, so
    the test silently stopped exercising the preempt-resume path it exists
    to pin. The logical claim is unchanged: A is interrupted mid-prefill
    and its resumed logits bit-match the uninterrupted reference."""
    params, pred, ex_shared = served_model
    toks = rand_tokens(LONG, 7)

    # uninterrupted reference via the bare executor
    want = ex_shared.run_all(ex_shared.start(jnp.asarray(toks[None], jnp.int32)))

    # machine-calibrated scale (see test_fig8): per-operator cost from the
    # fitted long-prefill latency, B's SLO generous over its own compute
    t_long = float(pred.predict(LONG))
    op_time = t_long \
        / ex_shared.start(jnp.zeros((1, LONG), jnp.int32)).total_segments
    slo_b = max(1.0, 6 * float(pred.predict(SHORT)) + 12 * op_time)

    inst = make_instance(params, pred, ex_shared)
    try:
        A = Request(num_tokens=LONG, slo=60.0, arrival=time.monotonic(),
                    task_type="file")
        inst.submit_request(A, toks)
        # wait until A is genuinely mid-prefill (state RUNNING plus a few
        # operators' worth of progress) so B's arrival forces a real
        # interruption — a fixed 0.3s could fall before A's first operator
        # under contention, turning this into an uninterrupted run
        deadline = time.monotonic() + 60.0
        while A.state != RequestState.RUNNING \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert A.state == RequestState.RUNNING, "A never started prefilling"
        time.sleep(max(0.05, 4 * op_time))
        B = Request(num_tokens=SHORT, slo=slo_b, arrival=time.monotonic())
        inst.submit_request(B, rand_tokens(SHORT, 8))
        assert inst.drain(120.0)
        # B preempted A at an operator boundary: blocking was observed and
        # stayed operator-bounded (the test is vacuous without this)
        assert len(inst.blocking_stats.samples) >= 1
        assert inst.blocking_stats.max < max(1.2, 15 * op_time)
        done = {t.head.rid: t for t in inst.completed_tasks}
        got = done[A.rid].prefill_task.logits
        np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                                   rtol=1e-5, atol=1e-5)
    finally:
        inst.shutdown()


def test_flowprefill_beats_fcfs_on_heterogeneous_trace(served_model):
    """Mini QwenTrace-like mix: short/strict + long/relaxed. FlowPrefill
    (S-EDF + op preemption) must beat FCFS on strict-SLO attainment.

    DEFLAKED: the strict SLO is calibrated from THIS machine's fitted
    prefill profile (the test_fig8 pattern) instead of a hard-coded 1.0s —
    under full-suite CPU contention the constant tripped FlowPrefill's
    attainment even though preemption served every short request far ahead
    of the long prefill. The SLO must stay BELOW the long prefill's
    remaining time (or FCFS would trivially pass too, erasing the
    contrast), so it is capped at a fraction of the fitted long-prefill
    latency — the discrimination window the scenario is built around."""
    params, pred, ex = served_model
    t_long = float(pred.predict(LONG))
    op_time = t_long / ex.start(jnp.zeros((1, LONG), jnp.int32)).total_segments
    # headroom over the short request's own compute + operator-bounded
    # blocking; floored at the paper's 1s scenario, capped well inside the
    # long prefill so FCFS's head-of-line wait still violates it
    slo_text = min(max(1.0, 6 * float(pred.predict(SHORT)) + 12 * op_time),
                   0.6 * t_long)

    def run(policy):
        inst = make_instance(params, pred, ex, policy=policy)
        reqs = []
        try:
            # one long request, then a stream of short strict ones
            long_r = Request(num_tokens=LONG, slo=60.0, task_type="file",
                             arrival=time.monotonic())
            inst.submit_request(long_r, rand_tokens(LONG, 100))
            reqs.append(long_r)
            time.sleep(0.2)
            for i in range(4):
                r = Request(num_tokens=SHORT, slo=slo_text, task_type="text",
                            arrival=time.monotonic())
                inst.submit_request(r, rand_tokens(SHORT, 200 + i))
                reqs.append(r)
                time.sleep(0.05)
            assert inst.drain(180.0)
        finally:
            inst.shutdown()
        text = [r for r in reqs if r.task_type == "text"]
        return sum(r.slo_met for r in text) / len(text)

    att_flow = run("s-edf")
    att_fcfs = run("fcfs")
    assert att_flow > att_fcfs, (att_flow, att_fcfs)
    assert att_flow == 1.0, f"FlowPrefill text attainment {att_flow}"


def test_pd_pipeline_with_decode(served_model):
    """Full proxy -> prefill -> decode handoff produces finished requests."""
    params, pred, ex = served_model
    inst = make_instance(params, pred, ex)
    dec = DecodeInstance(params, CFG, decode_tokens=4)
    proxy = Proxy([inst], [dec])
    try:
        for i in range(3):
            r = Request(num_tokens=SHORT, slo=30.0, arrival=time.monotonic())
            proxy.submit(r, rand_tokens(SHORT, 300 + i))
        assert proxy.drain(120.0)
        # DEFLAKED (test_fig8 pattern: calibrate, don't hard-code): drain's
        # atomic decode-idle observation already implies the finish list is
        # complete, so the old fixed `time.sleep(1.0)` only added a flake
        # window under full-suite contention. Keep a machine-calibrated
        # grace loop for the cross-thread list append instead: bounded by
        # the fitted prefill profile, exits immediately when done.
        deadline = time.monotonic() + max(1.0, 10 * float(pred.predict(SHORT)))
        while len(dec.finished) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(dec.finished) == 3
        assert all(r.finish_time is not None for r in dec.finished)
        rep = proxy.report()
        assert rep["n_requests"] == 3
    finally:
        proxy.shutdown()
