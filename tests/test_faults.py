"""Fault-tolerance tests: FaultPlan determinism/persistence, simulator churn
invariants (exactly-once terminal accounting under random fault
interleavings), and threaded-runtime chaos scenarios (crash+revive,
hang+watchdog, decode churn, naive-mode loss) against the real Proxy.

`run_sim_fault_case` is the scenario shared with tests/test_property.py:
fixed seeds drive it here so the invariants hold without hypothesis
installed; the property suite delegates to it with free rein over the seed
space.
"""
import dataclasses
import math
import threading
import time

import numpy as np
import pytest

from repro.core import Request, RequestState, SchedulerCore, TTFTPredictor
from repro.core.faults import FaultEvent, FaultPlan, merge_plans
from repro.sim.cluster import simulate_cluster

# --- FaultPlan: determinism, validation, persistence -------------------------


def test_generate_is_deterministic():
    a = FaultPlan.generate(7, n_instances=4, duration=60.0, rate=0.1)
    b = FaultPlan.generate(7, n_instances=4, duration=60.0, rate=0.1)
    assert a.events == b.events and a.seed == 7
    c = FaultPlan.generate(8, n_instances=4, duration=60.0, rate=0.1)
    assert a.events != c.events
    # schedule is time-sorted and in-range
    times = [e.time for e in a]
    assert times == sorted(times)
    assert all(0 <= e.time < 60.0 and 0 <= e.instance < 4 for e in a)


def test_plan_json_roundtrip_including_inf_duration():
    plan = FaultPlan(events=(
        FaultEvent(time=1.0, instance=0, kind="crash", duration=math.inf),
        FaultEvent(time=2.0, instance=1, kind="spot", notice=1.5,
                   duration=4.0),
        FaultEvent(time=3.0, instance=2, kind="slowdown", factor=3.0,
                   duration=2.0, target="decode"),
    ), seed=42)
    back = FaultPlan.from_json(plan.to_json())
    assert back == plan
    assert math.isinf(back.events[0].duration)


def test_from_spec_preset_seed_and_file(tmp_path):
    assert len(FaultPlan.from_spec("churn")) == 1
    assert FaultPlan.from_spec("seed:5").seed == 5
    p = tmp_path / "plan.json"
    p.write_text(FaultPlan.preset("gray").to_json())
    assert FaultPlan.from_spec(str(p)) == FaultPlan.preset("gray")
    with pytest.raises(ValueError, match="neither a preset"):
        FaultPlan.from_spec("no-such-preset")


def test_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(time=0.0, instance=0, kind="meteor")
    with pytest.raises(ValueError, match="unknown fault target"):
        FaultEvent(time=0.0, instance=0, target="gateway")
    with pytest.raises(ValueError, match="duration"):
        FaultEvent(time=0.0, instance=0, duration=0.0)
    with pytest.raises(ValueError, match="factor"):
        FaultEvent(time=0.0, instance=0, kind="slowdown", factor=1.0)
    # spot timing: serves through the notice, rejoins after the outage
    e = FaultEvent(time=10.0, instance=0, kind="spot", notice=2.0,
                   duration=5.0)
    assert e.down_at == 12.0 and e.up_at == 17.0


def test_merge_plans_time_sorted():
    m = merge_plans([FaultPlan.preset("churn", duration=30.0),
                     FaultPlan.preset("gray", duration=30.0)])
    times = [e.time for e in m]
    assert times == sorted(times) and len(m) == 3


# --- simulator churn: exactly-once terminal accounting -----------------------


def run_sim_fault_case(rng):
    """One random churn scenario through ClusterSim; asserts the invariants
    that must hold under ANY fault interleaving:

      * every request reaches EXACTLY one terminal state — served (has a
        first token) or DROPPED — never both, never neither;
      * counters conserve: served + lost + shed == submitted, and the
        result's shed/lost tallies match the per-request states;
      * with retry recovery, a loss only happens past the retry budget;
      * the run terminates with a finite makespan (no wedged instances).
    """
    n = int(rng.integers(20, 60))
    reqs = [Request(num_tokens=int(rng.integers(200, 8000)),
                    slo=float(rng.uniform(0.5, 6.0)),
                    arrival=round(float(rng.uniform(0.0, 20.0)), 3),
                    output_tokens=int(rng.integers(0, 24)),
                    tbt_slo=1.0)
            for _ in range(n)]
    decode = int(rng.integers(0, 3))
    plan = merge_plans([
        FaultPlan.generate(int(rng.integers(0, 2**31)), n_instances=3,
                           duration=25.0, rate=0.15, mean_outage=4.0),
        FaultPlan.generate(int(rng.integers(0, 2**31)),
                           n_instances=max(decode, 1), duration=25.0,
                           rate=0.1, mean_outage=3.0, target="decode"),
    ]) if decode else FaultPlan.generate(
        int(rng.integers(0, 2**31)), n_instances=3, duration=25.0,
        rate=0.15, mean_outage=4.0)
    max_retries = int(rng.integers(1, 5))
    shed_policy = ("off", "doomed-only", "budget")[int(rng.integers(0, 3))]
    res = simulate_cluster(
        "flowprefill", reqs, num_instances=3, decode_instances=decode,
        dispatch="least-loaded", fault_plan=plan, recovery="retry",
        max_retries=max_retries, retry_backoff=0.05, watchdog_s=1.0,
        shed_policy=shed_policy, shed_budget=1.5)

    assert len(res.requests) == n
    served = [r for r in res.requests if r.state is not RequestState.DROPPED]
    dropped = [r for r in res.requests if r.state is RequestState.DROPPED]
    for r in served:
        # terminal means actually served: a first token exists and, when the
        # request decodes, it finished
        assert r.first_token_time is not None
        if r.output_tokens and decode:
            assert r.finish_time is not None
    shed = [r for r in dropped if r.shed]
    lost = [r for r in dropped if not r.shed]
    for r in lost:   # loss only past the retry budget under retry recovery
        assert r.retries > max_retries
    for r in shed:   # shedding happens at admission, before any attempt
        assert r.retries == 0 and r.first_token_time is None
    assert res.shed_requests == len(shed)
    assert res.lost_requests == len(lost)
    assert len(served) + len(lost) + len(shed) == n
    assert math.isfinite(res.makespan)
    assert res.retries >= 0
    return res


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 17, 23, 1234, 99991])
def test_sim_fault_interleavings_exactly_once(seed):
    run_sim_fault_case(np.random.default_rng(seed))


def test_sim_faults_off_counters_zero():
    """The churn code paths are inert without a plan: zero fault counters
    and full service (the byte-equality of the fig baselines is gated in
    benchmarks; this is the cheap in-tree canary)."""
    rng = np.random.default_rng(5)
    reqs = [Request(num_tokens=int(rng.integers(500, 4000)), slo=5.0,
                    arrival=float(i) * 0.2) for i in range(20)]
    res = simulate_cluster("flowprefill", reqs, num_instances=2,
                           recovery="retry", watchdog_s=1.0,
                           shed_policy="off")
    assert res.retries == res.shed_requests == res.lost_requests == 0
    assert all(r.first_token_time is not None for r in res.requests)


def test_sim_naive_recovery_loses_stranded():
    """recovery="none" on a mid-trace crash with no rejoin loses exactly
    the stranded work, and the fault-tolerant run on the SAME plan and
    trace loses nothing."""
    reqs = [Request(num_tokens=2000, slo=10.0, arrival=float(i) * 0.5)
            for i in range(24)]
    plan = FaultPlan(events=(
        FaultEvent(time=3.0, instance=0, kind="crash", duration=math.inf),))
    naive = simulate_cluster("flowprefill", reqs, num_instances=2,
                             fault_plan=plan, recovery="none")
    ft = simulate_cluster("flowprefill", reqs, num_instances=2,
                          fault_plan=plan, recovery="retry")
    assert naive.lost_requests > 0
    assert ft.lost_requests == 0 and ft.retries >= naive.lost_requests
    assert ft.attainment >= naive.attainment


def test_sim_shedding_rejects_only_doomed():
    """Shedding engages only under overload, never with a loose budget, and
    rejecting the doomed tail does not hurt the requests that were
    admitted."""
    rng = np.random.default_rng(11)
    reqs = [Request(num_tokens=int(rng.integers(4000, 16000)), slo=0.8,
                    arrival=float(i) * 0.05) for i in range(40)]
    off = simulate_cluster("flowprefill", reqs, num_instances=2,
                           shed_policy="off")
    doomed = simulate_cluster("flowprefill", reqs, num_instances=2,
                              shed_policy="doomed-only")
    budget = simulate_cluster("flowprefill", reqs, num_instances=2,
                              shed_policy="budget", shed_budget=1.2)
    loose = simulate_cluster("flowprefill", reqs, num_instances=2,
                             shed_policy="budget", shed_budget=1e9)
    assert off.shed_requests == 0
    assert loose.shed_requests == 0     # a generous budget admits everything
    assert doomed.shed_requests > 0 and budget.shed_requests > 0
    # shedding the doomed tail must not hurt the admitted requests
    adm = [r for r in doomed.requests if not r.shed]
    adm_off = [r for r in off.requests if r.rid in {a.rid for a in adm}]
    att = sum(r.slo_met for r in adm) / max(len(adm), 1)
    att_off = sum(r.slo_met for r in adm_off) / max(len(adm_off), 1)
    assert att >= att_off


# --- threaded runtime chaos ---------------------------------------------------

jax = pytest.importorskip("jax")


from repro.configs.base import get_tiny_config          # noqa: E402
from repro.models import init_params                    # noqa: E402
from repro.models.segments import SegmentedPrefill      # noqa: E402
from repro.serving.decode_instance import DecodeInstance  # noqa: E402
from repro.serving.prefill_instance import PrefillInstance  # noqa: E402
from repro.serving.proxy import Proxy                   # noqa: E402

CFG = dataclasses.replace(get_tiny_config("llama3_8b"),
                          num_layers=2, d_model=64, d_ff=128)
MAX_SEQ = 512


@pytest.fixture(scope="module")
def chaos_model():
    params = init_params(CFG, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, CFG, max_seq=MAX_SEQ, granularity="op",
                          chunk_tokens=128)
    pred = TTFTPredictor(coeffs=np.array([1e-4, 0.0]), floor=0.0)
    return params, ex, pred


def _mk_prefill(params, ex, pred):
    core = SchedulerCore(predictor=pred, policy="s-edf",
                         enable_batching=False)
    return PrefillInstance(params, CFG, core, max_seq=MAX_SEQ,
                           attn_impl="xla", executor=ex)


def _assert_chaos_invariants(name, proxy, decs, reqs):
    """The runtime mirror of `run_sim_fault_case`'s invariants: exactly-once
    completion, conservation of finished+lost, and KV block accounting
    (only the decode scratch slot may remain resident after drain)."""
    rep = proxy.report()
    served = [r for r in reqs if r.state is not RequestState.DROPPED]
    fin_rids = [r.rid for d in decs for r in d.finished]
    assert len(fin_rids) == len(set(fin_rids)), \
        f"{name}: a request completed twice"
    for r in served:
        assert r.first_token_time is not None, \
            f"{name}: rid {r.rid} neither served nor declared lost"
    if decs:   # every served request made it through decode exactly once
        assert set(fin_rids) == {r.rid for r in served}
        assert all(r.finish_time is not None for r in served)
    assert len(served) + rep["lost_requests"] == len(reqs), \
        f"{name}: {len(served)} served + {rep['lost_requests']} lost " \
        f"!= {len(reqs)} submitted"
    assert rep["stranded_rids"] == [], \
        f"{name}: non-terminal requests left after drain"
    for d in decs:
        if d.kv is not None:
            live = d.kv.num_blocks - d.kv.free_blocks
            assert live <= 1, f"{name}: {live} KV blocks leaked after drain"
    return rep


def _run_chaos(params, ex, pred, *, n_prefill=2, n_decode=1, n_reqs=10,
               fault_at=4, fault=None, seed=0, drain_s=120.0, **proxy_kw):
    insts = [_mk_prefill(params, ex, pred) for _ in range(n_prefill)]
    decs = [DecodeInstance(params, CFG, decode_tokens=4, policy="fcfs")
            for _ in range(n_decode)]
    proxy = Proxy(insts, decs, dispatch="round-robin",
                  retry_backoff=0.02, retry_backoff_cap=0.2, **proxy_kw)
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for i in range(n_reqs):
            n = int(rng.integers(64, 256))
            r = Request(num_tokens=n, slo=30.0, arrival=time.monotonic(),
                        output_tokens=4 if n_decode else 0,
                        tbt_slo=5.0 if n_decode else None)
            reqs.append(r)
            proxy.submit(r, rng.integers(0, CFG.vocab_size, size=n))
            time.sleep(0.01)
            if i == fault_at and fault is not None:
                fault(proxy, insts, decs)
        assert proxy.drain(drain_s), "drain timed out mid-recovery"
        return proxy, insts, decs, reqs
    except BaseException:
        proxy.shutdown()
        raise


def test_runtime_no_fault_baseline(chaos_model):
    proxy, _, decs, reqs = _run_chaos(*chaos_model, fault=None)
    try:
        rep = _assert_chaos_invariants("no-fault", proxy, decs, reqs)
        assert rep["retries"] == rep["lost_requests"] == 0
        assert all(rep["instance_health"]["prefill"])
    finally:
        proxy.shutdown()


def test_runtime_crash_and_revive_recovers_all(chaos_model):
    def fault(proxy, insts, decs):
        proxy.kill_instance(0, "prefill")
        threading.Timer(0.3, proxy.revive_instance,
                        args=(0, "prefill")).start()

    proxy, _, decs, reqs = _run_chaos(*chaos_model, fault=fault)
    try:
        rep = _assert_chaos_invariants("crash+revive", proxy, decs, reqs)
        assert rep["lost_requests"] == 0        # stranded work re-dispatched
        assert rep["retries"] >= 1              # ... by charging retries
        assert all(rep["instance_health"]["prefill"])  # revive took
    finally:
        proxy.shutdown()


def test_runtime_decode_crash_recovers_all(chaos_model):
    def fault(proxy, insts, decs):
        # crash the decode instance only once it actually holds in-flight
        # work: under heavy external load no prefill may have completed by
        # the time the submit loop reaches the kill point, and crashing an
        # EMPTY decode instance strands nothing (retries would stay 0)
        deadline = time.monotonic() + 30.0
        while decs[0].idle() and time.monotonic() < deadline:
            time.sleep(0.005)
        proxy.kill_instance(0, "decode")
        threading.Timer(0.3, proxy.revive_instance,
                        args=(0, "decode")).start()

    proxy, _, decs, reqs = _run_chaos(*chaos_model, n_prefill=1, n_decode=2,
                                      fault=fault)
    try:
        rep = _assert_chaos_invariants("decode-crash", proxy, decs, reqs)
        # a decode-stranded request needs a FULL re-prefill (its KV died
        # with the instance), so recovery shows up as prefill retries
        assert rep["lost_requests"] == 0
        assert rep["retries"] >= 1
    finally:
        proxy.shutdown()


def test_runtime_hang_detected_by_watchdog(chaos_model):
    """A hung (not dead) worker makes no progress; the watchdog must strand
    its work, the supervisor auto-restarts it, and every request still
    finishes exactly once."""
    params, ex, pred = chaos_model

    # Calibrate the watchdog period to THIS machine under its CURRENT load
    # (the test_fig8 pattern): a fixed period cannot separate the injected
    # hang from an honest CPU-starvation stall when the whole suite (or a
    # loaded CI runner) competes for cores — a spuriously-stranded slow
    # instance then burns retry budget on work that was progressing. One
    # warm full prefill pass of the largest request is the yardstick for
    # "an honest stall"; the period must dwarf it, and the injected hang
    # must dwarf the period so detection stays unambiguous.
    wd = min(4.0, max(0.4, 25 * ex.profile([256])[0]))
    hang = max(1.0, 2.5 * wd)

    def fault(proxy, insts, decs):
        insts[0].inject_fault(("hang", hang))
        decs[0].inject_fault(("hang", hang))

    # max_retries must cover the whole hang: the zombie sleep outlasts one
    # watchdog+auto-restart cycle, so the watchdog legitimately re-strands
    # the same work several times before the worker wakes — and the sole
    # decode instance gives those requests nowhere else to go. Each fire
    # charges a retry; the default budget of 3 sits exactly at that cliff.
    # The invariant under test is detect-and-recover exactly-once, not
    # budget exhaustion (naive-mode covers loss), so the budget is sized
    # far past any plausible fire count.
    # drain budget scales with the hang: under heavy external load the
    # recovery storm legitimately takes several watchdog+restart cycles to
    # quiesce (a cap, not a sleep — the uncontended run still settles fast)
    proxy, _, decs, reqs = _run_chaos(*chaos_model, n_reqs=8, fault=fault,
                                      watchdog_s=wd,
                                      auto_restart_s=1.25 * wd,
                                      max_retries=50,
                                      drain_s=max(120.0, 30 * wd))
    try:
        rep = _assert_chaos_invariants("hang+watchdog", proxy, decs, reqs)
        assert rep["lost_requests"] == 0
        assert rep["retries"] >= 1              # watchdog fired at least once
    finally:
        proxy.shutdown()


def test_runtime_naive_mode_loses_stranded(chaos_model):
    """recovery="none" is the contrast case: a crash with no revive loses
    exactly the stranded requests, and the report names them."""
    params, ex, pred = chaos_model
    insts = [_mk_prefill(params, ex, pred) for _ in range(2)]
    proxy = Proxy(insts, [], dispatch="round-robin", recovery="none")
    rng = np.random.default_rng(0)
    reqs = []
    try:
        # pin instance 0 so its queue cannot drain before the kill (a warm
        # jit cache otherwise empties it between submit and crash)
        insts[0].inject_fault(("hang", 0.5))
        for i in range(8):
            n = int(rng.integers(64, 256))
            r = Request(num_tokens=n, slo=30.0, arrival=time.monotonic())
            reqs.append(r)
            proxy.submit(r, rng.integers(0, CFG.vocab_size, size=n))
        proxy.kill_instance(0, "prefill")   # strands its queued requests
        assert proxy.drain(60.0)
        rep = _assert_chaos_invariants("naive", proxy, [], reqs)
        assert rep["lost_requests"] > 0
        assert rep["lost_rids"] == sorted(
            r.rid for r in reqs if r.state is RequestState.DROPPED)
        # the healthy instance still served its share
        assert rep["lost_requests"] < len(reqs)
    finally:
        proxy.shutdown()


def test_runtime_shed_policy_rejects_doomed(chaos_model):
    """Proxy admission control mirrors the sim: with every instance busy
    and a predicted TTFT already past the SLO, a fresh arrival is shed
    (DROPPED + shed, never dispatched) instead of deepening the queue."""
    params, ex, pred = chaos_model
    insts = [_mk_prefill(params, ex, pred)]
    # a predictor that makes every request look doomed once one is queued
    slow_pred = TTFTPredictor(coeffs=np.array([1.0, 0.0]), floor=0.0)
    proxy = Proxy(insts, [], dispatch="round-robin",
                  shed_policy="doomed-only", predictor=slow_pred)
    try:
        rng = np.random.default_rng(3)
        reqs = []
        for i in range(4):
            r = Request(num_tokens=128, slo=0.5, arrival=time.monotonic())
            reqs.append(r)
            proxy.submit(r, rng.integers(0, CFG.vocab_size, size=128))
        assert proxy.drain(60.0)
        rep = proxy.report()
        shed = [r for r in reqs if r.shed]
        assert rep["shed_requests"] == len(shed) >= 1
        assert all(r.state is RequestState.DROPPED and
                   r.first_token_time is None for r in shed)
        # the first arrival found an empty instance: never shed
        assert not reqs[0].shed
        assert rep["lost_requests"] == 0
    finally:
        proxy.shutdown()
