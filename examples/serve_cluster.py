"""Cluster-scale serving walkthrough: N prefill instances behind SLO-aware
dispatch, with decode-phase TPOT/TBT accounting — the multi-instance scenario
behind the paper's cluster-scale goodput claims.

Two modes share the SAME dispatch policy objects (repro.core.dispatch):

  default — calibrated discrete-event ClusterSim (fast, no model needed):
      PYTHONPATH=src python examples/serve_cluster.py [--instances 4]
          [--rate 24] [--burstiness 3] [--policy all]
          [--scenario fitted-chat]         # fitted/stress scenario trace
          [--hetero a800,a800,a100,a100]   # mixed-hardware pool
          [--decode-sched s-edf] [--decode-max-batch 16]
          [--decode-migration]             # TBT-slack-aware decode stage
          [--prefix-share]                 # shared-prefix trace + per-
          [--prefix-cache-blocks 2048]     # instance prefix KV caches

  --real  — a tiny REAL model on CPU: Proxy + N threaded PrefillInstances +
            a DecodeInstance, load-aware dispatch against live backlog
            (--prefix-share turns on the real prefix-sharing PagedKVCache:
            repeated prompts prefill suffix-only; add --scenario to replay
            a scenario's arrival pacing + hash-chained prompts against it):
      PYTHONPATH=src python examples/serve_cluster.py --real [--requests 10]

Chaos replay (--chaos churn | spot-wave | gray | seed:<int> | plan.json):
the SAME `FaultPlan` drives simulator instance churn and real fault
injection against the threaded pool (supervised recovery + watchdog,
docs/ARCHITECTURE.md) — both modes report retries / sheds / lost:
      PYTHONPATH=src python examples/serve_cluster.py --chaos churn
      PYTHONPATH=src python examples/serve_cluster.py --real --chaos gray
"""
import argparse

from repro.core.faults import FaultPlan
from repro.sim.cluster import simulate_cluster
from repro.traces.qwentrace import TraceConfig, generate

POLICIES = ["round-robin", "least-loaded", "deflection",
            "capacity-weighted", "decode-aware", "prefix-affinity"]


def _scenario_trace(args):
    from repro.traces.scenarios import SCENARIOS, scenario_names
    sc = SCENARIOS.get(args.scenario)
    if sc is None:
        raise SystemExit(f"unknown scenario {args.scenario!r}; "
                         f"known: {scenario_names()}")
    print(f"scenario {sc.name!r}: {sc.summary}")
    print(f"  punishes: {sc.punishes}")
    return generate(TraceConfig(scenario=args.scenario, rate=args.rate,
                                duration=args.duration, seed=args.seed))


def _chaos_plan(args, n_instances):
    if not args.chaos:
        return None
    plan = FaultPlan.from_spec(args.chaos, n_instances=n_instances,
                               duration=args.duration)
    print(f"chaos plan {args.chaos!r}: {len(plan)} fault event(s)")
    for e in plan:
        rejoin = "never" if e.up_at == float("inf") else f"{e.up_at:.1f}s"
        print(f"  t={e.time:6.1f}s  {e.kind:8s} {e.target}[{e.instance}]"
              + (f" notice={e.notice}s" if e.kind == "spot" else "")
              + (f" x{e.factor}" if e.kind == "slowdown" else "")
              + f"  rejoin={rejoin}")
    return plan


def run_sim(args):
    hardware = args.hetero.split(",") if args.hetero else None
    n = len(hardware) if hardware else args.instances
    pool = " hetero[" + args.hetero + "]" if hardware else ""
    spec = f", spec-decode k={args.draft_k} accept={args.spec_accept}" \
        if args.spec_decode else ""
    print(f"== ClusterSim: {n} prefill + {n} decode instances{pool}, "
          f"rate={args.rate} req/s, burstiness={args.burstiness}{spec} ==")
    plan = _chaos_plan(args, n)
    if args.scenario:
        # scenario traces bring their own fitted output/TBT/prefix shape;
        # they always carry hash chains, so the prefix caches go live
        reqs = _scenario_trace(args)
        cache_blocks = args.prefix_cache_blocks
    else:
        share = dict(shared_prefix_frac=0.25, multi_turn_prob=0.75) \
            if args.prefix_share else {}
        reqs = generate(TraceConfig(rate=args.rate, duration=args.duration,
                                    seed=args.seed,
                                    burstiness=args.burstiness,
                                    output_mean=200, tbt_slo=args.tbt_slo,
                                    **share))
        cache_blocks = args.prefix_cache_blocks if args.prefix_share else 0
    print(f"{len(reqs)} requests "
          f"({sum(r.num_tokens for r in reqs)} prefill tokens)"
          + (f", prefix caches {cache_blocks} blocks/instance"
             if cache_blocks else ""))
    policies = POLICIES if args.policy == "all" else [args.policy]
    fault_cols = f" {'retry':>5s} {'shed':>4s} {'lost':>4s}" \
        if plan or args.shed_policy != "off" else ""
    print(f"{'dispatch':>17s} | {'TTFT att':>8s} {'e2e att':>8s} "
          f"{'p99/SLO':>7s} {'imbalance':>9s} {'preempts':>8s} "
          f"{'dec-pre':>7s} {'migr':>4s} {'hit':>5s}{fault_cols} "
          f"| per-instance dispatched")
    for policy in policies:
        res = simulate_cluster("flowprefill", reqs,
                               num_instances=n, dispatch=policy,
                               decode_instances=n, hardware=hardware,
                               decode_hardware=hardware,
                               decode_policy=args.decode_sched,
                               decode_max_batch=args.decode_max_batch,
                               decode_migration=args.decode_migration,
                               prefix_cache_blocks=cache_blocks,
                               fault_plan=plan, recovery=args.recovery,
                               shed_policy=args.shed_policy,
                               shed_budget=args.shed_budget,
                               spec_decode=args.spec_decode,
                               draft_k=args.draft_k,
                               spec_accept=args.spec_accept)
        faults = f" {res.retries:5d} {res.shed_requests:4d} " \
                 f"{res.lost_requests:4d}" if fault_cols else ""
        print(f"{policy:>17s} | {res.attainment:8.3f} "
              f"{res.e2e_attainment:8.3f} {res.e2e_p99_norm:7.2f} "
              f"{res.imbalance:9.2f} "
              f"{res.preemptions:8d} {res.decode_preemptions:7d} "
              f"{res.migrations:4d} {res.prefix_hit_rate:5.2f}{faults} "
              f"| {res.dispatched}")


def run_real(args):
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.configs.base import get_tiny_config
    from repro.core import Request, SchedulerCore, TTFTPredictor
    from repro.models import init_params
    from repro.models.segments import SegmentedPrefill
    from repro.serving.decode_instance import DecodeInstance
    from repro.serving.prefill_instance import PrefillInstance
    from repro.serving.proxy import Proxy

    cfg = dataclasses.replace(get_tiny_config("llama3_8b"),
                              num_layers=2, d_model=128, d_ff=256)
    max_seq = 2048
    print(f"== real mode: {args.instances} PrefillInstances (tiny model, "
          f"CPU threads), dispatch={args.policy} ==")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, cfg, max_seq=max_seq, granularity="op",
                          chunk_tokens=512)
    xs = [256, 1024, 2048]                    # offline TTFT profile + warmup
    ys = ex.profile(xs)
    pred = TTFTPredictor.fit(xs, ys)

    policy = args.policy if args.policy != "all" else "least-loaded"
    # --prefix-share: per-instance prefix-sharing PagedKVCache (each
    # instance keeps its own trie; the executor itself is stateless and
    # stays shared) — resubmitted prompts prefill suffix-only
    insts = [PrefillInstance(
        params, cfg, SchedulerCore(predictor=pred, enable_batching=False),
        max_seq=max_seq, executor=ex,
        prefix_share=args.prefix_share,
        prefix_cache_blocks=args.prefix_cache_blocks)
        for _ in range(args.instances)]
    # the decode flags apply here too: --decode-sched picks the instances'
    # admission policy, --decode-max-batch the continuous-batching slot cap
    # (the REAL batched jitted step, paged KV), --decode-migration needs
    # >= 2 decode instances
    n_dec = 2 if args.decode_migration else 1
    # --spec-decode: the REAL speculative path (self-drafting n-gram drafter
    # + one batched k+1-position verify pass per step, bit-identical greedy
    # output); longer outputs give the drafter history to match against
    out_tokens = 16 if args.spec_decode else 2
    decs = [DecodeInstance(params, cfg, decode_tokens=2,
                           policy=args.decode_sched,
                           decode_max_batch=max(args.decode_max_batch, 1),
                           spec_decode=args.spec_decode,
                           draft_k=args.draft_k)
            for _ in range(n_dec)]
    # wire the hetero-pool signals so capacity-weighted / decode-aware run
    # against real measurements, not silent 1.0/0.0 defaults: capacity from
    # the measured profile (identical executors -> identical capacities),
    # decode pressure priced by the analytic decode model for this config
    from repro.sim.costmodel import A800, DecodeCostModel, ModelSpec
    cap = xs[-1] / ys[-1]                  # measured prefill tokens/s
    plan = _chaos_plan(args, args.instances)
    has_hang = plan is not None and any(e.kind == "hang" for e in plan)
    proxy = Proxy(insts, decs, dispatch=policy,
                  capacities=[cap] * args.instances,
                  decode_cost=DecodeCostModel(ModelSpec.from_config(cfg),
                                              A800),
                  decode_migration=args.decode_migration,
                  recovery=args.recovery,
                  shed_policy=args.shed_policy,
                  shed_budget=args.shed_budget,
                  # hangs are only detectable by the watchdog; generous
                  # period so tiny-model jit compiles don't false-positive
                  watchdog_s=2.0 if has_hang else 0.0)
    rng = np.random.default_rng(args.seed)

    # replay the plan in request-index space: event time t maps to "after
    # submission floor(t / duration * requests)", so a fault scheduled
    # mid-trace lands mid-stream regardless of real-mode pacing. Outages
    # are capped at 5s (the demo run is seconds, not the sim's minutes).
    import threading
    chaos_by_i = {}
    revive_timers = []
    if plan is not None:
        for e in plan:
            i = min(int(e.time / args.duration * args.requests),
                    args.requests - 1)
            chaos_by_i.setdefault(i, []).append(e)

    def fire(e):
        kind, idx = e.target, e.instance
        j = idx % (args.instances if kind == "prefill" else len(decs))
        outage = min(e.duration, 5.0)
        if e.kind in ("crash", "spot"):
            # spot notice is sub-second here; treat both as a kill + rejoin
            proxy.kill_instance(j, kind)
            t = threading.Timer(outage, proxy.revive_instance, args=(j, kind))
            t.daemon = True
            t.start()
            revive_timers.append(t)
            print(f"  [chaos] {e.kind} {kind}[{j}] (rejoin in {outage:.1f}s)")
        elif e.kind == "hang":
            target = insts[j] if kind == "prefill" else decs[j]
            target.inject_fault(("hang", min(e.duration, 2.0)))
            t = threading.Timer(outage, proxy.revive_instance, args=(j, kind))
            t.daemon = True
            t.start()
            revive_timers.append(t)
            print(f"  [chaos] hang {kind}[{j}] (watchdog will strand it)")
        else:
            print(f"  [chaos] {e.kind} not modeled in --real mode; skipped")
    scen = _scenario_trace(args)[:args.requests] if args.scenario else None

    def scenario_tokens(src, n):
        # block content derived from the chain key: equal keys -> equal
        # tokens, so resubmitted prefixes (multi-turn chains, templates)
        # genuinely hit the real PagedKVCache trie instead of merely
        # colliding in the sim's residency model
        toks = rng.integers(0, cfg.vocab_size, n)
        for bi, key in enumerate((src.prefix_hash or ())[:n // 128]):
            block_rng = np.random.default_rng(key & 0xFFFFFFFF)
            toks[bi * 128:(bi + 1) * 128] = \
                block_rng.integers(0, cfg.vocab_size, 128)
        return toks

    try:
        prev_arrival = scen[0].arrival if scen else 0.0
        for i in range(args.requests):
            if scen and i < len(scen):
                # replay the scenario's task mix, pacing, and hash-chained
                # prompts (truncated to the tiny model's max_seq); SLOs use
                # the real-mode convention — the tiny CPU model's latencies
                # are not A800's, so the scenario's SLOs don't transfer
                src = scen[i]
                n = min(src.num_tokens, max_seq)
                req = Request(num_tokens=n, slo=5.0 if n <= 256 else 30.0,
                              arrival=time.monotonic(),
                              task_type=src.task_type,
                              output_tokens=out_tokens, tbt_slo=2.0,
                              prefix_hash=(src.prefix_hash or ())[:n // 128])
                proxy.submit(req, scenario_tokens(src, n))
                gap, prev_arrival = src.arrival - prev_arrival, src.arrival
                time.sleep(min(max(gap, 0.0), 0.5))
            else:
                n = int(rng.choice([256, 256, 1024, 2048]))
                req = Request(num_tokens=n, slo=5.0 if n <= 256 else 30.0,
                              arrival=time.monotonic(),
                              output_tokens=out_tokens, tbt_slo=2.0)
                proxy.submit(req, rng.integers(0, cfg.vocab_size, n))
                time.sleep(float(rng.exponential(0.15)))
            for e in chaos_by_i.pop(i, ()):
                fire(e)
        if not proxy.drain(300.0):
            rep = proxy.report()
            raise SystemExit(
                f"drain timed out: {len(rep['stranded_rids'])} request(s) "
                f"stranded (rids {rep['stranded_rids']}), instance health "
                f"{rep['instance_health']}")
        time.sleep(0.5)
        rep = proxy.report()
        print(f"  requests={rep['n_requests']} "
              f"dispatched={rep['dispatched_by_instance']}")
        print(f"  SLO attainment={rep['slo_attainment']:.2f} "
              f"TTFT mean={rep['ttft']['mean']:.3f}s "
              f"p99={rep['ttft']['p99']:.3f}s "
              f"e2e p99/SLO={rep['percentiles']['e2e_p99_norm']:.2f}")
        print(f"  decoded={sum(len(d.finished) for d in decs)} "
              f"decode_migrations={rep['decode_migrations']} "
              f"decode_preemptions={rep['decode_preemptions']}")
        if args.spec_decode:
            sp = rep["spec"]
            print(f"  spec: steps={sp['spec_steps']} "
                  f"accept={sp['accept_rate']:.2f} "
                  f"tokens/step={sp['tokens_per_step']:.2f} "
                  f"(drafted {sp['draft_proposed']}, "
                  f"accepted {sp['draft_accepted']})")
        if plan is not None or args.shed_policy != "off":
            served = rep["n_requests"] - rep["lost_requests"] \
                - rep["shed_requests"]
            print(f"  chaos: retries={rep['retries']} "
                  f"shed={rep['shed_requests']} "
                  f"lost={rep['lost_requests']} "
                  f"recovered goodput={served}/{rep['n_requests']} served "
                  f"(health {rep['instance_health']})")
    finally:
        proxy.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--instances", type=int, default=4)
    ap.add_argument("--rate", type=float, default=24.0)
    ap.add_argument("--duration", type=float, default=40.0)
    ap.add_argument("--burstiness", type=float, default=3.0)
    ap.add_argument("--policy", default="all",
                    choices=["all"] + POLICIES)
    ap.add_argument("--scenario", default=None,
                    help="fitted/stress scenario trace (repro.traces."
                    "scenarios; see docs/TRACES.md). Sim mode runs the "
                    "scenario against each dispatch policy with prefix "
                    "caches on; --real replays its pacing, task mix, and "
                    "hash-chained prompt content (block tokens derived "
                    "from chain keys, so shared prefixes hit the real "
                    "PagedKVCache). Overrides --burstiness/--prefix-share "
                    "trace shaping")
    ap.add_argument("--hetero", default=None, metavar="HW,HW,...",
                    help="comma-separated per-instance hardware "
                    "(a800 / a100 / tpu-v5e); overrides --instances")
    ap.add_argument("--tbt-slo", type=float, default=0.02,
                    help="decode TBT SLO (s/token); tight values make the "
                    "decode-aware policy visible on mixed pools")
    ap.add_argument("--decode-sched", default="fcfs",
                    choices=["fcfs", "s-edf"],
                    help="decode batch-admission policy (s-edf = TBT-slack-"
                    "aware with token-boundary preemption)")
    ap.add_argument("--decode-max-batch", type=int, default=0,
                    help="decode KV slot cap per instance. Sim mode: 0 = "
                    "unbounded processor sharing (scheduling needs a cap to "
                    "matter). Real mode: the continuous-batching slot count "
                    "of the batched jitted decode step (min 1)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding. Sim mode: fluid multi-token "
                    "advancement at --spec-accept per-token acceptance; "
                    "--real: the actual self-drafting n-gram drafter + "
                    "batched verify pass (bit-identical greedy output)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per speculative step")
    ap.add_argument("--spec-accept", type=float, default=0.7,
                    help="sim mode: per-token draft accept probability "
                    "(--real measures the real n-gram accept rate instead)")
    ap.add_argument("--decode-migration", action="store_true",
                    help="cost-gated migration of queued decodes off "
                    "instances past the TBT knee")
    ap.add_argument("--prefix-share", action="store_true",
                    help="block-level prefix sharing: sim mode generates a "
                    "shared-prefix trace (system prompts + multi-turn) and "
                    "gives every instance a prefix cache; real mode turns "
                    "on the prefix-sharing PagedKVCache (pair with "
                    "--policy prefix-affinity to route onto cached KV)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=2048,
                    help="prefix cache capacity per instance, in KV blocks "
                    "of 128 tokens (with --prefix-share)")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="replay a FaultPlan: a preset (churn, spot-wave, "
                    "gray), seed:<int> for a generated schedule, or a JSON "
                    "file from FaultPlan.to_json. Sim mode feeds it to "
                    "ClusterSim; --real injects the same faults into the "
                    "threaded pool (kill/revive + hang watchdog)")
    ap.add_argument("--recovery", default="retry",
                    choices=["retry", "none"],
                    help="stranded-work handling under --chaos: re-dispatch "
                    "with backoff (retry) or count as lost (none, the "
                    "naive baseline)")
    ap.add_argument("--shed-policy", default="off",
                    choices=["off", "doomed-only", "budget"],
                    help="SLO-aware admission control (docs/SCHEDULING.md): "
                    "reject doomed arrivals at the proxy instead of letting "
                    "them poison the tail")
    ap.add_argument("--shed-budget", type=float, default=2.0,
                    help="budget policy: shed when predicted TTFT > "
                    "budget x SLO")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--requests", type=int, default=10,
                    help="request count in --real mode")
    args = ap.parse_args()
    if args.decode_migration and args.decode_max_batch <= 0 and not args.real:
        ap.error("--decode-migration migrates QUEUED decodes: set "
                 "--decode-max-batch > 0 (unbounded decode never queues)")
    if args.real:
        run_real(args)
    else:
        run_sim(args)


if __name__ == "__main__":
    main()
