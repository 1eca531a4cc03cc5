"""End-to-end serving driver: a QwenTrace-statistics workload served by the
full FlowPrefill stack — Proxy -> PrefillInstance (event-driven scheduler,
operator-level preemption, SLO-aware batching) -> DecodeInstance — with a REAL
(tiny) model on CPU. Compares S-EDF against FCFS on the same trace.

    PYTHONPATH=src python examples/serve_trace.py [--requests 12] [--policy both]
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import get_tiny_config
from repro.core import Request, SchedulerCore, TTFTPredictor
from repro.core.metrics import attainment_by_task, slo_attainment
from repro.models import init_params
from repro.models.segments import SegmentedPrefill
from repro.serving.decode_instance import DecodeInstance
from repro.serving.prefill_instance import PrefillInstance
from repro.serving.proxy import Proxy

CFG = dataclasses.replace(get_tiny_config("llama3_8b"),
                          num_layers=2, d_model=128, d_ff=256)
MAX_SEQ = 4096
# scaled-down QwenTrace mix: (task, tokens, slo_seconds, probability)
MIX = [("text", 256, 1.5, 0.60), ("image", 256, 3.0, 0.08),
       ("search", 2048, 15.0, 0.24), ("file", 4096, 25.0, 0.08)]


def build(params, pred, ex, policy):
    core = SchedulerCore(predictor=pred, policy=policy, batch_budget=512,
                         enable_batching=False)
    inst = PrefillInstance(params, CFG, core, max_seq=MAX_SEQ, executor=ex)
    dec = DecodeInstance(params, CFG, decode_tokens=2)
    return Proxy([inst], [dec]), inst, dec


def run(policy, params, pred, ex, n_requests, seed=0):
    proxy, inst, dec = build(params, pred, ex, policy)
    rng = np.random.default_rng(seed)
    reqs = []
    try:
        for i in range(n_requests):
            r = rng.random()
            acc = 0.0
            for task, tokens, slo, p in MIX:
                acc += p
                if r <= acc:
                    break
            req = Request(num_tokens=tokens, slo=slo, task_type=task,
                          arrival=time.monotonic())
            proxy.submit(req, rng.integers(0, CFG.vocab_size, tokens))
            reqs.append(req)
            time.sleep(float(rng.exponential(0.6)))
        assert proxy.drain(300.0)
        time.sleep(0.5)
        rep = proxy.report()
        rep["by_task"] = attainment_by_task(reqs)
        rep["decoded"] = len(dec.finished)
        return rep
    finally:
        proxy.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--policy", default="both",
                    choices=["both", "s-edf", "fcfs"])
    args = ap.parse_args()

    print("== end-to-end FlowPrefill serving (real execution, tiny model) ==")
    params = init_params(CFG, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, CFG, max_seq=MAX_SEQ, granularity="op",
                          chunk_tokens=512)
    xs = [256, 1024, 2048, 4096]
    pred = TTFTPredictor.fit(xs, ex.profile(xs))

    policies = ["s-edf", "fcfs"] if args.policy == "both" else [args.policy]
    for policy in policies:
        rep = run(policy, params, pred, ex, args.requests)
        print(f"\n--- policy={policy} ---")
        print(f"  requests={rep['n_requests']} decoded={rep['decoded']}")
        print(f"  SLO attainment={rep['slo_attainment']:.2f} "
              f"by task={ {k: round(v, 2) for k, v in rep['by_task'].items()} }")
        print(f"  TTFT mean={rep['ttft']['mean']:.3f}s "
              f"p99={rep['ttft']['p99']:.3f}s")
        print(f"  scheduling rounds={rep['scheduling_rounds']}, "
              f"mean blocking={rep['blocking_mean']*1e3:.1f}ms")


if __name__ == "__main__":
    main()
