"""Quickstart: FlowPrefill's operator-level preemption on a tiny model (CPU).

Reproduces the paper's Fig. 8 walk-through with real jitted execution:
request A (long, relaxed SLO) starts prefilling; request B (short, strict SLO)
arrives mid-flight; the event-driven scheduler preempts A at an operator
boundary, serves B, then resumes A — and A's result is bit-identical to an
uninterrupted run.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_tiny_config
from repro.core import Request, SchedulerCore, TTFTPredictor
from repro.models import init_params
from repro.models.segments import SegmentedPrefill
from repro.serving.prefill_instance import PrefillInstance

CFG = dataclasses.replace(get_tiny_config("llama3_8b"),
                          num_layers=2, d_model=128, d_ff=256)
MAX_SEQ, LONG, SHORT = 4096, 4096, 128


def main():
    print("== FlowPrefill quickstart (operator-level preemption) ==")
    params = init_params(CFG, jax.random.PRNGKey(0))
    ex = SegmentedPrefill(params, CFG, max_seq=MAX_SEQ, granularity="op",
                          chunk_tokens=512)

    # offline TTFT profile -> polynomial predictor (paper §6.4)
    xs = [128, 512, 1024, 2048, 4096]
    ys = ex.profile(xs)
    for n, y in zip(xs, ys):
        print(f"  profile: {n:5d} tokens -> {y*1e3:7.1f} ms")
    pred = TTFTPredictor.fit(xs, ys)

    core = SchedulerCore(predictor=pred, policy="s-edf",
                         enable_batching=False)
    inst = PrefillInstance(params, CFG, core, max_seq=MAX_SEQ, executor=ex)
    rng = np.random.default_rng(0)
    try:
        A = Request(num_tokens=LONG, slo=60.0, task_type="file",
                    arrival=time.monotonic())
        inst.submit_request(A, rng.integers(0, CFG.vocab_size, LONG))
        time.sleep(0.3)
        B = Request(num_tokens=SHORT, slo=1.0, task_type="text",
                    arrival=time.monotonic())
        inst.submit_request(B, rng.integers(0, CFG.vocab_size, SHORT))
        print(f"\n  A (file, {LONG} tok, SLO 60s) submitted; "
              f"B (text, {SHORT} tok, SLO 1s) arrives 0.3s later")
        assert inst.drain(120.0)
        print(f"  B TTFT = {B.ttft:.3f}s  (SLO met: {B.slo_met})")
        print(f"  A TTFT = {A.ttft:.3f}s  (SLO met: {A.slo_met})")
        print(f"  preemption blocking time = "
              f"{inst.blocking_stats.mean*1e3:.1f} ms "
              f"(max {inst.blocking_stats.max*1e3:.1f} ms)")
        print(f"  scheduling rounds = {inst.scheduling_rounds} "
              f"(<= 2 per request: event-driven)")

        # exactness: preempted-and-resumed A == uninterrupted run
        a_tokens = np.random.default_rng(0).integers(0, CFG.vocab_size, LONG)
        solo = ex.run_all(ex.start(jnp.asarray(a_tokens[None], jnp.int32)))
        done = {t.head.rid: t for t in inst.completed_tasks}
        same = np.array_equal(np.asarray(done[A.rid].prefill_task.logits),
                              np.asarray(solo))
        print(f"  preempt/resume bit-exact vs uninterrupted: {same}")
    finally:
        inst.shutdown()


if __name__ == "__main__":
    main()
