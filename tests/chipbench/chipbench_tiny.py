"""A cell at a size the CPU runs in a minute, driven through the harness's
`execute` (everything after the look for a chip)."""
import argparse
import importlib.util
import sys

import jax.numpy as jnp

from conftest import BENCH

from harness import serving, spec


def load_run(bench=BENCH):
    """`run.py` of the benchmark at `bench` as a module (it is a script,
    not a package); it finds families under its own directory."""
    src = str(BENCH.parent / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    s = importlib.util.spec_from_file_location("chipbench_run",
                                               bench / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def tiny(hidden=64, layers=2, vocab=512, seconds=3.0, control=0, seed=7,
         bench_dir=BENCH):
    """(run module, args, bench, workload, cfg, mix, cell) of a small
    open-loop hol-mix cell: 64-token chunks, so prompts span several chunks
    and short arrivals preempt long ones. `bench_dir` is the benchmark
    whose `run.py` serves it."""
    run = load_run(bench_dir)
    bench = spec.benchmark(BENCH.parent)
    wl = spec.workload(bench, "qwen2-1.5b.hol-mix")
    cfg = dict(spec.config("qwen2-1.5b"), num_hidden_layers=layers,
               hidden_size=hidden, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=2 * hidden,
               vocab_size=vocab)
    mix = dict(spec.traffic("hol-mix"), lead_s=0.5)
    mix["output"] = dict(mix["output"], max=24, mean=8, std=8)
    # the limit at this size: sound runs read 0.00-0.03 here, the fp8
    # control 0.9 and an altered token about 1.4 (CPU); the cell file's
    # limit is set from readings at the published widths
    cell = dict(spec.cell(wl["name"]), rate=6.0, max_seq=512,
                max_prompt=448, chunk=64, pool_request=[128, 24],
                profile_lens=[64, 256, 448], max_logit_gap=0.25)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              control=control, rates="", reps=1)
    return run, args, bench, wl, cfg, mix, cell


def alter_tokens(monkeypatch) -> dict:
    """Break the timed path where a token is produced: every decode step,
    row 0 emits the token after its argmax. Returns the count of altered
    steps (`n`)."""
    build = serving.build
    calls = {"n": 0}

    def broken_build(*a, **kw):
        proxy = build(*a, **kw)
        dec = proxy.decode_instances[0]
        step = dec._step_ragged

        def altered(p, tokens, kg, vg, kv_lens):
            logits, k, v = step(p, tokens, kg, vg, kv_lens)
            calls["n"] += 1
            wrong = (jnp.argmax(logits[0]) + 1) % logits.shape[-1]
            return logits.at[0, wrong].set(1e4), k, v
        dec._step_ragged = altered
        return proxy
    monkeypatch.setattr(serving, "build", broken_build)
    return calls
