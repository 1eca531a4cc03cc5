#!/usr/bin/env python3
"""On-chip benchmark of the serving path: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: make the weights from the seed (in the layout of the
configuration's family, `families/<family>.py`), build the deployment
(`harness.serving`), warm every shape the cell's traffic uses, send the
traffic (`harness.traffic`) for a lead-in and then the measured window,
wait for the window's requests, check what was served against the float32
reference (`harness.check`), and print one JSON line last. With `--trace 1`
the window is traced with the JAX profiler and the line carries the cell's
per-layer metrics instead of the end-to-end ones.

It runs only on a TPU with as many chips as the cell asks for, and only
from a checkout that holds the program's sources (`src/`).

Options for calibration, not used by the benchmark's own runs:
  --control 1     put the fp8 reference in the program's place on the same
                  sample: its widest gap is what `correct` compares
  --rates a,b,..  serve windows at these rates in one process (knee sweep);
  --reps n        n windows per rate, each on another schedule
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

T_START = time.monotonic()
BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SRC = ROOT / "src"
DRAIN_S = 90.0                  # wait for the window's requests after it
SAMPLE_TOKENS = 320             # served tokens the reference checks


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--reps", type=int, default=1)
    return ap.parse_args(argv)


def setup_cache() -> str:
    """JAX's persistent compilation cache: `$JAX_COMPILATION_CACHE_DIR`
    when set, else `.jax_cache/` at the checkout's root (a fixed path, so
    later runs in this checkout find every program)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def check_layout(params, pcfg) -> None:
    """The benchmark's weights have the program's parameter shapes."""
    import jax
    from repro.models import init_params
    want = jax.eval_shape(lambda k: init_params(pcfg, k), jax.random.key(0))
    got_s = jax.tree.map(lambda a: a.shape, params)
    want_s = jax.tree.map(lambda a: a.shape, want)
    if got_s != want_s:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got_s} != {want_s}")


def widths(mix: dict, cell: dict, block: int):
    """Decode width buckets (power-of-two block counts) the traffic forms:
    from its shortest request to its longest, prompt plus output."""
    from harness import traffic
    grid = traffic.prompt_grid(mix, cell)

    def pow2(n):
        return 1 << max(n - 1, 0).bit_length()
    lo = pow2(-(-(grid[0] + int(mix["output"]["min"])) // block))
    hi = pow2(-(-(grid[-1] + int(mix["output"]["max"])) // block))
    return [1 << i for i in range(hi.bit_length()) if lo <= 1 << i <= hi]


class Run:
    """What one window produced, as the metric readers see it."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def warm(proxy, mix, cell, pcfg, rng_tokens):
    """Serve the pool-sizing request, then one request per prompt-grid
    length, then run every decode step shape."""
    from harness import serving, traffic
    import numpy as np
    t_out = int(mix["output"]["min"])
    first = cell["pool_request"]
    reqs = [(serving.make_request(first[0], first[1], 1e9, 1e9, "warm",
                                  time.monotonic()),
             rng_tokens.integers(0, pcfg.vocab_size, first[0],
                                 dtype=np.int32))]
    if not serving.serve_blocking(proxy, reqs, 600.0):
        raise RuntimeError("warm-up: the pool-sizing request did not finish")
    reqs = [(serving.make_request(n, t_out, 1e9, 1e9, "warm",
                                  time.monotonic()),
             rng_tokens.integers(0, pcfg.vocab_size, n, dtype=np.int32))
            for n in traffic.prompt_grid(mix, cell)]
    if not serving.serve_blocking(proxy, reqs, 900.0):
        raise RuntimeError("warm-up: the prompt-grid requests did not finish")
    dec = proxy.decode_instances[0]
    serving.warm_decode(proxy, dec._b_buckets,
                        widths(mix, cell, dec.kv_block_size))


def drive(proxy, items, tokens, t0, t_end):
    from harness import serving
    sent = []                         # (item, request, sent time)
    for it in items:
        due = t0 + it.due
        if due >= t_end:
            break
        while True:
            now = time.monotonic()
            if now >= due:
                break
            serving.reap(proxy)
            time.sleep(min(due - now, 0.005))
        req = serving.make_request(it.prompt_len, it.output_len, it.ttft_slo,
                                   it.tbt_slo, it.task, due)
        proxy.submit(req, tokens[it.index])
        sent.append((it, req, time.monotonic()))
    while time.monotonic() < t_end:
        serving.reap(proxy)
        time.sleep(0.005)
    return sent


def wait_done(proxy, reqs, timeout):
    from harness import serving
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        serving.reap(proxy)
        if all(r.finish_time is not None for r in reqs):
            return True
        time.sleep(0.01)
    return False


def window(proxy, rec, mix, cell, seed, seconds, pcfg, trace_dir):
    """Send the traffic, measure, wait. Returns the Run."""
    import jax
    from harness import traffic, stats
    lead = float(mix["lead_s"])
    horizon = lead + seconds
    items = traffic.open_loop(mix, cell, horizon)
    tokens = traffic.prompt_tokens(items, pcfg.vocab_size, seed)
    inst = proxy.prefill_instances[0]
    t0 = time.monotonic()
    w0, w1 = t0 + lead, t0 + horizon
    state = {}

    def mark():
        state["rounds"] = inst.scheduling_rounds
        state["blocking"] = len(inst.blocking_stats.samples)
        state["steps"] = len(rec.steps)
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state["t"] = time.monotonic()

    # the window opens at w0 while the generator runs: a timer thread
    # marks it, so the generator keeps its schedule
    timer = threading.Timer(max(w0 - time.monotonic(), 0.0), mark)
    timer.start()
    sent = drive(proxy, items, tokens, t0, w1)
    timer.join()
    rounds1 = inst.scheduling_rounds
    blocking1 = len(inst.blocking_stats.samples)
    steps1 = len(rec.steps)
    t_close = time.monotonic()
    if trace_dir is not None:
        jax.profiler.stop_trace()
    win_start = state["t"]
    done = wait_done(proxy, [r for _, r, _ in sent], DRAIN_S)
    recs = [stats.Record(due=r.arrival, prompt_len=it.prompt_len,
                         output_len=it.output_len, ttft_slo=it.ttft_slo,
                         tbt_slo=it.tbt_slo, first_token=r.first_token_time,
                         finish=r.finish_time, mean_tpot=r.mean_tpot)
            for it, r, _ in sent if w0 <= r.arrival < w1]
    late = stats.lateness([r.arrival for _, r, _ in sent],
                          [s for _, _, s in sent])
    steps = [(t, [(rid, p) for rid, p in zip(ids, pos) if rid >= 0])
             for t, ids, pos, _ in rec.steps[state["steps"]:steps1]]
    return Run(
        start=win_start, end=t_close, seconds=t_close - win_start,
        sent=sent, records=recs, drained=done, lateness=late, tokens=tokens,
        rounds=rounds1 - state["rounds"],
        blocking=list(inst.blocking_stats.samples[state["blocking"]:blocking1]),
        steps=steps)


def end_to_end(run) -> dict:
    from harness import stats
    recs = run.records
    return {
        "goodput_rps": (stats.goodput_rps(recs, run.seconds), "req/s"),
        "ttft_slo_frac_p90": (stats.ttft_slo_frac(recs, 90.0), "ratio"),
        "tbt_p90_ms": (stats.tbt_ms(recs, 90.0), "ms"),
    }


def served_sample(run, rec, seed):
    """Requests finished in this run with the tokens the decode steps fed,
    and the sample the reference checks."""
    from harness import check
    fed = rec.served_tokens()
    served, missing = [], []
    for it, r, _ in run.sent:
        if r.finish_time is None:
            continue
        pos = fed.get(r.rid, {})
        toks = [pos.get(it.prompt_len + j) for j in range(it.output_len)]
        if any(t is None for t in toks):
            missing.append(r.rid)
            continue
        served.append(check.Served(rid=r.rid, prompt=run.tokens[it.index],
                                   tokens=toks,
                                   preempted=r.rid in rec.preempted))
    return served, check.sample(served, seed, SAMPLE_TOKENS), missing


def main(argv=None) -> int:
    args = parse(argv)
    if not (SRC / "repro").is_dir() or not (ROOT / "BENCHMARK.json").is_file():
        log(f"FAILED: the program's sources (src/repro) or BENCHMARK.json "
            f"are not in {ROOT}")
        return 2
    sys.path.insert(0, str(SRC))
    sys.path.insert(0, str(BENCH_DIR))
    from harness import spec
    bench = spec.benchmark(ROOT)
    wl = spec.workload(bench, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        log(f"FAILED: this cell needs {wl['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 1
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{setup_cache()}")
    from repro.kernels.ops import default_impl
    out = execute(args, bench, wl, spec.config(wl["config"]),
                  spec.traffic(wl["traffic"]), spec.cell(wl["name"]),
                  default_impl())
    if out is not None:
        print(json.dumps(out), flush=True)
    return 0


def execute(args, bench, wl, cfg, mix, cell, attn):
    """One run after the device check: returns the result object (or None
    for a sweep). Tests drive it on the CPU at a tiny size."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import check, serving, spec, weights
    devs = jax.devices()
    dev = devs[0]
    seed = args.seed & ((1 << 63) - 1)
    fam = spec.family(cfg["family"], BENCH_DIR)
    pcfg = fam.program_config(cfg)
    if int(cell["max_seq"]) > int(cfg["max_position_embeddings"]):
        raise ValueError(f"cell max_seq {cell['max_seq']} exceeds the "
                         f"configuration's max_position_embeddings")
    params = weights.make(fam, cfg, seed, jnp.bfloat16)
    jax.block_until_ready(params)
    check_layout(params, pcfg)
    log(f"weights: {sum(x.size for x in jax.tree.leaves(params))} params "
        f"bf16, {time.monotonic() - T_START:.1f}s after start")
    proxy = serving.build(params, pcfg, cell, attn, cell["profile_lens"])
    counter = serving.CompileCounter()
    rec = serving.Recorder()
    try:
        warm(proxy, mix, cell, pcfg, np.random.default_rng([seed, 3]))
        rec.install(proxy)
        counter.arm()
        setup_s = time.monotonic() - T_START
        log(f"setup_s={setup_s!r} (JAX start, weights, compiles or cache "
            f"loads, TTFT profile, warm-up)")
        if args.rates:
            sweep(proxy, rec, mix, cell, seed, args.seconds, pcfg,
                  [float(x) for x in args.rates.split(",")], args.reps)
            return None
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if args.trace else None
        run = window(proxy, rec, mix, cell, seed, args.seconds, pcfg,
                     trace_dir)
        compiles = counter.count
        memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        served, sample, missing = served_sample(run, rec, seed)
    finally:
        proxy.shutdown()
    del proxy, rec
    gc.collect()

    late = sorted(run.lateness)
    log(f"window: {run.seconds!r}s, {len(run.records)} requests due in it, "
        f"{len(run.sent)} sent in all; compiles in window: {compiles} "
        f"(cache loads {counter.cache_loads}); generator lateness p50 "
        f"{late[len(late) // 2] * 1e3 if late else 0:.3f}ms max "
        f"{late[-1] * 1e3 if late else 0:.3f}ms")
    unfinished = sum(r.first_token is None or r.finish is None
                     for r in run.records)
    # the reference over the sample, after the program's state is freed.
    # With --control 1 the fp8 reference takes the program's place: its
    # reading is the one compared with the cell's limit
    t_ref = time.monotonic()
    program_gap, control_gap, n_tok = 0.0, 0.0, 0
    for s in sample:
        g = check.gaps(fam, params, cfg, s,
                       "fp8" if args.control else "f32")
        program_gap = max(program_gap, float(g["served"].max()))
        n_tok += len(s.tokens)
        if args.control:
            control_gap = max(control_gap, float(g["control"].max()))
        log(f"  request {s.rid}: prompt {len(s.prompt)}, {len(s.tokens)} "
            f"served, preempted {s.preempted}, first-token gap "
            f"{float(g['served'][0])!r}, widest decode gap "
            f"{float(g['served'][1:].max(initial=0.0))!r}, positions off "
            f"the reference's best {int((g['served'] > 0).sum())}"
            + (f", control widest {float(g['control'].max())!r}"
               if args.control else ""))
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"longest prompt {max((len(s.prompt) for s in sample), default=0)}, "
        f"preempted in sample {sum(s.preempted for s in sample)} "
        f"(of {sum(s.preempted for s in served)} finished), "
        f"{time.monotonic() - t_ref:.1f}s")
    gap = program_gap
    if args.control:
        log(f"control: the fp8 reference in the program's place; the "
            f"program read max_logit_gap={program_gap!r}")
        gap = control_gap
    checks = {
        "max_logit_gap": {"value": gap, "limit": cell["max_logit_gap"]},
        "unfinished": {"value": unfinished, "limit": 0},
        "tokens_missing": {"value": len(missing), "limit": 0},
    }
    correct = (gap <= cell["max_logit_gap"] and unfinished == 0
               and not missing and n_tok > 0 and run.drained)

    result_metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    breakdown = None
    if args.trace:
        from harness import trace as tr, work
        planes = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        dt = planes[f"/device:TPU:{dev.id}"]
        busy_s = tr.busy_ns(dt) * 1e-9
        device.update(busy_s=busy_s, window_s=run.seconds)
        breakdown = {"device_ops": [[k, v] for k, v in tr.top_ops(dt)],
                     "idle_gaps": [[k, v] for k, v in tr.idle_gaps(dt)]}
        r = Run(**run.__dict__, cfg=cfg, family=fam, cell=cell,
                device_trace=dt, busy_s=busy_s,
                peaks=spec.peaks(dev.device_kind))
        rows = work.decode_rows(r)
        log(f"counted work: prompts whose first token came in the window "
            f"{work.prompts(r)}; {sum(len(p) for _, p in rows)} decode rows "
            f"in {len(rows)} steps; busy_s={busy_s!r}")
        for name, m in spec.metrics_for(bench, wl["name"],
                                        "per_layer").items():
            v = spec.metric_reader(name)(r)
            if v is not None:
                result_metrics[name] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run)
        e2e["setup_s"] = (setup_s, "s")
        for name, m in spec.metrics_for(bench, wl["name"],
                                        "end_to_end").items():
            v = e2e[name][0]
            result_metrics[name] = {"value": v if math.isfinite(v) else 1e9,
                                    "unit": m["unit"]}
    for k, c in checks.items():
        log(f"check {k}={c['value']!r} limit={c['limit']!r}")
    out = {"correct": bool(correct), "attempted": len(run.records),
           "failed": unfinished, "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.control:
        out["program"] = {"max_logit_gap": program_gap}
    out["checks"] = checks
    return out


def sweep(proxy, rec, mix, cell, seed, seconds, pcfg, rates, reps):
    """Knee search: `reps` windows per rate in this process, window r on the
    schedule of base seed `base_seed + r`. Prints, per window, the share of
    its requests that met both limits and the backlog left at its close."""
    for rate in rates:
        for r in range(reps):
            m = dict(mix, base_seed=int(mix["base_seed"]) + r)
            run = window(proxy, rec, m, dict(cell, rate=rate), seed + r,
                         seconds, pcfg, None)
            recs = run.records
            met = sum(x.met for x in recs) / max(len(recs), 1)
            late_first = sum(x.first_token is None or x.first_token > run.end
                             for x in recs)
            print(json.dumps({"rate": rate, "base_seed": m["base_seed"],
                              "requests": len(recs), "met_share": met,
                              "not_first_by_close": late_first,
                              "drained": run.drained,
                              **{k: v for k, (v, _) in
                                 end_to_end(run).items()}}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
