"""Serving launcher: bring up a FlowPrefill PD-disaggregated deployment.

One `PrefillInstance` (operator-segmented prefill) hands off to one
`DecodeInstance` (batched ragged decode) behind a `Proxy`, all on the default
device. Attention runs through the platform's kernel
(`kernels.ops.default_impl`: the Pallas kernels on a TPU, blocked XLA
elsewhere); the launcher builds no mesh. It exits non-zero when the drain
times out or any request was retried, lost or stranded by a worker error.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3_8b \
        --requests 12 --policy s-edf [--granularity op] [--chunk 512]

`chip_smoke.py` at the repository root drives the same functions at
published widths on a TPU.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.base import get_config, get_tiny_config
from repro.core import Request, SchedulerCore, TTFTPredictor
from repro.core.metrics import attainment_by_task, ttft_stats
from repro.kernels.ops import default_impl
from repro.models import init_params
from repro.models.model import supports_ragged_decode
from repro.models.segments import SegmentedPrefill
from repro.serving.decode_instance import DecodeInstance
from repro.serving.prefill_instance import PrefillInstance
from repro.serving.proxy import Proxy

REPO_ROOT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in one fixed directory and
    cache every compile there. ``$JAX_COMPILATION_CACHE_DIR`` wins when it
    is set (JAX reads it itself); otherwise ``<repo>/.jax_cache``. Called at
    the start of an entry point, never at import. Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the per-operator prefill segments compile in well under the default
    # one-second threshold; cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--full-config", action="store_true",
                    help="use the published config (TPU runtimes)")
    ap.add_argument("--policy", default="s-edf",
                    choices=["s-edf", "d-edf", "edf", "fcfs"])
    ap.add_argument("--granularity", default="op")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--batch-budget", type=int, default=4096)
    ap.add_argument("--attn", default=None, choices=[None, "xla", "pallas"],
                    help="attention kernel (default: the platform's, "
                    "pallas on a TPU and xla elsewhere)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=4096)
    ap.add_argument("--decode-tokens", type=int, default=4)
    ap.add_argument("--decode-max-batch", type=int, default=4,
                    help="continuous-batching decode slot cap (batched "
                    "jitted step + paged KV; families without a dense "
                    "per-layer KV cache fall back to 1)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="prefix-sharing prompt KV cache: resubmitted "
                    "prompt prefixes (this launcher's mix reuses a few "
                    "fixed lengths of random tokens, so exact repeats "
                    "occur) are served from cache and prefilled "
                    "suffix-only")
    ap.add_argument("--prefix-cache-blocks", type=int, default=512,
                    help="prefix cache capacity in 128-token KV blocks "
                    "(with --prefix-share)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def load_config(args: argparse.Namespace):
    return get_config(args.arch) if args.full_config \
        else get_tiny_config(args.arch)


def fit_predictor(executor: SegmentedPrefill, max_seq: int) -> TTFTPredictor:
    """Offline TTFT profile (paper §6.4) -> the S-EDF predictor. The
    lengths are the launcher mix's, so the profile also warms its shapes."""
    xs = [256, max_seq // 2, max_seq]
    ys = executor.profile(xs)
    print("TTFT profile:", {n: f"{y*1e3:.0f}ms" for n, y in zip(xs, ys)})
    return TTFTPredictor.fit(xs, ys)


def make_prefill(params, cfg, args: argparse.Namespace, attn: str,
                 predictor: TTFTPredictor,
                 executor: Optional[SegmentedPrefill] = None
                 ) -> PrefillInstance:
    core = SchedulerCore(predictor=predictor, policy=args.policy,
                         batch_budget=args.batch_budget)
    return PrefillInstance(params, cfg, core, max_seq=args.max_seq,
                           granularity=args.granularity,
                           chunk_tokens=args.chunk, attn_impl=attn,
                           executor=executor,
                           prefix_share=args.prefix_share,
                           prefix_cache_blocks=args.prefix_cache_blocks)


def make_decode(params, cfg, args: argparse.Namespace,
                attn: str) -> DecodeInstance:
    dmb = args.decode_max_batch if supports_ragged_decode(cfg) else 1
    return DecodeInstance(params, cfg, decode_tokens=args.decode_tokens,
                          decode_max_batch=dmb, attn_impl=attn)


def build(args: argparse.Namespace, cfg) -> Proxy:
    """The launcher's deployment: params, a warm prefill executor fitted
    into the TTFT predictor, one prefill and one decode instance behind a
    Proxy (call `proxy.shutdown()` when done)."""
    attn = args.attn or default_impl()
    print(f"serving {cfg.name} ({cfg.family}) attn={attn} "
          f"granularity={args.granularity} chunk={args.chunk}")
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    executor = SegmentedPrefill(params, cfg, max_seq=args.max_seq,
                                granularity=args.granularity,
                                chunk_tokens=args.chunk, attn_impl=attn)
    pred = fit_predictor(executor, args.max_seq)
    inst = make_prefill(params, cfg, args, attn, pred, executor=executor)
    return Proxy([inst], [make_decode(params, cfg, args, attn)])


def submit_mix(proxy: Proxy, cfg, args: argparse.Namespace,
               rng: np.random.Generator
               ) -> List[Tuple[Request, np.ndarray]]:
    """Submit `args.requests` requests of the launcher's task mix with
    exponential gaps (mean 0.5 s). Returns the (request, prompt) pairs."""
    mix = [(256, 1.5, "text", 0.7), (args.max_seq // 2, 15.0, "search", 0.2),
           (args.max_seq, 25.0, "file", 0.1)]
    # with --prefix-share: each task class gets a fixed system-prompt
    # template covering half its prompt — repeat submissions hit the
    # prefix cache and prefill only the random tail
    templates = {task: rng.integers(0, cfg.vocab_size, tokens // 2)
                 for tokens, _, task, _ in mix} if args.prefix_share else {}
    sent = []
    for _ in range(args.requests):
        r = rng.random()
        acc = 0.0
        for tokens, slo, task, p in mix:
            acc += p
            if r <= acc:
                break
        req = Request(num_tokens=tokens, slo=slo, task_type=task,
                      arrival=time.monotonic())
        tail = rng.integers(0, cfg.vocab_size,
                            tokens - len(templates.get(task, ())))
        toks = np.concatenate([templates[task], tail]) \
            if args.prefix_share else tail
        proxy.submit(req, toks)
        sent.append((req, toks))
        time.sleep(float(rng.exponential(0.5)))
    return sent


def serving_problems(proxy: Proxy, drained: bool) -> List[str]:
    """Why a served run does not count as clean: a drain that timed out, a
    request that was not decoded, retried, lost or stranded, or an instance
    that recorded a worker error. Empty when the run is clean."""
    rep = proxy.report()
    out = []
    if not drained:
        out.append("drain timed out")
    decoded = sum(len(d.finished) for d in proxy.decode_instances)
    if proxy.decode_instances and decoded != rep["n_requests"]:
        out.append(f"decoded {decoded} of {rep['n_requests']} requests")
    if rep["retries"]:
        out.append(f"{rep['retries']} retries")
    for key in ("lost_rids", "stranded_rids"):
        if rep[key]:
            out.append(f"{key}={rep[key]}")
    for kind, insts in (("prefill", proxy.prefill_instances),
                        ("decode", proxy.decode_instances)):
        for i, inst in enumerate(insts):
            if not inst.healthy or inst.last_error is not None:
                out.append(f"{kind}[{i}] error: {inst.last_error!r}")
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    setup_compile_cache()
    cfg = load_config(args)
    proxy = build(args, cfg)
    try:
        submit_mix(proxy, cfg, args, np.random.default_rng(args.seed))
        drained = proxy.drain(600.0)
        rep = proxy.report()
        print(f"\nattainment={rep['slo_attainment']:.2f} "
              f"by_task={ {k: round(v,2) for k,v in attainment_by_task(proxy.requests).items()} }")
        print(f"ttft={ttft_stats(proxy.requests)}")
        print(f"rounds={rep['scheduling_rounds']} "
              f"blocking_mean={rep['blocking_mean']*1e3:.1f}ms "
              f"decoded={sum(len(d.finished) for d in proxy.decode_instances)}")
        if args.prefix_share:
            print(f"prefix hits={rep['prefix_hits']} "
                  f"({rep['prefix_hit_tokens']} prompt tokens served "
                  f"from cache)")
        problems = serving_problems(proxy, drained)
    finally:
        proxy.shutdown()
    for p in problems:
        print(f"FAILED: {p}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
