#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU.

Serves Qwen2-1.5B at its published widths (28 layers, d_model 1536, 12 heads
over 2 KV heads, vocab 151936; random float32 weights from a seed) through
the launcher's path: `Proxy` -> `PrefillInstance` (operator-segmented
chunked prefill, Pallas prefill kernel) -> KV handoff -> `DecodeInstance`
(batched ragged decode step, Pallas decode kernel). It checks the prefill
and decode logits against the plain float32 `model.forward`, and fails on a
drain that times out, a request that is not decoded, retried, lost or
stranded, or an instance that recorded an error.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # two prefill and two decode instances,
                                      # one per chip, handing off across chips

It refuses to run without a TPU. The last line of its output is one JSON
object naming the device; any failure exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
# the launcher's flags for the smoke: published config, Pallas kernels,
# eight requests of its mix; its defaults otherwise (max-seq 4096, chunk
# 512, batch budget 4096, decode slot cap 4)
SERVE_FLAGS = ["--arch", "qwen2_1_5b", "--full-config", "--attn", "pallas",
               "--requests", "8", "--seed", str(SEED)]
# prompts of the logit check: one of at least 1024 tokens whose 512-token
# chunks leave a 76-token tail, and a shorter one that makes the batched
# prefill and the decode step ragged
CHECK_LENS = (1100, 600)
# Logit tolerance, as max |got - ref| over max |ref|. The reference is
# float32 at HIGHEST matmul precision and so is the served code under test,
# but the Pallas kernels' own dots run at the TPU compiler's default
# precision, which may round float32 operands to bfloat16 (relative error
# 2^-8 per product); through 28 layers that stays well under 1e-2 of the
# logit range. A wrong mask, RoPE position, chunk offset or KV row moves
# the logits by a sizable fraction of their range.
LOGIT_RTOL = 1e-2
DRAIN_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(msg, flush=True)


def logit_error(got, ref):
    """(max abs error, max abs error over max |ref|)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = float(np.max(np.abs(got - ref)))
    return err, err / float(np.max(np.abs(ref)))


def check_error(name: str, got, ref, problems: list) -> None:
    err, rel = logit_error(got, ref)
    ok = rel <= LOGIT_RTOL
    log(f"{name}: max_abs_err={err!r} max_rel_err={rel!r} "
        f"tol_rel={LOGIT_RTOL} {'ok' if ok else 'FAILED'}")
    if not ok:
        problems.append(f"{name} logits off by {rel!r} of their range")


def reference_logits(params, cfg, tokens, positions):
    """Plain float32 `model.forward` logits at (row, position) pairs:
    tokens (B, S); positions (B, P) -> (B, P, V). Call under HIGHEST
    matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M

    rows = jnp.arange(tokens.shape[0])[:, None]
    fn = jax.jit(lambda p, t, pos: M.forward(p, cfg, {"tokens": t})[rows, pos])
    return fn(params, jnp.asarray(tokens), jnp.asarray(positions))


def check_logits(executor, cfg, attn: str, problems: list) -> None:
    """The served prefill executor (operator granularity, chunked, Pallas)
    on a batched ragged pair of prompts, then one ragged decode step (Pallas)
    over its KV through a paged pool, each against `model.forward` over the
    prompt and the prompt extended by the decoded token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import decode_step_ragged
    from repro.serving.kvcache import PagedKVCache

    params = executor.params
    lens = np.asarray(CHECK_LENS, np.int32)
    B, S = len(lens), int(lens.max())
    rng = np.random.default_rng(SEED)
    ext = np.zeros((B, S + 1), np.int32)          # prompt + decode input
    for i, n in enumerate(lens):
        ext[i, :n + 1] = rng.integers(0, cfg.vocab_size, n + 1)
    # right padding leaves earlier positions unchanged under causal masking
    prompt = np.where(np.arange(S)[None] < lens[:, None], ext[:, :S], 0)
    nxt = ext[np.arange(B), lens]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference_logits(
            params, cfg, ext, np.stack([lens - 1, lens], axis=1)))
        task = executor.start(jnp.asarray(prompt), lens=jnp.asarray(lens))
        got_prefill = np.asarray(executor.run_all(task))

        bs = 128
        st = task.state
        blocks = [-(-(int(n) + 1) // bs) for n in lens]
        kv = PagedKVCache(cfg.num_layers, sum(blocks) + 1, bs,
                          cfg.num_kv_heads, cfg.resolved_head_dim,
                          dtype=executor.cache_dtype)
        for i, n in enumerate(lens):
            kv.allocate(i, int(n) + 1)
            kv.write_prompt(i, st["k_cache"][:, i, :n], st["v_cache"][:, i, :n])
        del task, st
        width = 1
        while width < max(blocks):
            width *= 2
        k_g, v_g, _ = kv.gather_batch(list(range(B)), width)
        step = jax.jit(lambda p, t, kg, vg, kl: decode_step_ragged(
            p, cfg, t, kg, vg, kl, attn_impl=attn))
        got_decode, _, _ = step(params, jnp.asarray(nxt), k_g, v_g,
                                jnp.asarray(lens))
        got_decode = np.asarray(got_decode)
    log(f"logit check: prompts {lens.tolist()} batched, chunk "
        f"{executor.chunk_tokens}, granularity {executor.granularity}, "
        f"attn {attn}; decode step B={B} over a paged pool of width "
        f"{width * bs}")
    check_error("prefill", got_prefill, ref[:, 0], problems)
    check_error("decode", got_decode, ref[:, 1], problems)


def param_bytes(params) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))


def device_memory(dev, key: str):
    stats = dev.memory_stats() or {}
    return stats.get(key)


def log_memory(dev, phase: str) -> None:
    """Device memory after `phase`. The peak never falls, so the first
    phase whose line shows the final peak is the one that set it."""
    log(f"memory after {phase}: bytes_in_use="
        f"{device_memory(dev, 'bytes_in_use')} peak_bytes_in_use="
        f"{device_memory(dev, 'peak_bytes_in_use')} bytes_limit="
        f"{device_memory(dev, 'bytes_limit')}")


def serve_and_check(proxy, cfg, sargs, problems: list):
    """Submit the launcher's mix, drain, and apply its clean-run checks.
    Returns the submitted (request, tokens) pairs."""
    import numpy as np
    from repro.launch import serve

    t0 = time.monotonic()
    sent = serve.submit_mix(proxy, cfg, sargs, np.random.default_rng(SEED))
    drained = proxy.drain(DRAIN_TIMEOUT_S)
    rep = proxy.report()
    decoded = sum(len(d.finished) for d in proxy.decode_instances)
    log(f"serve: requests={rep['n_requests']} "
        f"lengths={sorted(r.num_tokens for r, _ in sent)} "
        f"drained={drained} decoded={decoded} retries={rep['retries']} "
        f"lost={rep['lost_rids']} stranded={rep['stranded_rids']} "
        f"dispatched={rep['dispatched_by_instance']} "
        f"decode_steps={rep['decode_steps']} "
        f"wall_s_incl_compile={time.monotonic() - t0!r}")
    problems.extend(serve.serving_problems(proxy, drained))
    for kind, insts in (("prefill", proxy.prefill_instances),
                        ("decode", proxy.decode_instances)):
        for i, inst in enumerate(insts):
            log(f"{kind}[{i}]: healthy={inst.healthy} "
                f"last_error={inst.last_error!r}")
    return sent


def one_chip(sargs, cfg, dev) -> list:
    """Build, check logits, serve, report memory. Returns the problems."""
    from repro.launch import serve

    problems: list = []
    log(f"config: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} max_seq={sargs.max_seq} chunk={sargs.chunk} "
        f"batch_budget={sargs.batch_budget} "
        f"decode_max_batch={sargs.decode_max_batch} "
        f"decode_tokens={sargs.decode_tokens}")
    t0 = time.monotonic()
    proxy = serve.build(sargs, cfg)
    try:
        ex = proxy.prefill_instances[0].executor
        log(f"build: param_bytes={param_bytes(ex.params)} "
            f"attn={sargs.attn} decode_attn="
            f"{proxy.decode_instances[0].attn_impl} "
            f"wall_s_incl_compile={time.monotonic() - t0!r} "
            f"(params init, prefill compiles, TTFT profile)")
        log_memory(dev, "build")
        t0 = time.monotonic()
        check_logits(ex, cfg, sargs.attn, problems)
        log(f"logit check wall_s_incl_compile={time.monotonic() - t0!r}")
        log_memory(dev, "logit check")
        serve_and_check(proxy, cfg, sargs, problems)
    finally:
        proxy.shutdown()
    log_memory(dev, "serve")
    return problems


def four_chips(sargs, cfg, devs) -> list:
    """Two prefill and two decode instances behind one Proxy, one per chip,
    each with its own copy of the params. Every handoff crosses chips. The
    served first-token logits are checked against the single-chip float32
    reference."""
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.models import init_params
    from repro.models.segments import SegmentedPrefill
    from repro.serving.proxy import Proxy

    problems: list = []
    # worker threads do not inherit a precision context: set it globally,
    # so the served path and the reference both run at HIGHEST
    jax.config.update("jax_default_matmul_precision", "highest")
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    copies = [jax.device_put(params, d) for d in devs[:4]]
    del params
    ex0 = SegmentedPrefill(copies[0], cfg, max_seq=sargs.max_seq,
                           granularity=sargs.granularity,
                           chunk_tokens=sargs.chunk, attn_impl=sargs.attn)
    pred = serve.fit_predictor(ex0, sargs.max_seq)
    prefill = [serve.make_prefill(copies[0], cfg, sargs, sargs.attn, pred,
                                  executor=ex0),
               serve.make_prefill(copies[1], cfg, sargs, sargs.attn, pred)]
    decode = [serve.make_decode(copies[i], cfg, sargs, sargs.attn)
              for i in (2, 3)]
    proxy = Proxy(prefill, decode)
    try:
        sent = serve_and_check(proxy, cfg, sargs, problems)
        served = {}                                # rid -> (instance, logits)
        for i, inst in enumerate(prefill):
            for task in inst.completed_tasks:
                logits = np.asarray(task.prefill_task.logits)
                for row, r in enumerate(task.requests):
                    served[r.rid] = (i, logits[row])
            st = inst.completed_tasks[-1].prefill_task.state \
                if inst.completed_tasks else None
            where = sorted(str(d) for d in st["k_cache"].devices()) \
                if st else "none"
            log(f"prefill[{i}] on {inst.device}: params on "
                f"{sorted(str(d) for d in jax.tree.leaves(inst.executor.params)[0].devices())} "
                f"prefill state on {where} served={len(inst.completed)}")
            if not inst.completed:
                problems.append(f"prefill[{i}] served nothing")
        decoded_by = {}
        for j, dec in enumerate(decode):
            pool = sorted(str(d) for d in dec.kv.k_pool.devices()) \
                if dec.kv is not None else "none"
            log(f"decode[{j}] on {dec.device}: params on "
                f"{sorted(str(d) for d in jax.tree.leaves(dec.params)[0].devices())} "
                f"KV pool on {pool} decoded={len(dec.finished)}")
            if not dec.finished or pool != [str(dec.device)]:
                problems.append(f"decode[{j}] decoded nothing or its pool "
                                f"is not on its own chip")
            for r in dec.finished:
                decoded_by[r.rid] = j
        crossings = sum(
            prefill[served[rid][0]].device != decode[decoded_by[rid]].device
            for rid in decoded_by if rid in served)
        log(f"handoffs: {crossings} of {len(decoded_by)} decoded requests "
            f"crossed chips (prefill on {prefill[0].device}, "
            f"{prefill[1].device}; decode on {decode[0].device}, "
            f"{decode[1].device})")
        for d in devs:
            log(f"{d}: bytes_in_use={device_memory(d, 'bytes_in_use')} "
                f"peak_bytes_in_use={device_memory(d, 'peak_bytes_in_use')}")
        missing = [r.rid for r, _ in sent if r.rid not in served]
        if missing:
            problems.append(f"no prefill logits for rids {missing}")
        else:
            refs = [np.asarray(reference_logits(
                copies[0], cfg, toks[None].astype(np.int32),
                np.array([[len(toks) - 1]])))[0, 0] for _, toks in sent]
            check_error(f"served first-token ({len(sent)} requests, "
                        f"reference on {devs[0]})",
                        np.stack([served[r.rid][1] for r, _ in sent]),
                        np.stack(refs), problems)
    finally:
        proxy.shutdown()
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        log(f"FAILED: the repository's sources are not at {SRC}")
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"FAILED: no TPU found (JAX platform is {devs[0].platform!r}); "
            f"this smoke test runs only on a TPU")
        return 1
    if len(devs) < args.chips:
        log(f"FAILED: --chips {args.chips} needs {args.chips} TPU chips, "
            f"found {len(devs)}")
        return 1
    from repro.launch import serve

    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{serve.setup_compile_cache()}")
    sargs = serve.parse_args(SERVE_FLAGS)
    cfg = serve.load_config(sargs)
    t0 = time.monotonic()
    if args.chips == 1:
        problems = one_chip(sargs, cfg, devs[0])
    else:
        problems = four_chips(sargs, cfg, devs)
    log(f"total wall_s={time.monotonic() - t0!r}")
    for p in problems:
        log(f"FAILED: {p}")
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
