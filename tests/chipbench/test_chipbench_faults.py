"""A run whose timed path is broken underneath comes out not correct: a
served token altered where the decode step produces it."""
import jax.numpy as jnp

from chipbench_tiny import tiny
from harness import serving


def test_altered_token_is_not_correct(monkeypatch):
    run, args, bench, wl, cfg, mix, cell = tiny(seconds=3.0)
    monkeypatch.setattr(run, "SAMPLE_TOKENS", 96)
    build = serving.build
    calls = {"n": 0}

    def broken_build(*a, **kw):
        proxy = build(*a, **kw)
        dec = proxy.decode_instances[0]
        step = dec._step_ragged

        def altered(p, tokens, kg, vg, kv_lens):
            logits, k, v = step(p, tokens, kg, vg, kv_lens)
            calls["n"] += 1
            # every step: row 0 emits the token after its argmax
            wrong = (jnp.argmax(logits[0]) + 1) % logits.shape[-1]
            return logits.at[0, wrong].set(1e4), k, v
        dec._step_ragged = altered
        return proxy
    monkeypatch.setattr(serving, "build", broken_build)
    out = run.execute(args, bench, wl, cfg, mix, cell, "xla")
    assert calls["n"] > 0
    assert out["checks"]["max_logit_gap"]["value"] > cell["max_logit_gap"]
    assert out["correct"] is False


def test_sound_run_is_correct():
    run, args, bench, wl, cfg, mix, cell = tiny(seconds=2.0, seed=11)
    run.SAMPLE_TOKENS = 96
    out = run.execute(args, bench, wl, cfg, mix, cell, "xla")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"goodput_rps", "ttft_slo_frac_p90",
                                   "tbt_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
