"""A run whose timed path is broken underneath comes out not correct: a
served token altered where the decode step produces it."""
from chipbench_tiny import alter_tokens, tiny


def test_altered_token_is_not_correct(monkeypatch):
    run, args, bench, wl, cfg, mix, cell = tiny(seconds=3.0)
    monkeypatch.setattr(run, "SAMPLE_TOKENS", 96)
    calls = alter_tokens(monkeypatch)
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
