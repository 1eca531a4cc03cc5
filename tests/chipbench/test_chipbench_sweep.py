"""The knee sweep (`--rates`, `--reps`): one line per window, each window
of a rate on its own schedule, every request finished."""
import json

from chipbench_tiny import tiny


def test_sweep_prints_one_line_per_window(capsys):
    run, args, bench, wl, cfg, mix, cell = tiny(seconds=1.5)
    args.rates, args.reps = "4,6", 2
    assert run.execute(args, bench, wl, cfg, mix, cell, "xla") is None
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]
    assert [(r["rate"], r["base_seed"]) for r in rows] == \
        [(4.0, 0), (4.0, 1), (6.0, 0), (6.0, 1)]
    assert all(r["drained"] and r["requests"] > 0 for r in rows)
    assert {"goodput_rps", "ttft_slo_frac_p90", "tbt_p90_ms"} <= set(rows[0])
