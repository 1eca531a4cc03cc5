"""The control: the float32 reference computed in fp8, put in the
program's place, reads a widest logit gap above the cell's limit, so the
run comes out not correct, while the bfloat16 program on the same served
tokens reads under it. At a small size (d_model 256, 4 layers, vocab 4096)
on the CPU; the chip readings at the cell's size are in PERF.md."""
from chipbench_tiny import tiny
from harness import reference


def test_fp8_control_is_not_correct(monkeypatch):
    run, args, bench, wl, cfg, mix, cell = tiny(
        hidden=256, layers=4, vocab=4096, seconds=2.0, control=1, seed=3)
    mix["output"] = dict(mix["output"], max=64, mean=32, std=16)
    run.SAMPLE_TOKENS = 600
    monkeypatch.setattr(reference, "PAD_TO", 512)   # short prompts here
    out = run.execute(args, bench, wl, cfg, mix, cell, "xla")
    control = out["checks"]["max_logit_gap"]["value"]
    program = out["program"]["max_logit_gap"]
    assert out["checks"]["max_logit_gap"]["limit"] == cell["max_logit_gap"]
    assert program <= cell["max_logit_gap"] < control, (program, control)
    assert out["checks"]["unfinished"]["value"] == 0
    assert out["correct"] is False
