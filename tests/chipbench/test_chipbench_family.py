"""A model family brought as new files only: a copy of the benchmark gets
`families/untied_dense.py` (a dense stack with no q/k/v bias and an untied
output head, kept in `data/untied_dense/`) and a configuration that names
it. `execute` of the copy's `run.py` serves it at a tiny size on the CPU
through the same path as the Qwen2 cell: a sound run reads correct, the
altered-token fault reads not correct, and neither edits a file of the
copy."""
import hashlib
import shutil

import pytest

from chipbench_tiny import alter_tokens, tiny
from conftest import BENCH
from harness import flops, spec

DATA = BENCH.parent / "tests" / "chipbench" / "data" / "untied_dense"


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def copy(tmp_path):
    c = tmp_path / "chipbench"
    shutil.copytree(BENCH, c, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "untied_dense.py", c / "families")
    shutil.copy(DATA / "untied-probe.json", c / "configs")
    return c


def _cell(copy, **kw):
    run, args, bench, wl, _, mix, cell = tiny(bench_dir=copy, **kw)
    run.SAMPLE_TOKENS = 96
    return run, args, bench, wl, spec.config("untied-probe", copy), mix, cell


def test_family_layout_and_counts(copy):
    cfg = spec.config("untied-probe", copy)
    fam = spec.family(cfg["family"], copy)
    tree = fam.shapes(cfg)
    assert tree["unembed"] == (64, 512)
    assert not {"bq", "bk", "bv"} & set(tree["layers"])
    pc = fam.program_config(cfg)
    assert not pc.tie_embeddings and not pc.qkv_bias
    # no bias, an untied head: 2 x (q, k, v, o and gate/up/down) per layer
    per_layer = 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128)
    assert flops.prefill_flops(fam, cfg, 10) == \
        10 * 2 * per_layer + 2 * 4 * 4 * 16 * 55 + 2 * 64 * 512


def test_new_family_is_served_and_correct(copy):
    before = _digests(copy)
    run, args, bench, wl, cfg, mix, cell = _cell(copy, seconds=2.0, seed=5)
    out = run.execute(args, bench, wl, cfg, mix, cell, "xla")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert _digests(copy) == before


def test_new_family_altered_token_is_not_correct(copy, monkeypatch):
    before = _digests(copy)
    run, args, bench, wl, cfg, mix, cell = _cell(copy, seconds=3.0)
    calls = alter_tokens(monkeypatch)
    out = run.execute(args, bench, wl, cfg, mix, cell, "xla")
    assert calls["n"] > 0
    assert out["checks"]["max_logit_gap"]["value"] > cell["max_logit_gap"]
    assert out["correct"] is False
    assert _digests(copy) == before
