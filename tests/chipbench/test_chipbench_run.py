"""The harness's entry: it refuses to run without a TPU or without the
program's sources, and finds configurations, families, traffic mixes and
metrics by name, so that adding one is adding a file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import BENCH
from harness import spec, traffic

ROOT = BENCH.parent
ARGS = ["--workload", "qwen2-1.5b.hol-mix", "--seed", "2147483659",
        "--seconds", "10", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT, BENCH / "run.py")
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""            # no result line


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's paths
    has no program to measure."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "chipbench" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric reader dropped into a
    copy of the benchmark are used by name, with no code edited."""
    copy = tmp_path / "chipbench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = dict(spec.config("qwen2-1.5b"), name="qwen2-probe",
               num_hidden_layers=4)
    (copy / "configs" / "qwen2-probe.json").write_text(json.dumps(cfg))
    base = spec.traffic("hol-mix")
    mix = dict(base, classes={k: base["classes"][k]
                              for k in ("text", "image")})
    (copy / "traffic" / "short-only.json").write_text(json.dumps(mix))
    (copy / "metrics" / "probe_share.py").write_text(
        "def read(run):\n    return 100.0 * run.hits / run.total\n")

    assert spec.config("qwen2-probe", copy)["num_hidden_layers"] == 4
    short = spec.traffic("short-only", copy)
    cell = spec.cell("qwen2-1.5b.hol-mix", copy)
    items = traffic.open_loop(short, cell, 60.0)
    assert items and {it.task for it in items} <= {"text", "image"}

    class Run:
        hits, total = 3, 4
    assert spec.metric_reader("probe_share", copy)(Run()) == 75.0
    bench = {"per_layer": [
        {"name": "probe_share", "workloads": ["a.short-only"]},
        {"name": "everywhere"}]}
    assert set(spec.metrics_for(bench, "a.short-only", "per_layer")) == \
        {"probe_share", "everywhere"}
    assert set(spec.metrics_for(bench, "b.other", "per_layer")) == \
        {"everywhere"}


def test_every_listed_name_has_its_file():
    bench = spec.benchmark(ROOT)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert spec.config(c["name"])["name"] == c["name"]
        fam = spec.family(spec.config(c["name"])["family"])
        assert all(callable(getattr(fam, f)) for f in (
            "program_config", "shapes", "distribution", "logits_at",
            "token_flops", "head_flops", "attention"))
        assert sorted(spec.config(c["name"])["reduced"]) == \
            sorted(c["reduced"])
    for w in bench["workloads"]:
        assert w["config"] in {c["name"] for c in bench["configs"]}
        spec.traffic(w["traffic"])
        cell = spec.cell(w["name"])
        assert cell["max_prompt"] <= cell["max_seq"] <= \
            spec.config(w["config"])["max_position_embeddings"]
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    # no file of a cell, mix or metric that BENCHMARK.json does not list
    names = {d: {p.stem for p in (BENCH / d).iterdir()
                 if p.suffix in (".json", ".py")}
             for d in ("configs", "traffic", "cells", "metrics")}
    assert names["configs"] == {c["name"] for c in bench["configs"]}
    assert names["traffic"] == {w["traffic"] for w in bench["workloads"]}
    assert names["cells"] == {w["name"] for w in bench["workloads"]}
    assert names["metrics"] == {m["name"] for m in bench["per_layer"]}


@pytest.mark.parametrize("change,says", [
    ({"family": None}, r"has no `family` key"),
    ({"family": "alien"}, r"no family 'alien' \(no families/alien.py\)"),
])
def test_a_configuration_without_its_family_is_an_error(tmp_path, change,
                                                         says):
    copy = tmp_path / "chipbench"
    shutil.copytree(BENCH, copy, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = {k: v for k, v in dict(spec.config("qwen2-1.5b"), **change).items()
           if v is not None}
    (copy / "configs" / "probe.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match=says + r"; the families are "
                       r"\['qwen2'\]"):
        spec.config("probe", copy)
    with pytest.raises(KeyError, match=r"no family 'alien'.*\['qwen2'\]"):
        spec.family("alien", copy)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        spec.peaks("TPU v9 imaginary")
    except KeyError as e:
        assert "peaks.json" in str(e)
    else:
        raise AssertionError("an unknown device kind got a default")
