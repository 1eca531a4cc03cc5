"""What a cell is, read from files found by name.

`BENCHMARK.json` at the checkout's root lists the cells (`workloads`) and
metrics; each cell's configuration, traffic mix and parameters are files of
their own under `chipbench/`:

    configs/<config>.json     the model's published sizes, as run, and
                              the `family` it belongs to
    families/<family>.py      everything that depends on the architecture:
                              the program's config, the weight layout, the
                              float32 reference and the FLOP counts
    traffic/<traffic>.json    the mix's parameters for `harness.traffic`
    cells/<workload>.json     the cell's rate or clients, max_seq, limits
    metrics/<metric>.py       a reader: read(run) -> float or None

Nothing here names a cell, a mix or a metric: a later change adds files.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """`configs/<name>.json`; its `family` must have a file (`family`)."""
    cfg = _json(bench_dir / "configs" / f"{name}.json")
    if "family" not in cfg:
        raise KeyError(f"configs/{name}.json has no `family` key; the "
                       f"families are {families(bench_dir)}")
    family(cfg["family"], bench_dir)
    return cfg


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def cell(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "cells" / f"{name}.json")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def families(bench_dir: Path = BENCH_DIR) -> List[str]:
    """The names of the family modules under `families/`."""
    return sorted(p.stem for p in (bench_dir / "families").glob("*.py"))


def family(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The module `families/<name>.py`, loaded once per process, so that
    the jitted reference programs it defines compile once."""
    path = bench_dir / "families" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no family {name!r} (no families/{name}.py); the "
                       f"families are {families(bench_dir)}")
    return _load_family(path.resolve())


@functools.cache
def _load_family(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The `read` function of `metrics/<name>.py`."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, workload_name: str, kind: str) -> Dict[str, dict]:
    """The `end_to_end` or `per_layer` metrics a cell reports: those with
    no `workloads` key, and those that list it."""
    return {m["name"]: m for m in bench[kind]
            if workload_name in m.get("workloads", [workload_name])}
