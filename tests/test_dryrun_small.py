"""Sharding validation on a small forced-device mesh (subprocess so the main
test process keeps its single real device). Exercises the same lower+compile
path as the production dry-run for one representative arch per family x all
four shapes, plus the HLO collective parser."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.collectives import collective_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.configs.base import SHAPES, get_tiny_config, shape_applicable
    from repro.distributed import sharding as shd
    from repro.launch.dryrun import cost_dict, lower_cell
    import dataclasses

    arch, shape_name, multi_pod = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
    cfg = get_tiny_config(arch)
    # pad dims so a 2-way model axis divides head counts etc.
    shape = dataclasses.replace(SHAPES[shape_name], global_batch=4,
                                seq_len=min(SHAPES[shape_name].seq_len, 64))
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        print(json.dumps({"status": "skipped", "reason": why}))
        sys.exit(0)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh = jax.make_mesh((2,) * len(axes), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    rules = (shd.train_rules(multi_pod=multi_pod) if shape.kind == "train"
             else shd.serve_rules(multi_pod=multi_pod))
    with mesh, shd.use_sharding(mesh, rules):
        lowered = lower_cell(cfg, shape, mesh, rules)
        compiled = lowered.compile()
        cost = cost_dict(compiled)
        hlo_len = len(compiled.as_text())
    print(json.dumps({"status": "ok", "flops": float(cost.get("flops", 0)),
                      "hlo_len": hlo_len}))
""")

FAMILY_REPS = ["llama3_2_1b", "qwen3_30b_a3b", "mamba2_370m",
               "recurrentgemma_9b", "whisper_large_v3", "internvl2_76b"]

# lower+compile in subprocesses: minutes of XLA work — kept out of the CI
# fast job (run with `-m slow`; test_collective_parser below stays fast)
slow = pytest.mark.slow


def run_cell(arch, shape, multi_pod):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, arch, shape, "1" if multi_pod else "0"],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, f"{arch}/{shape}: {out.stderr[-2000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@slow
@pytest.mark.parametrize("arch", FAMILY_REPS)
def test_train_cell_lowers_small_mesh(arch):
    r = run_cell(arch, "train_4k", multi_pod=False)
    assert r["status"] == "ok" and r["flops"] > 0


@slow
@pytest.mark.parametrize("shape", ["prefill_32k", "decode_32k", "long_500k"])
def test_serve_cells_lower_small_mesh(shape):
    for arch in ("llama3_2_1b", "mamba2_370m"):
        r = run_cell(arch, shape, multi_pod=False)
        if r["status"] == "skipped":
            assert shape == "long_500k" and arch == "llama3_2_1b"
        else:
            assert r["status"] == "ok"


@slow
def test_multi_pod_axis_shards():
    r = run_cell("llama3_2_1b", "train_4k", multi_pod=True)
    assert r["status"] == "ok"


def test_collective_parser():
    hlo = """
    %all-reduce.7 = bf16[16,128]{1,0} all-reduce(bf16[16,128]{1,0} %x), replica_groups={}
    %ag = f32[64]{0} all-gather(f32[16]{0} %y), dimensions={0}
    %rs = f32[16]{0} reduce-scatter(f32[64]{0} %z), dimensions={0}
    %cp = bf16[8,8]{1,0} collective-permute(bf16[8,8]{1,0} %w)
    %add.1 = f32[64]{0} add(f32[64]{0} %a, f32[64]{0} %b)
    """
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 16 * 128 * 2
    assert got["all-gather"] == 16 * 4
    assert got["reduce-scatter"] == 64 * 4
    assert got["collective-permute"] == 8 * 8 * 2
    assert "add" not in got
