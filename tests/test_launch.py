"""The serving launcher's entry-point contract: where the compile cache
goes, a non-zero exit for a run that is not clean, and instances that keep
their arrays on their own device when one host holds several."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import serve
from repro.serving.proxy import Proxy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--arch", "qwen2_1_5b", "--requests", "2", "--max-seq", "1024",
        "--chunk", "128", "--decode-tokens", "2", "--seed", "1"]


@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_to_repo_dir(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve.setup_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_env_wins(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert serve.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # JAX's own read


@pytest.mark.parametrize("drains", [True, False])
def test_main_exit_code(monkeypatch, capsys, drains):
    """A clean run exits 0; a drain that times out exits 1 and says why."""
    monkeypatch.setattr(serve, "setup_compile_cache", lambda: "")
    if not drains:
        monkeypatch.setattr(Proxy, "drain", lambda self, timeout=0.0: False)
    assert serve.main(TINY) == (0 if drains else 1)
    out = capsys.readouterr().out
    assert ("FAILED: drain timed out" in out) is not drains
    assert ("decoded=2" in out) or not drains


def test_serving_problems_names_retries():
    class Inst:
        healthy, last_error, finished = True, None, []

    class FakeProxy:
        prefill_instances, decode_instances = [Inst()], []

        def report(self):
            return {"n_requests": 1, "retries": 2, "lost_rids": [],
                    "stranded_rids": [7]}

    assert serve.serving_problems(FakeProxy(), drained=True) == \
        ["2 retries", "stranded_rids=[7]"]


FOUR_DEVICES = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import jax
    import chip_smoke
    from repro.launch import serve
    sargs = serve.parse_args(["--arch", "qwen2_1_5b", "--attn", "xla",
                              "--requests", "6", "--max-seq", "1024",
                              "--chunk", "128", "--decode-tokens", "2"])
    problems = chip_smoke.four_chips(sargs, serve.load_config(sargs),
                                     jax.devices())
    print(json.dumps(problems))
""")


def test_instances_stay_on_their_devices():
    """Two prefill and two decode instances on four (virtual CPU) devices:
    each instance's state and pool live on its own device, every handoff
    crosses devices, and the served logits match the one-device reference
    (chip_smoke.py's four-chip phase, at a tiny size)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", FOUR_DEVICES, REPO],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == [], out.stdout[-3000:]
    assert any("6 of 6 decoded requests crossed chips" in ln
               for ln in lines), out.stdout[-3000:]
