"""The training entry point, the chip smoke script and the compile cache.

``launch/train.py`` trains the paper's ResNet-50 (smoke widths) through
``build_cell`` -> ``make_ps_train_step`` -> ``PSExchange`` with gradient
accumulation, the path ``chip_smoke.py`` drives at full width on a TPU.
``chip_smoke.py`` must refuse any other platform without printing a
result.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
and only there.
"""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.train import main as train_main

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir(monkeypatch, tmp_path):
    """Point the entry points' compile cache at a temporary directory, and
    hand the process its own setting back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield tmp_path / "jc"
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


@pytest.mark.parametrize("strategy", ["pbox", "allreduce"])
def test_train_main_resnet50_smoke(cache_dir, strategy):
    out = train_main(["--arch", "resnet50", "--steps", "2", "--mesh", "1x1",
                      "--strategy", strategy, "--log-every", "1"])
    assert len(out["losses"]) == len(out["step_s"]) == 2
    assert all(math.isfinite(x) for x in out["losses"])
    assert out["meta"]["microbatches"] == 2
    assert out["meta"]["examples"] == 2
    assert out["compile_s"] > 0
    assert out["pflat"].shape == (1, out["meta"]["space"].flat_elems)
    assert bool(jax.numpy.isfinite(out["pflat"]).all())


def test_train_main_rejects_a_mesh_larger_than_the_devices(cache_dir):
    n = len(jax.devices())
    with pytest.raises(ValueError):
        train_main(["--arch", "resnet50", "--steps", "1",
                    "--mesh", f"{n + 1}x1"])


def test_compile_cache_follows_the_environment(cache_dir, monkeypatch):
    assert compile_cache.enable_compile_cache() == cache_dir
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    default = compile_cache.CHECKOUT / ".jax_cache"
    assert compile_cache.enable_compile_cache() == default
    assert default == REPO / ".jax_cache"


def _run(args, env_extra, cwd=REPO):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO / "src"),
                **env_extra})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_compile_cache_writes_only_where_the_environment_says(tmp_path):
    target = tmp_path / "cache"
    default = REPO / ".jax_cache"
    before = set(default.rglob("*")) if default.exists() else set()
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    p = _run(["-c", code], {"JAX_COMPILATION_CACHE_DIR": str(target)})
    assert p.returncode == 0, p.stderr
    assert any(target.iterdir())
    after = set(default.rglob("*")) if default.exists() else set()
    assert after == before


def test_compile_cache_keeps_apart_steps_whose_scopes_differ(tmp_path):
    """An entry serves only a program with the same scopes (a profile reads
    the phases from the executable's metadata), and a checkout moved to
    another path still finds its own entries."""
    code = ("import json, os, sys, jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "def f(x):\n"
            "    with jax.named_scope(sys.argv[1]):\n"
            "        return jnp.sin(x) * 2\n"
            "text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()\n"
            "print(sys.argv[1] in text, json.dumps(sorted(n for n in os.listdir(\n"
            "    os.environ['JAX_COMPILATION_CACHE_DIR']) if n.startswith('jit_f-'))))\n")
    for tree in ("a", "b"):  # two copies of a checkout at two paths
        launch = tmp_path / tree / "src" / "repro" / "launch"
        launch.mkdir(parents=True)
        (launch / "__init__.py").write_text("")
        (launch / "compile_cache.py").write_text(
            (REPO / "src/repro/launch/compile_cache.py").read_text())
        (tmp_path / tree / "step.py").write_text(code)
    cache = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    seen = []
    for tree, scope in (("a", "fwd"), ("b", "fwd"), ("a", "bwd")):
        root = tmp_path / tree
        p = _run([str(root / "step.py"), scope],
                 {**cache, "PYTHONPATH": str(root / "src")}, cwd=root)
        assert p.returncode == 0, p.stderr
        named, entries = p.stdout.strip().split(" ", 1)
        assert named == "True"
        seen.append(json.loads(entries))
    assert len(seen[0]) == 1
    assert seen[1] == seen[0]  # the moved checkout hits the same entry
    assert len(seen[2]) == 2  # other scopes: an entry of their own


def test_chip_smoke_refuses_a_cpu_host():
    p = _run([str(REPO / "chip_smoke.py")], {})
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    p = _run([str(tmp_path / "chip_smoke.py")], {"PYTHONPATH": ""},
             cwd=tmp_path)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
