"""Process-wide JAX settings: where the persistent compile cache lives, and
the CPU pin that keeps host-side processes off a chip the trainer holds."""
import os
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import waiters
from repro import jaxenv
from repro.core import maps as M, shm as SH

REPO = Path(__file__).resolve().parents[1]


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.update(extra)
    return env


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = jaxenv.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert path == str(REPO / ".jax_cache")


def test_compile_cache_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    assert jaxenv.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev


def test_compile_cache_entries_land_in_the_env_dir(tmp_path):
    code = ("from repro.jaxenv import use_compile_cache; use_compile_cache()\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((8, 8)))"
            ".compile()\n")
    out = waiters.run_cli(
        [sys.executable, "-c", code], cwd=str(tmp_path),
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert any((tmp_path / "cache").iterdir())


@pytest.mark.parametrize("verb", [["--once"], ["agg", "--tree", "--once"]],
                         ids=["summary", "tree-agg"])
def test_daemon_never_starts_an_accelerator_backend(tmp_path, verb):
    """Asked for the TPU platform on a host without one, the daemon still
    runs: it pins itself to the CPU before any backend starts (the tree
    aggregator folds through jitted device reductions)."""
    root = str(tmp_path / "shm")
    specs = [M.MapSpec("pin_hist", M.MapKind.LOG2HIST)]
    if verb[0] == "agg":
        region = SH.ShmRegion.create(root, specs, worker_id="w0")
    else:
        region = SH.ShmRegion.create(root, specs)
    st = M.init_states(specs, np)
    st["pin_hist"]["bins"][3] = 7
    region.publish_device(st)
    out = waiters.run_cli(
        [sys.executable, "-m", "repro.core.daemon", root] + verb,
        cwd=str(tmp_path), env=_env(JAX_PLATFORMS="tpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    if verb[0] == "agg":
        got = SH.GlobalView.attach(root).snapshot("pin_hist")["bins"]
        assert int(got[3]) == 7
