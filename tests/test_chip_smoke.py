"""The serving entry points and ``chip_smoke.py`` on the CPU: the smoke
refuses a host without a TPU, and its phases and checks run end to end
on a tiny bf16 model (one device in process, four virtual devices in a
subprocess)."""

import dataclasses
import functools
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_bf16():
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen3-4b", reduced=True),
                               compute_dtype="bfloat16")


def _env(**extra):
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return {**env, **extra}


# ------------------------------------------------------------ serve.py

def test_serve_defaults_to_published_widths():
    from repro.launch.serve import parse_args
    assert parse_args([]).reduced is False
    assert parse_args(["--reduced"]).reduced is True


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_batch_server_weights_in_compute_dtype_with_mesh_shardings(
        compute_dtype):
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer

    cfg = dataclasses.replace(get_config("qwen3-4b", reduced=True),
                              compute_dtype=compute_dtype)
    mesh = make_local_mesh()
    server = BatchServer(cfg, mesh, max_len=32)
    leaves = jax.tree.leaves(server.params)
    assert {str(x.dtype) for x in leaves} == {compute_dtype}
    assert sum(x.size for x in leaves) == cfg.param_count()
    placed = jax.tree.map(lambda x, s: x.sharding == s and s.mesh == mesh,
                          server.params, server.param_shardings)
    assert all(jax.tree.leaves(placed))


def test_local_mesh_takes_a_device_subset():
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == jax.devices()[:1]


# ------------------------------------------------------- compile cache

@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defers_to_env(monkeypatch, restore_cache_dir,
                                     tmp_path):
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_dir_in_checkout(
        monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CACHE_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


# -------------------------------------------------------- chip_smoke.py

def test_chip_smoke_refuses_cpu():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=240,
                          env=_env(), cwd=REPO)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compare_logits_flags_argmax_and_size(smoke):
    want = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 1.0]], np.float32)
    assert smoke.compare_logits(want + 0.01, want) == []
    flipped = want.copy()
    flipped[0, 2] = 2.05
    fails = smoke.compare_logits(flipped, want)
    assert any("argmax differs in rows [0]" in f for f in fails)
    assert any("max|diff|" in f for f in smoke.compare_logits(want * 2,
                                                               want))
    bad = want.copy()
    bad[1, 1] = np.nan
    assert smoke.compare_logits(bad, want) == ["non-finite logits"]


def test_chip_smoke_one_device_phases_on_cpu(smoke):
    reqs = functools.partial(smoke.make_requests, batch=4, gen=6,
                             lens=(4, 20))
    fails = smoke.smoke_one_chip(_tiny_bf16(), jax.devices()[0],
                                 max_len=32, requests=reqs)
    assert fails == []


def test_chip_smoke_four_device_comparison_on_virtual_cpus():
    code = (
        "import dataclasses, functools, importlib.util, sys, jax\n"
        f"spec = importlib.util.spec_from_file_location('s', "
        f"{str(REPO / 'chip_smoke.py')!r})\n"
        "s = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(s)\n"
        "from repro.configs import get_config\n"
        "cfg = dataclasses.replace(get_config('qwen3-4b', reduced=True),\n"
        "                          compute_dtype='bfloat16')\n"
        "reqs = functools.partial(s.make_requests, batch=4, gen=6,\n"
        "                         lens=(4, 20))\n"
        "fails = s.smoke_four_chips(cfg, jax.devices(), max_len=32,\n"
        "                           requests=reqs)\n"
        "print('FAILS', fails)\n"
        "sys.exit(1 if fails else 0)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "model axis 4: mesh {'data': 1, 'model': 4}" in proc.stdout
