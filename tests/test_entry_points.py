"""The chip-facing entry points, on a host with no chip.

``bench.py`` and ``chip_smoke.py`` must fail (non-zero, no device number)
rather than run on the CPU; the compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``; the
device backend pads every batch to one of the sizes it compiled; the
multichip dry run refuses a platform it cannot use.  Nothing here
compiles a kernel.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from lighthouse_tpu.crypto.bls.jax_backend import backend as BK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, tmp_path, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path), **env)
    return subprocess.run([sys.executable, os.path.join(ROOT, script)],
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path), env=full)


def test_bench_without_a_chip_fails_and_prints_no_number(tmp_path):
    proc = _run("bench.py", tmp_path)
    assert proc.returncode != 0
    assert "tpu_batch_verify" not in proc.stdout
    assert "not measured" in proc.stderr


def test_chip_smoke_without_a_chip_fails_with_ok_false(tmp_path):
    proc = _run("chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
    assert "device" not in last


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    jax.config.update("jax_compilation_cache_dir", "/set-by-jax")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert BK.enable_compile_cache() == str(tmp_path)
    # the directory is JAX's to read from the variable: nothing set here
    assert jax.config.jax_compilation_cache_dir == "/set-by-jax"


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = BK.enable_compile_cache()
    # CPU executables are kept per host CPU feature set
    assert os.path.dirname(path) == os.path.join(ROOT, ".jax_cache")
    assert os.path.basename(path).startswith("cpu-")
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("n, padded", [
    (1, 64), (64, 64), (65, 256), (300, 1024), (1024, 1024), (1025, 2048),
    (5000, 8192),
])
def test_backend_pads_to_its_compiled_sizes(n, padded):
    be = BK.JaxBackend(batch_sizes=(1024, 64, 256))
    assert be._padded_size(n) == padded
    assert be.min_batch == 64


def test_backend_default_sizes_are_powers_of_two_from_min_batch():
    be = BK.JaxBackend(min_batch=8)
    assert [be._padded_size(n) for n in (1, 8, 9, 100)] == [8, 8, 16, 128]


def test_warm_compile_refuses_a_size_the_backend_never_pads_to():
    be = BK.JaxBackend(batch_sizes=(64, 256))
    assert be.warm_compile(128) is False
    assert be._kernels == {}


def test_dryrun_multichip_refuses_a_mesh_it_cannot_have():
    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="needs a virtual CPU mesh"):
        graft.dryrun_multichip(len(jax.devices()) + 1)
