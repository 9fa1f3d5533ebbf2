"""Kernel cost functions, the peak table, the chip check and imports."""

import os
import subprocess
import sys

import pytest

from conftest import ROOT


def test_nest_gemm_cost_on_known_shapes():
    from bench.kernels import nest_gemm
    assert nest_gemm.cost([(2, 3, 4)]) == (48.0, 4.0 * (6 + 12 + 8))
    f, b = nest_gemm.cost([(1, 3072, 256000)])
    assert f == 2.0 * 3072 * 256000
    assert b == 4.0 * (3072 + 3072 * 256000 + 256000)
    assert nest_gemm.cost([(2, 3, 4)], elem_bytes=2)[1] == 2.0 * 26


def test_fused_chain_cost_keeps_interior_activations_on_chip():
    from bench.kernels import fused_chain
    dims = [(256, 1536, 512), (256, 64, 256)]
    f, b = fused_chain.cost(dims)
    assert f == 2.0 * (256 * 1536 * 512 + 256 * 64 * 256)
    assert b == 4.0 * (256 * 1536 + 1536 * 512 + 64 * 256 + 256 * 256)


def test_flash_decode_cost_reads_each_requests_kv_once():
    from bench.kernels import flash_decode
    f, b = flash_decode.cost([(2, 128, 512), (2, 512, 128)])
    assert f == 2.0 * (2 * 128 * 512 + 2 * 512 * 128)
    assert b == 4.0 * 2 * (128 + 128 * 512 + 512 * 128 + 128)


def test_peaks_are_the_published_v5e_numbers():
    from bench import peaks
    p = peaks.for_device("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


def test_unknown_device_kind_is_an_error():
    from bench import peaks
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(KeyError):
            peaks.for_device(kind)


def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite3moe.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minitron4b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_no_module_loads_a_backend_at_import():
    code = (
        "import sys; sys.path[:0] = ['.', 'src']\n"
        "import pkgutil, importlib, bench, bench.metrics, bench.kernels\n"
        "import bench.models, bench.models.gemm_stream\n"
        "for pkg in (bench, bench.metrics, bench.kernels, bench.models,\n"
        "            bench.models.gemm_stream):\n"
        "    for m in pkgutil.iter_modules(pkg.__path__):\n"
        "        if m.name not in ('tests', 'run', 'control'):\n"
        "            importlib.import_module(pkg.__name__ + '.' + m.name)\n"
        "import bench.run, bench.control\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
