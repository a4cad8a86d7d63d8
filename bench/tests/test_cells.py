"""Every cell end to end at a tiny size on the CPU, with the Pallas
kernels interpreted; and the refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import ROOT, shrink


@pytest.fixture
def pallas_everywhere(monkeypatch):
    """Dispatch every graph op and frontier primitive to the Pallas
    kernels (interpreted off the TPU), as ``auto`` does on the chip."""
    import repro.ops as ops
    from repro.ops import backend

    real = backend.resolve_backend

    def resolve(name=None):
        return "pallas" if name in (None, "auto") else real(name)

    monkeypatch.setattr(backend, "resolve_backend", resolve)
    monkeypatch.setattr(ops, "resolve_backend", resolve)


def _cells(bench_file=os.path.join(ROOT, "BENCHMARK.json")):
    import json
    with open(bench_file) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _cells())
def test_cell_runs_end_to_end(bench, cell, trace, pallas_everywhere,
                              no_disk_cache):
    from bench import harness
    r = harness.run_cell(bench, cell, 2**31 + 17, 0.5, bool(trace),
                         time.perf_counter(), adjust=shrink(),
                         check_chips=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    names = set(r["metrics"])
    if trace:
        # no device plane on the CPU: the trace readers find nothing
        # and their metrics are left out
        assert {"graph_build_s", "sampled_vertices_per_step"} <= names
        assert "breakdown" in r
    else:
        assert names == {"train_seeds_per_s", "setup_s"}
    assert list(r)[-2] == "checks" and list(r)[-1] == "_log"


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gcn-products.labor0",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_fails_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
