"""The benchmark's own tests, run on the CPU:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(``pyproject.toml`` points the repository's test run at ``tests/``
alone, so these are run by name.)"""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def shrink(num_vertices=1000, batch=16, fanouts=(2, 2, 2)):
    """A cell cut to a size the CPU runs in seconds; every other setting
    of its configuration and traffic stays."""
    def adjust(spec):
        spec = copy.deepcopy(spec)
        spec["config"]["graph"]["num_vertices"] = num_vertices
        spec["traffic"].update(batch_size=batch, fanouts=list(fanouts))
        return spec
    return adjust


@pytest.fixture
def no_disk_cache(monkeypatch, tmp_path):
    """Graphs in a temporary directory, JAX's persistent cache off."""
    from bench import graph, harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "CACHE", str(tmp_path))
    monkeypatch.setattr(graph, "CACHE_DIR", str(tmp_path / "graphs"))
    real = graph.load_or_build
    monkeypatch.setattr(graph, "load_or_build",
                        lambda name, spec, cache_dir=None:
                        real(name, spec, str(tmp_path / "graphs")))
