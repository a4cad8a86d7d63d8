"""What ``correct`` rests on: the control fails the cell's limits, and
a run whose timed step is broken underneath comes out not correct."""
import time

import jax
import pytest

from conftest import shrink


def _cells(bench):
    return [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("cell", ["gcn-products.labor0",
                                  "gatv2-flickr.labor0", "gcn-products.ns"])
def test_the_control_fails_the_cell_limits(bench, cell, no_disk_cache):
    """The reference with float8 matmul operands, put in the program's
    place, against the reference, with the cell's batch and fanouts on
    a graph a test run holds."""
    from bench import check, control, harness
    limits = harness.load_cell(bench, cell)["limits"]
    failed = []
    for seed in (1, 2, 3):
        r = control.readings(bench, cell, seed,
                             shrink(num_vertices=60_000, batch=1024,
                                    fanouts=(10, 10, 10)),
                             check_chips=False)
        assert not check.verdict(r["half_batch"], limits)[0]
        failed.append(not check.verdict(r["control"], limits)[0])
    assert all(failed)


def _unchanged(real):
    """The step computes, then hands back the state it was given."""
    def dispatch(self, params, state, data, seeds, key):
        copy = jax.tree.map(lambda x: x.copy(), (params, state))
        _, _, m = real(self, *copy, data, seeds, key)
        return params, state, m
    return dispatch


def _half_batch(real):
    """The step trains on the first half of the batch, its loss the
    mean over that half."""
    def dispatch(self, params, state, data, seeds, key):
        n = seeds.shape[0] // 2
        return real(self, params, state, data, seeds.at[n:].set(-1), key)
    return dispatch


def _other_sample(real):
    """The sampler's answer is altered where it is produced: the step
    samples its blocks under another key."""
    def dispatch(self, params, state, data, seeds, key):
        return real(self, params, state, data, seeds,
                    jax.random.fold_in(key, 1))
    return dispatch


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _other_sample])
def test_a_broken_step_is_not_correct(bench, fault, monkeypatch,
                                      no_disk_cache):
    from bench import harness
    from repro.runtime.engine import TrainEngine
    monkeypatch.setattr(TrainEngine, "_dispatch",
                        fault(TrainEngine._dispatch))
    r = harness.run_cell(bench, "gcn-products.labor0", 5, 0.2, False,
                         time.perf_counter(), adjust=shrink(),
                         check_chips=False)
    assert not r["correct"], r["checks"]
