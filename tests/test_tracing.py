"""The program's own measurement points: the stage and primitive scopes
in the fused step's op paths, the per-layer counts it returns, and the
per-layer overflow counter of the ledger poll (runtime/spans.py)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.scopes import unwrap
from bench.trace import op_paths
from repro.core import samplers
from repro.graph.generators import DatasetSpec, generate
from repro.models import gnn as gnn_models
from repro.optim import adam
from repro.runtime import inject as inject_lib
from repro.runtime import spans
from repro.runtime.engine import TrainEngine

FANOUTS = (4, 4)
SAMPLERS = ("labor-0", "ns")


@pytest.fixture(scope="module")
def ds():
    return generate(DatasetSpec("mini", 2000, 12.0, 16, 5, 0.5, 0.2, 0.6,
                                1000), scale=1.0, seed=0)


def _engine(ds, name, plan=None):
    s = samplers.from_dataset(name, ds, batch_size=32, fanouts=FANOUTS,
                              safety=3.0)
    # the model's Pallas kernels (interpreted off the TPU), as on the
    # chip: their backward pass is what reads the block's src_perm
    eng = TrainEngine(s, gnn_models.gcn_apply, adam.AdamConfig(lr=1e-2),
                      backend="pallas", inject=plan)
    params = gnn_models.gcn_init(jax.random.key(0), ds.features.shape[1],
                                 16, int(ds.labels.max()) + 1, len(FANOUTS))
    return eng, params, eng.make_data_from_dataset(ds)


def _seeds(i):
    return jnp.asarray(np.arange(32 * i, 32 * (i + 1), dtype=np.int32))


@pytest.fixture(scope="module")
def step_paths(ds):
    """{sampler: op paths of the compiled fused step's HLO}."""
    out = {}
    for name in SAMPLERS:
        eng, params, data = _engine(ds, name)
        state = eng.init_state(params)
        compiled = eng.step_fn.lower(
            params, state.opt, data.graph, data.features, data.labels,
            _seeds(0), jax.random.key(1)).compile()
        out[name] = list(op_paths(compiled.as_text()).values())
    return out


def _scopes(path):
    """The scopes an op lies under, autodiff wrappers taken off."""
    return {unwrap(s) for s in path.split("/")}


_CASES = [(n, s) for n in SAMPLERS for s in (
    *spans.STAGES, spans.layer(0), spans.layer(1), spans.HASH_DEDUP,
    spans.EXPAND_SEED_EDGES, spans.COMPACT_PERM, spans.INCLUDE,
    "jvp(model)", "transpose(jvp(model))")] + [("ns", spans.SEGMENT_SELECT)]


@pytest.mark.parametrize("sampler,scope", _CASES)
def test_scope_is_in_the_fused_step(step_paths, sampler, scope):
    if "(" in scope:   # an autodiff-wrapped segment, as it is written
        assert any(scope in p.split("/") for p in step_paths[sampler])
    else:
        assert any(scope in _scopes(p) for p in step_paths[sampler])


def test_labor_has_no_segment_select(step_paths):
    assert not any(spans.SEGMENT_SELECT in _scopes(p)
                   for p in step_paths["labor-0"])


def test_include_holds_the_exact_k_selection(step_paths):
    # ns' exact-k path runs segment_select inside the inclusion scope;
    # the expansion before it and the block built after it lie outside
    paths = step_paths["ns"]
    select = [p for p in paths if spans.SEGMENT_SELECT in _scopes(p)]
    assert select and all(spans.INCLUDE in _scopes(p) for p in select)
    for scope in (spans.EXPAND_SEED_EDGES, spans.COMPACT_PERM):
        assert not any({scope, spans.INCLUDE} <= _scopes(p) for p in paths)


_METADATA = re.compile(r",? metadata=\{[^}]*\}")
# the module's source tables (files, functions, locations, stack
# frames), which the metadata of each instruction points into
_SOURCE_TABLE = re.compile(
    r"^(\d+ |FileNames$|FunctionNames$|FileLocations$|StackFrames$)")


def _without_metadata(hlo_text):
    return _METADATA.sub("", "\n".join(
        l for l in hlo_text.splitlines() if not _SOURCE_TABLE.match(l)))


@pytest.mark.parametrize("sampler", SAMPLERS)
def test_include_scope_changes_only_metadata(ds, sampler, monkeypatch):
    """The optimised HLO of the fused step with the ``include`` scope
    equals the one without it, metadata aside: naming the inclusion
    decision costs nothing at run time."""
    def hlo():
        eng, params, data = _engine(ds, sampler)
        state = eng.init_state(params)
        text = eng.step_fn.lower(
            params, state.opt, data.graph, data.features, data.labels,
            _seeds(0), jax.random.key(1)).compile().as_text()
        return _without_metadata(text)

    scoped = hlo()
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: (contextlib.nullcontext()
                                      if name == spans.INCLUDE
                                      else real(name)))
    assert hlo() == scoped


@pytest.mark.parametrize("sampler", SAMPLERS)
def test_every_op_of_the_step_is_under_a_stage(step_paths, sampler):
    # ops of called computations (sort comparators, reducers) carry a
    # path relative to their computation; the step's own ops start at
    # its jit
    own = [p for p in step_paths[sampler] if p.startswith("jit(step)/")]
    assert own
    outside = [p for p in own if not _scopes(p) & set(spans.STAGES)]
    assert outside == []


@pytest.mark.parametrize("sampler", SAMPLERS)
def test_layer_counts_match_the_staged_blocks(ds, sampler):
    eng, params, data = _engine(ds, sampler)
    state = eng.init_state(params)
    seeds, key = _seeds(1), jax.random.key(7)
    blocks = eng.staged.sample(data.graph, seeds, key)
    _, _, m = eng.step(params, state, data, seeds, key)
    counts = np.asarray(m["layer_counts"])
    want = [[int(b.num_expanded), int(b.num_edges), int(b.num_next)]
            for b in blocks]
    assert counts.dtype == np.int32 and counts.shape == (len(FANOUTS), 3)
    assert counts.tolist() == want
    assert counts[-1, 2] == int(m["sampled_v"])
    assert counts[:, 1].sum() == int(m["sampled_e"])
    # the expanded in-edges bound the sampled ones, and fit their cap
    caps = eng.sampler.caps
    assert all(e <= x <= c.expand_cap
               for (x, e, _), c in zip(want, caps))


def test_overflow_storm_counts_overflow_by_layer(ds):
    plan = inject_lib.parse("overflow_storm@1:1")
    eng, params, data = _engine(ds, "labor-0", plan=plan)
    state = eng.init_state(params)
    assert eng.stats.overflow_by_layer == []
    for i in range(3):
        params, state, _ = eng.step(params, state, data, _seeds(i),
                                    jax.random.fold_in(jax.random.key(1), i),
                                    tag=i)
    params, state, _ = eng.flush(params, state, data)
    assert plan.all_fired()
    assert eng.stats.overflow_replays == 1
    # the storm sets every layer's flag of the one polled batch
    assert eng.stats.overflow_by_layer == [1] * len(FANOUTS)
