"""The readers of the program's scopes on a synthetic
trace: which ops each device metric sums and what it leaves out."""
from types import SimpleNamespace

import pytest

from bench.harness import load_reader
from bench.trace import Device, Event, Trace

MS = 1e6  # ns

# (op path, device ms per op), each op run once in each of 2 steps
OPS = [
    ("jit(step)/sample/layer0/expand_seed_edges/jit(expand_seed_edges)/"
     "gather", 3.0),
    ("jit(step)/sample/layer0/hash_dedup/jit(_dedup)/pallas_call", 5.0),
    ("jit(step)/sample/layer1/hash_dedup/jit(_dedup)/gather", 7.0),
    ("jit(step)/sample/layer1/segment_select/jit(segment_select_block_"
     "parallel)/pallas_call", 11.0),
    ("jit(step)/sample/layer1/compact_perm/sort", 13.0),
    ("jit(step)/feature_gather/gather", 17.0),
    ("jit(step)/jvp(model)/jit(spmm_block)/dot_general", 19.0),
    ("jit(step)/transpose(jvp(model))/scatter-add", 23.0),
    ("jit(step)/optimizer/mul", 29.0),
    # look-alikes: a bare XLA op name, another program, a longer name
    ("jit(step)/gather", 31.0),
    ("jit(sample)/layer0/add", 37.0),
    ("jit(step)/sampled/model_ops/hash_dedup_old/add", 41.0),
]
STEPS = 2


def _trace(ops=OPS):
    events, t = [], 0.0
    for _ in range(STEPS):
        for k, (path, ms) in enumerate(ops):
            events.append(Event(f"%op.{k} = f32[] add()", t, ms * MS, path))
            t += ms * MS + MS
    spans = [Event("bench.window", 0.0, t, "")]
    return Trace([Device("/device:TPU:0", events, [])], spans)


def _ctx(trace):
    return SimpleNamespace(trace=trace, steps=STEPS)


def test_a_loop_counts_once_with_the_ops_of_its_body():
    # a while op's event spans its body's op events, all under hash_dedup
    ops = [Event("%while.1 = s32[] while()", 0.0, 10 * MS,
                 "jit(step)/sample/layer0/hash_dedup/jit(_dedup)/while"),
           Event("%fusion.2 = s32[] fusion()", 1 * MS, 4 * MS,
                 "jit(step)/sample/layer0/hash_dedup/jit(_dedup)/while/body/"
                 "add"),
           Event("%fusion.3 = s32[] fusion()", 6 * MS, 3 * MS,
                 "jit(step)/sample/layer0/hash_dedup/jit(_dedup)/while/body/"
                 "pallas_call"),
           Event("%fusion.4 = s32[] fusion()", 12 * MS, 2 * MS,
                 "jit(step)/sample/layer0/hash_dedup/gather")]
    trace = Trace([Device("/device:TPU:0", ops, [])],
                  [Event("bench.window", 0.0, 14 * MS, "")])
    ctx = SimpleNamespace(trace=trace, steps=1)
    assert load_reader("dedup_ms").read(ctx) == pytest.approx(12.0)
    assert load_reader("step_sample_ms").read(ctx) == pytest.approx(12.0)


@pytest.mark.parametrize("name,want_ms", [
    ("step_sample_ms", 3.0 + 5.0 + 7.0 + 11.0 + 13.0),
    ("step_gather_ms", 17.0),
    ("step_model_ms", 19.0 + 23.0),
    ("dedup_ms", 5.0 + 7.0),
    ("expand_ms", 3.0),
    ("segment_select_ms", 11.0),
])
def test_scope_reader_sums_its_ops_per_step(name, want_ms):
    got = load_reader(name).read(_ctx(_trace()))
    assert got == pytest.approx(want_ms, rel=1e-12)


@pytest.mark.parametrize("name", ["step_sample_ms", "step_gather_ms",
                                  "step_model_ms", "dedup_ms", "expand_ms",
                                  "segment_select_ms"])
def test_scope_reader_finds_nothing_in_an_unscoped_program(name):
    # the parent program's paths: wrappers and bare op names only
    ops = [("jit(step)/gather", 31.0),
           ("jit(step)/jit(_dedup)/pallas_call", 5.0),
           ("jit(step)/jvp(jit(spmm_block))/dot_general", 19.0),
           ("jit(sample)/add", 37.0)]
    assert load_reader(name).read(_ctx(_trace(ops))) is None


def test_stages_and_look_alikes_partition_the_ops():
    from bench.scopes import under
    stages = ("sample", "feature_gather", "model", "optimizer")
    inside = [p for p, _ in OPS if any(under(p, s) for s in stages)]
    assert len(inside) == 9
    assert not any(under("jit(step)/gather", s) for s in stages)

