"""The ``include_ms`` reader on a synthetic trace: it sums the ops under
the ``include`` scope of every layer, and reads nothing in a program that
has no such scope."""
from types import SimpleNamespace

import pytest

from bench.harness import load_reader
from bench.trace import Device, Event, Trace

MS = 1e6  # ns
STEPS = 2


def _ctx(ops):
    """A reader context whose trace runs each (op path, ms) once per step."""
    events, t = [], 0.0
    for _ in range(STEPS):
        for k, (path, ms) in enumerate(ops):
            events.append(Event(f"%op.{k} = f32[] add()", t, ms * MS, path))
            t += ms * MS + MS
    trace = Trace([Device("/device:TPU:0", events, [])],
                  [Event("bench.window", 0.0, t, "")])
    return SimpleNamespace(trace=trace, steps=STEPS)


def test_include_reader_sums_the_inclusion_decision_per_step():
    # ns' exact-k selection lies inside include; the expansion before it,
    # the block built after it and a look-alike segment do not
    ops = [("jit(step)/sample/layer2/include/mul", 3.0),
           ("jit(step)/sample/layer2/include/segment_select/"
            "jit(segment_select_block_parallel)/pallas_call", 5.0),
           ("jit(step)/sample/layer1/include/lt", 7.0),
           ("jit(step)/sample/layer2/expand_seed_edges/gather", 11.0),
           ("jit(step)/sample/layer2/compact/add", 13.0),
           ("jit(step)/sample/layer2/included/add", 17.0)]
    ctx = _ctx(ops)
    assert load_reader("include_ms").read(ctx) == pytest.approx(3.0 + 5.0
                                                                + 7.0)
    assert load_reader("segment_select_ms").read(ctx) == pytest.approx(5.0)


def test_include_reader_finds_nothing_in_an_unscoped_program():
    # the parent program's paths: stage and primitive scopes, no include
    ops = [("jit(step)/sample/layer2/expand_seed_edges/gather", 11.0),
           ("jit(step)/sample/layer2/mul", 3.0),
           ("jit(step)/gather", 31.0),
           ("jit(step)/jit(_dedup)/pallas_call", 5.0),
           ("jit(sample)/add", 37.0)]
    assert load_reader("include_ms").read(_ctx(ops)) is None
