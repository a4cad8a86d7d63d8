"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): two runs of a program ``step`` with the frontier
sort, SpMM and edge-softmax kernels, 10 ms of host sleep after each,
then one run of a program ``sample``, all in a ``bench.window`` span;
with the op paths of ``step``'s compiled HLO beside it."""
import json
import os

import pytest

from bench.trace import Trace, instruction, op_paths

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(FIXTURES, "small.paths.json")) as f:
        paths = json.load(f)
    return Trace.from_file(os.path.join(FIXTURES, "small.xplane.pb"), paths)


def test_one_chip_and_a_window_that_holds_every_op(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    lo, hi = trace.window_ns()
    span = [s for s in trace.spans if s.name == "bench.window"][0]
    ops = trace.devices[0].ops
    assert lo == min(span.start_ns, min(e.start_ns for e in ops))
    assert hi == max(span.end_ns, max(e.end_ns for e in ops))
    assert trace.window_s == pytest.approx((hi - lo) * 1e-9)


def test_busy_is_the_union_of_op_intervals(trace):
    # an independent union: walk every interval edge in order
    ops = trace.devices[0].ops
    points = sorted({p for e in ops for p in (e.start_ns, e.end_ns)})
    busy = sum(b - a for a, b in zip(points, points[1:])
               if any(e.start_ns <= a and e.end_ns >= b for e in ops))
    assert trace.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < trace.busy_s < trace.window_s


@pytest.mark.parametrize("wrapper", ["jit(spmm_sorted)/",
                                     "jit(edge_softmax_stats)/",
                                     "jit(compact_block_parallel)/"])
def test_kernel_time_sums_the_kernel_ops(trace, wrapper):
    def match(path):
        return wrapper in path and path.endswith("pallas_call")
    evs = [e for e in trace.devices[0].ops if match(e.path)]
    # each kernel ran in both runs of the program, as a custom call
    assert len(evs) >= 2
    assert all("custom_call_target=\"tpu_custom_call\"" in e.name
               for e in evs)
    assert trace.kernel_seconds(match) == pytest.approx(
        sum(e.dur_ns for e in evs) * 1e-9)
    assert trace.kernel_seconds(lambda p: False) is None


def test_programs_by_name(trace):
    seconds, runs = trace.module_seconds("jit_sample")
    assert runs == 1 and 0 < seconds < trace.busy_s
    assert trace.module_seconds("jit_step")[1] == 2
    assert trace.module_seconds("jit_nothing") is None


def test_breakdown(trace):
    top = trace.top_ops(10)
    assert 1 <= len(top) <= 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    gaps = trace.idle_gaps(10)
    # the longest idle stretches are the two 10 ms host sleeps
    assert [g[0] for g in gaps[:2]] == ["bench.host", "bench.host"]
    assert all(g[1] >= 0.009 for g in gaps[:2])


def test_op_paths_read_instruction_names_and_op_names():
    hlo = ('  %spmm_sorted.5 = f32[8,128]{1,0} custom-call(s32[8]{0} %a), '
           'custom_call_target="tpu_custom_call", frontend_attributes='
           '{kernel_metadata={}}, metadata={op_name="jit(step)/jit('
           'spmm_sorted)/pallas_call" stack_frame_id=9}\n'
           '  ROOT %tuple.3 = (f32[]) tuple(f32[] %b)\n')
    assert op_paths(hlo) == {
        "spmm_sorted.5": "jit(step)/jit(spmm_sorted)/pallas_call"}
    assert instruction(hlo.splitlines()[1]) == "tuple.3"
