"""The reduction from a profiler trace to numbers, on one small trace
recorded on a TPU v5e (``benchmark/testdata``): 23 steps of
``deepfm_dim9_array.train_zipf``, with the step program's optimized HLO
beside it for the scope names."""

import gzip
import os

import pytest

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata")
TRACE = os.path.join(DATA, "array_step.xplane.pb.gz")
HLO = os.path.join(DATA, "array_step.hlo.txt.gz")


@pytest.fixture(scope="module")
def reduced():
    with gzip.open(HLO, "rt") as f:
        return trace_reduce.reduce(TRACE, f.read())


def test_union_merges_overlaps():
    total, merged = trace_reduce._union([(0, 4), (2, 6), (10, 11), (11, 12)])
    assert total == 8 and merged == [[0, 6], [10, 12]]


def test_scope_names_unwrap_jax_wrappers():
    hlo = '''
  %fusion.13 = f32[8,9]{0,1} fusion(%a), kind=kCustom, calls=%fc, metadata={op_name="jit(step_fn)/jit(push_a2a)/scatter" stack_frame_id=9}
  ROOT %dot.2 = f32[4,4]{1,0} dot(%x, %y), metadata={op_name="jit(step_fn)/transpose(jvp(DeepFM))/MLP_0/dot_general"}
  %copy.1 = f32[4]{0} copy(%z)
'''
    names = trace_reduce.scope_names(hlo)
    assert names == {"fusion.13": "step_fn/push_a2a/scatter",
                     "dot.2": "step_fn/DeepFM/MLP_0/dot_general"}
    assert trace_reduce.op_label(
        "%fusion.13 = f32[8,9]{0,1} fusion(f32[8,9] %a)", names) == \
        "step_fn/push_a2a/scatter/fusion.13"
    assert trace_reduce.op_label("%copy.1 = f32[4]{0} copy(%z)", names) == \
        "copy.1"


def test_gap_owner_is_the_innermost_span():
    spans = [("benchmark.fit", 0, 100), ("step", 10, 30),
             ("benchmark.wait", 12, 20)]
    assert trace_reduce._gap_owners(spans, [15, 25, 50, 200]) == \
        ["benchmark.wait", "step", "benchmark.fit", "no_host_span"]


def test_busy_and_idle_of_the_recorded_trace(reduced):
    assert reduced["chips"] == 1
    assert reduced["steps"] == RECORDED["steps"]
    assert reduced["window_s"] == pytest.approx(RECORDED["window_s"], rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_scope_and_kind_sums_of_the_recorded_trace(reduced):
    steps = reduced["steps"]
    push = reduced["scope_s"]["push_a2a"] / steps
    pull = reduced["scope_s"]["pull_a2a"] / steps
    # the scatter into the big table is most of the step, the pull a tenth
    assert 0.035 < push < 0.050 and 0.003 < pull < 0.006
    assert reduced["kind_s"]["scatter"] > reduced["kind_s"]["gather"] > 0
    assert "collective" not in reduced["kind_s"]      # one chip
    top = reduced["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("step_fn/push_a2a/scatter/")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_idle_gaps_are_attributed_to_host_spans(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert gaps and all(v > 0 for v in gaps.values())
    assert sum(gaps.values()) <= reduced["window_s"] - reduced["busy_s"] + 1e-9


# what the reduction read from the recorded trace when it was recorded
RECORDED = {"steps": 23, "window_s": 1.0499535370000002, "busy_s": 1.049387769}
