"""The reduction from a trace to time per named stage.

The two rules, on hand-made events and HLO: an operation belongs to the
deepest stage in its label path, and an operation that holds others (a
``while``, a ``conditional``) counts only the part of its interval they
leave uncovered, so the stages add up to the device's busy time. Then the
whole reduction on one small trace recorded on a TPU v5e
(``benchmark/testdata``): 21 steps of ``deepfm_dim9_hash.train_zipf`` with
the stages in the program, the step's optimized HLO beside it.
"""

import gzip
import os

import pytest

from benchmark import stage_reduce, trace_reduce

DATA = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata")
TRACE = os.path.join(DATA, "hash_step_stages.xplane.pb.gz")
HLO = os.path.join(DATA, "hash_step_stages.hlo.txt.gz")


@pytest.mark.parametrize("intervals, own", [
    # a while holds two body operations and a gap between them
    ([(0, 100), (10, 30), (50, 90)], [40, 20, 40]),
    # siblings that touch, nothing nested
    ([(0, 10), (10, 25), (30, 31)], [10, 15, 1]),
    # a while in a conditional: each level gives up what the next covers
    ([(0, 100), (20, 80), (30, 40), (40, 70)], [40, 20, 10, 30]),
    # given out of order: the order of the answer is the order given
    ([(50, 90), (0, 100), (10, 30)], [40, 40, 20]),
    ([], []),
], ids=["nested_while", "siblings", "two_levels", "unordered", "empty"])
def test_self_time_leaves_out_what_the_children_cover(intervals, own):
    assert stage_reduce.self_times(intervals) == own
    if intervals:       # which is why the stages add up to the busy time
        assert sum(own) == trace_reduce._union(intervals)[0]


HAND_MADE = '''
HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %gather.9 = s32[8]{0} gather(%param_0.1, %param_0.1), metadata={op_name="gather"}
}

%body.1 (arg.1: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg.1 = (s32[], s32[8]{0}) parameter(0)
  %scatter.3 = s32[8]{0} scatter(%arg.1), metadata={op_name="jit(step_fn)/jit(hash_push_a2a)/jit(probe)/while/body/scatter"}
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(%scatter.3)
}

ENTRY %main.1 (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %sort.1 = s32[8]{0} sort(%p.1), metadata={op_name="jit(step_fn)/jit(hash_push_a2a)/jit(push_routed)/jit(dedup)/sort"}
  %fusion.1 = s32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/jit(hash_push_a2a)/jit(push_routed)/jit(route)/jit(_take)/gather"}
  %slice_reduce_fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1
  %while.1 = (s32[], s32[8]{0}) while(%slice_reduce_fusion.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(step_fn)/jit(hash_push_a2a)/jit(probe)/while"}
  %dot.1 = f32[4]{0} dot(%p.1, %p.1), metadata={op_name="jit(step_fn)/jit(dense_bwd)/transpose(jvp(DeepFM))/dot_general"}
  %add.1 = s32[]{0} add(%p.1, %p.1), metadata={op_name="jit(step_fn)/add"}
  ROOT %copy.1 = s32[8]{0} copy(%p.1)
}
'''


@pytest.mark.parametrize("instruction, stage", [
    ("sort.1", "dedup"),                 # deeper than push_routed
    ("fusion.1", "route"),
    ("gather.9", "route"),               # its caller's: the fusion's
    ("slice_reduce_fusion.2", "route"),  # its operand's: no metadata
    ("while.1", "probe"),
    ("scatter.3", "probe"),
    ("tuple.1", "probe"),                # its caller's: the while's
    ("dot.1", "dense_bwd"),
    ("add.1", None),                     # in the step, in no stage
    ("copy.1", None),                    # nothing to inherit
], ids=lambda v: v if isinstance(v, str) and "." in v else "")
def test_an_instruction_belongs_to_the_deepest_stage(instruction, stage):
    assert stage_reduce.instruction_stages(HAND_MADE).get(instruction) \
        == stage


def test_an_hlo_without_stage_names_reads_as_nothing():
    """The parent of the PR that named the stages: its readers stay
    silent."""
    with gzip.open(os.path.join(DATA, "array_step.hlo.txt.gz"), "rt") as f:
        older = f.read()
    assert stage_reduce.instruction_stages(older) == {}
    assert stage_reduce.reduce(
        os.path.join(DATA, "array_step.xplane.pb.gz"), older) is None
    run = {"trace_dir": None, "step_hlo": None}
    assert stage_reduce.stage_ms_per_step(run, "dedup") is None
    assert stage_reduce.host_ms_per_step(run, "trainer.dispatch") is None


@pytest.fixture(scope="module")
def table():
    with gzip.open(HLO, "rt") as f:
        return stage_reduce.reduce(TRACE, f.read())


def test_stages_of_the_recorded_trace_add_up_to_its_busy_time(table):
    assert table["chips"] == 1 and table["steps"] == RECORDED["steps"]
    assert table["busy_s"] == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    assert sum(table["stage_s"].values()) == pytest.approx(
        table["busy_s"], rel=0.01)
    assert table["stage_s"].get("unattributed", 0.0) < 0.05 * table["busy_s"]


def test_probe_and_the_apply_stages_of_the_recorded_trace(table):
    per_step = {k: v * 1e3 / table["steps"]
                for k, v in table["stage_s"].items()}
    # the two probe-and-insert loops are half of a hash step
    assert per_step["probe"] > 40.0
    for stage in ("dedup", "init_rows", "resolve", "apply_gather",
                  "apply_update", "apply_scatter", "dense_fwd", "dense_bwd",
                  "dense_update"):
        assert per_step[stage] > 0.0, stage
    assert not table["branch_s"]            # one chip: no push branches


def test_host_spans_of_the_recorded_trace(table):
    assert table["host_steps"] > 0
    for name in stage_reduce.HOST_SPANS:
        assert table["host_s"][name] > 0.0, name
    # a step span holds its three children
    inside = sum(table["host_s"][n] for n in stage_reduce.HOST_SPANS[1:])
    assert inside <= table["host_s"]["step"]


def test_a_host_span_outside_the_device_window_is_left_out(monkeypatch):
    class Event:
        def __init__(self, name, start_ns, duration_ns):
            self.name, self.start_ns = name, start_ns
            self.duration_ns = duration_ns

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Data:
        planes = [
            Plane("/device:TPU:0", [
                Line("XLA Ops", [
                    Event("%while.1 = (s32[]) while(...)", 1000, 100),
                    Event("%scatter.3 = s32[8] scatter(...)", 1010, 60),
                    Event("%sort.1 = s32[8] sort(...)", 1200, 50),
                    Event("%copy.1 = s32[8] copy(...)", 1300, 10)]),
                Line("XLA Modules", [Event("jit_step_fn(1)", 1000, 310)])]),
            Plane("/host:CPU", [Line("python", [
                Event("trainer.dispatch", 900, 50),      # before the window
                Event("step", 1005, 200),
                Event("trainer.dispatch", 1100, 30),
                Event("trainer.bookkeeping", 1010, 5),
                Event("trainer.bookkeeping", 1150, 7),   # summed by name
                Event("trainer.place_batch", 1400, 9),   # after it
                Event("benchmark.wait", 1050, 20)])]),   # not the trainer's
        ]

    monkeypatch.setattr(trace_reduce, "load", lambda path: Data)
    table = stage_reduce.reduce("unused", HAND_MADE)
    assert table["steps"] == 1 and table["host_steps"] == 1
    assert table["host_s"] == pytest.approx(
        {"step": 200e-9, "trainer.dispatch": 30e-9,
         "trainer.bookkeeping": 12e-9})
    assert table["stage_s"] == pytest.approx(
        {"probe": 100e-9, "dedup": 50e-9, "unattributed": 10e-9})
    assert table["branch_s"] == pytest.approx({"push_routed": 50e-9})
    assert table["busy_s"] == pytest.approx(160e-9)


# what the reduction read from the recorded trace when it was recorded
RECORDED = {"steps": 21, "busy_s": 2.36944174}
