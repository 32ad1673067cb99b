"""The offload cell's halves on the CPU: ``tiny_offload`` through the
entry point end to end with ``null`` timings, the tier against the plain
reference and against its own host store, the two planted faults and one
eviction through ``benchmark.offload_controls``, and the new readers on a
run that has nothing for them to read."""

import gzip
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import (offload_controls, offload_system, run, stage_reduce,
                       trace_reduce)
from benchmark.metrics import _offload

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepfm_dim9_offload.train_zipf_offload"
TINY = "tiny_offload.train_zipf_offload"
NEW_METRICS = ("train_offload_prepare_host_ms_per_step",
               "train_offload_wait_ms_per_step",
               "train_offload_apply_host_ms_per_step",
               "train_offload_insert_device_ms_per_step",
               "train_offload_miss_rows_per_step",
               "train_offload_hit_share")
# the step program's own stages, the insert program's events kept out:
# each under the layer of the accepted reader it stands in for
STEP_METRICS = {
    "train_offload_step_pull_device_ms_per_step": "collection + exchange",
    "train_offload_step_push_device_ms_per_step": "collection + exchange",
    "train_offload_step_dedup_device_ms_per_step": "collection + exchange",
    "train_offload_step_resolve_device_ms_per_step": "collection + exchange",
    "train_offload_step_probe_device_ms_per_step": "hash probe and insert",
    "train_offload_step_apply_device_ms_per_step": "sparse apply",
    "train_offload_step_dense_device_ms_per_step": "dense model",
    "train_offload_step_unattributed_share": "device"}
SEED = 3000000019       # past 2**31, as the driver's are


def test_dry_resolves_the_offload_cell_to_its_own_runner():
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--dry"],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert len(lines) == 4
    assert lines[-1] == (
        f"{CELL}: configs/deepfm_dim9_offload.json "
        "traffic/train_zipf_offload.json traffic_gen/zipf_train.py "
        "train_offload_runner.py")


def test_the_offload_configuration_keeps_the_array_cells_widths():
    """Every width as in ``deepfm_dim9_array.json``; what differs is where
    the rows live, how many, and the tier's own numbers."""
    array = run.load("configs", "deepfm_dim9_array")
    offload = run.load("configs", "deepfm_dim9_offload")
    same = ("model", "sparse_features", "dense_features", "embedding_dim",
            "linear_dim", "dnn_units", "batch", "dtype", "plane", "adagrad",
            "dense_optimizer", "init_scale", "chips", "mesh")
    assert {k: array[k] for k in same} == {k: offload[k] for k in same}
    for gap in ("loss_gap", "grad_gap"):    # the accepted cells' limits
        assert offload["limits"][gap] == array["limits"][gap]
    # between its two readings on the chip (PERF.md, PR 28): the program's
    # largest and the bfloat16 control's smallest
    assert 4.5e-3 * 2 < offload["limits"]["delta_gap"] < 0.0185 / 1.5
    assert offload["guarantees"][:3] == array["guarantees"]
    assert offload["table_kind"] == "offload"
    assert offload["reduced"] == ["rows_per_feature", "cache_capacity"]
    rows = offload["rows_per_feature"] * offload["sparse_features"]
    # the table as device arrays (136 B a row, PERF.md) passes the chip
    assert rows * 136 > 16 * 2 ** 30
    budget = offload["occupancy_threshold"] * offload["cache_capacity"]
    held = offload["prefill_ranks_per_feature"] * offload["sparse_features"]
    assert offload["keep_fraction"] * budget < held < budget
    cells = {w["name"]: w for w in run.manifest()["workloads"]}
    assert cells[CELL]["chips"] == 1


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_offload_runs_end_to_end_with_null_timings(trace):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TINY,
         "--seed", str(SEED), "--seconds", "1", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["store_mismatch"] == {"value": 0, "limit": 0}
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if trace == "0":
        assert set(metrics) == {"examples_per_s", "setup_s"}
        assert all(m["value"] is None for m in metrics.values())
        return
    assert metrics["train_compiles_in_window"]["value"] == 0
    assert 0 < metrics["train_offload_hit_share"]["value"] <= 100
    assert metrics["train_offload_miss_rows_per_step"]["value"] >= 0
    for name in NEW_METRICS[:3]:            # a clock: null in a rehearsal
        assert metrics[name] == {"value": None, "unit": "ms"}
    assert "train_offload_insert_device_ms_per_step" not in metrics
    assert not set(STEP_METRICS) & set(metrics)     # no device plane here
    window = next(json.loads(text) for text in out.stdout.splitlines()
                  if text.startswith('{"window_s"'))
    assert window["offload"]["offload_evictions"] == 0
    assert window["offload"]["offload.wait_prepare"]["calls"] > 0


def _controls(capsys, *args):
    assert offload_controls.main(["tiny_offload", *map(str, args)]) == 0
    lines = capsys.readouterr().out.splitlines()
    followed = next(json.loads(text) for text in lines
                    if text.startswith('{"compared_at"'))
    return json.loads(lines[-1]), followed


def test_tier_agrees_with_the_reference_and_with_its_store(capsys):
    """Three steps through ``Trainer.fit(offload=)``, then ``flush``: the
    rows read through the cache and the rows of the host store agree bit
    for bit, and both with the plain reference on seeded rows."""
    result, followed = _controls(capsys, "none", SEED)
    assert result["correct"] is True and result["evictions"] == 0
    compared = result["compared"]
    assert compared["store_mismatch"]["value"] == 0
    assert compared["loss_gap"]["value"] < 1e-5
    assert compared["delta_gap"]["value"] < 1e-5
    program, reference = followed["program"], followed["reference"]
    for table in ("fields", "linear"):
        assert program["store_delta"][table] == program["delta"][table] > 0
        assert program["delta"][table] == pytest.approx(
            reference["delta"][table], rel=1e-5)


@pytest.mark.parametrize("fault", offload_system.FAULTS)
def test_a_planted_fault_is_not_correct(capsys, fault):
    result, _ = _controls(capsys, fault, SEED)
    assert result["correct"] is False
    assert result["compared"]["store_mismatch"]["value"] > 0
    gaps_over = [k for k in ("loss_gap", "grad_gap", "delta_gap")
                 if result["compared"][k]["value"]
                 > result["compared"][k]["limit"]]
    # rows that never came from the store move the arithmetic too; rows
    # that never reached it leave the arithmetic as it was
    assert bool(gaps_over) == (fault == "miss_from_initializer")


def test_an_eviction_between_steps_and_reading_keeps_rows_exact(capsys):
    """``evict``: six more steps leave their rows dirty, the cache is
    warmed to 64 rows under a budget of 11,468 and one more batch prepared:
    both tables evict, write their dirty rows back and re-insert the
    survivors, and the followed batches' rows read the same, bit for bit,
    before it, after it, and in the store."""
    result, _ = _controls(capsys, "evict", SEED, 16384, 6)
    assert result["evictions"] == 2
    evicted = result["evicted"]
    assert evicted["steps"] == 6 and evicted["evict_mismatch"] == 0
    assert all(rows > 0 for rows in evicted["dirty_rows"].values())
    assert all(v["calls"] == 1 for v in result["evict_span"].values())
    assert result["correct"] is True
    budget = int(0.7 * 16384)
    assert all(0 < rows < budget for rows in result["resident_rows"].values())


@pytest.mark.parametrize("name", NEW_METRICS + tuple(STEP_METRICS))
def test_a_reader_finds_nothing_where_the_program_has_no_tier(name):
    """On a program without the tier's spans, counters or stage (the
    parent, under this benchmark), a reader returns None and does not
    raise; the line then leaves the metric out."""
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    zeros = {c: 0.0 for c in offload_system.COUNTERS}
    zeros.update({s: {"s": 0.0, "calls": 0} for s in offload_system.SPANS})
    for run_ in ({"steps": 100, "trace": None, "trace_dir": None},
                 {"steps": 100, "trace": None, "trace_dir": None,
                  "offload": zeros}):
        assert reader.read(run_) is None
    entry = next(m for m in run.manifest()["per_layer"] if m["name"] == name)
    assert entry["layer"] == STEP_METRICS.get(name, "offload tier")
    assert entry["workloads"] == [CELL]


STEP_HLO = '''
HloModule jit_step_fn, is_scheduled=true

ENTRY %main.1 (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(step_fn)/jit(hash_push_a2a)/jit(probe)/gather"}
  %fusion.2 = s32[8]{0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(step_fn)/jit(hash_pull_a2a)/jit(resolve)/gather"}
  ROOT %copy.1 = s32[8]{0} copy(%p.1)
}
'''


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def test_the_step_stage_table_leaves_the_insert_programs_events_out():
    """Two steps with the insert program between them: its ``fusion.1``
    is another program's instruction and is given to no stage of the
    step's; ``stage_reduce`` alone would file it under ``probe``."""
    modules = [_event("jit_step_fn(1)", 0, 100),
               _event("jit_offload_insert(2)", 100, 140),
               _event("jit_step_fn(1)", 140, 240)]
    ops = [_event("%fusion.1 = s32[8] fusion(...)", 10, 40),
           _event("%fusion.2 = s32[8] fusion(...)", 40, 60),
           _event("%copy.1 = s32[8] copy(...)", 60, 65),
           _event("%fusion.1 = f32[64] fusion(...)", 100, 135),
           _event("%fusion.1 = s32[8] fusion(...)", 150, 180)]
    run_ = {"step_hlo": STEP_HLO, "device_lines": [(ops, modules)],
            "trace": {"steps": 2}, "steps": 2}
    table = _offload.step_stages(run_)
    assert table["steps"] == 2
    assert table["stage_s"] == pytest.approx(
        {"probe": 60e-9, "resolve": 20e-9, "unattributed": 5e-9})
    assert table["busy_s"] == pytest.approx(85e-9)
    assert table["scope_s"] == pytest.approx(
        {"push_a2a": 60e-9, "pull_a2a": 20e-9})
    assert _offload.step_stage_ms_per_step(run_, "probe") \
        == pytest.approx(30e-6)
    assert _offload.insert_device_ms_per_step(run_) == pytest.approx(20e-6)
    share = importlib.import_module(
        "benchmark.metrics.train_offload_step_unattributed_share")
    assert share.read(run_) == pytest.approx(100 * 5 / 85)


def test_the_step_stage_table_agrees_with_stage_reduce_on_a_lone_step():
    """On the recorded hash trace, where the step is the only program of
    any length, the step's own table is ``stage_reduce``'s."""
    data = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata")
    trace = os.path.join(data, "hash_step_stages.xplane.pb.gz")
    with gzip.open(os.path.join(data, "hash_step_stages.hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    whole = stage_reduce.reduce(trace, hlo)
    own = _offload.step_stages({
        "step_hlo": hlo,
        "device_lines": _offload.lines_of(trace_reduce.load(trace))})
    assert own["steps"] == whole["steps"]
    assert own["busy_s"] == pytest.approx(whole["busy_s"], rel=1e-3)
    for stage, seconds in whole["stage_s"].items():
        assert own["stage_s"].get(stage, 0.0) == pytest.approx(
            seconds, rel=1e-3, abs=1e-6), stage
