"""The autosave cell's halves on the CPU: the files resolve, the
configuration is the array cell's plus the deployment, ``tiny_array_ckpt``
rehearses the cell end to end, the three planted faults come out as not
correct, the checkpoint's plain reference imports nothing of the program,
and the new readers reduce what a run leaves them."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import (autosave_controls, counts_chain, reference_chain, run,
                       train_autosave_runner)
from benchmark.metrics import _autosave

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepfm_dim9_array_ckpt.train_zipf_autosave"
TINY = "tiny_array_ckpt.train_zipf_autosave"
NEW_METRICS = ("train_autosave_stall_ms_per_save",
               "train_autosave_gather_device_ms_per_save",
               "train_autosave_gather_roofline",
               "train_autosave_d2h_ms_per_save",
               "train_autosave_write_ms_per_save",
               "train_autosave_commit_lag_ms",
               "train_autosave_rows_per_save",
               "train_autosave_mb_per_save")
SEED = 3000000019       # past 2**31, as the driver's are


def test_dry_resolves_every_cell_to_its_own_runner():
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--dry"],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert len(lines) == len(run.manifest()["workloads"]) == 5
    assert lines[-1] == (
        f"{CELL}: configs/deepfm_dim9_array_ckpt.json "
        "traffic/train_zipf_autosave.json traffic_gen/zipf_train.py "
        "train_autosave_runner.py")
    assert (       # what the accepted test of the offload cell stood for
        "deepfm_dim9_offload.train_zipf_offload: "
        "configs/deepfm_dim9_offload.json traffic/train_zipf_offload.json "
        "traffic_gen/zipf_train.py train_offload_runner.py") in lines


def test_the_configuration_is_the_array_cells_plus_the_deployment():
    array = run.load("configs", "deepfm_dim9_array")
    ckpt = run.load("configs", "deepfm_dim9_array_ckpt")
    differs = {"name", "source", "stands_for", "guarantees", "reduced",
               "assumed"}
    assert {k: array[k] for k in array if k not in differs} \
        == {k: ckpt[k] for k in array if k not in differs}
    assert ckpt["guarantees"][:3] == array["guarantees"]
    assert len(ckpt["guarantees"]) == 7
    assert ckpt["checkpoint"] == {
        "mode": "delta", "include_optimizer": True, "autosave_every": 200,
        "saves_in_flight": 1, "base": "a full save in set-up"}
    assert ckpt["reduced"] == ["rows_per_feature", "autosave_every"]
    assert set(ckpt["why_reduced"]) == set(ckpt["reduced"])
    assert ckpt["assumed"]["autosave_dir"] and ckpt["stands_for"]
    assert len(ckpt["source"]) <= 200 and "--checkpoint" in ckpt["source"]
    traffic = run.load("traffic", "train_zipf_autosave")
    same = run.load("traffic", "train_zipf")
    for key in ("generator", "zipf_a", "pool_batches", "steps_in_flight",
                "warmup_steps", "lead_in_steps"):
        assert traffic[key] == same[key]
    assert traffic["kind"] == "train_autosave"
    bench = run.manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    reported = {m["name"] for m in run.metrics_of(
        bench, "per_layer", CELL, "train_zipf_autosave")}
    assert reported >= set(NEW_METRICS) | {"train_step_mfu",
                                           "train_device_idle_share"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "checkpoint"


def test_the_checkpoints_reference_imports_nothing_of_the_program():
    with open(reference_chain.__file__) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import json", "import os", "import re",
                       "import numpy as np"]


def test_period_feed_closes_only_on_whole_periods():
    class Probe:
        def __call__(self, x):
            return self

        def block_until_ready(self):
            return self

    feed = train_autosave_runner.PeriodFeed(
        list(range(7)), Probe(), lag=1, in_flight=2, seconds=0.0, lead_in=3)
    feed.period = 5
    handed = list(feed)
    assert len(handed) == 3 + 5 and feed.handed - feed.lead_in == 5
    assert handed[:8] == [0, 1, 2, 3, 4, 5, 6, 0]


def test_a_program_that_tracks_chunks_is_refused_before_the_tables():
    """The parent under this benchmark: its every delta of the cell's
    table is the whole table (my chip run, PR 32: 66 s a save, then the
    host's 40 GiB). The run ends at once, with another exit code than 0."""
    from benchmark import autosave_system, system as system_lib
    system = system_lib.build(run.load("configs", "tiny_array_ckpt"))
    arm = system.coll.enable_dirty_tracking
    system.coll.enable_dirty_tracking = lambda: arm(target_chunks=8)
    with pytest.raises(SystemExit, match="not to the row"):
        autosave_system.arm(system)
    system = system_lib.build(run.load("configs", "tiny_array_ckpt"))
    autosave_system.arm(system)                 # to the row: armed
    assert set(system.coll.dirty_trackers) == set(system.coll.specs)


def test_counts_of_a_save():
    config = run.load("configs", "deepfm_dim9_array_ckpt")
    assert counts_chain.saved_row_bytes(config) == 80
    ids = np.array([[1, 5], [1, 6]], np.uint64)
    assert counts_chain.distinct_rows([{"ids": ids}, {"ids": ids[:1]}]) == 3
    assert counts_chain.gather_bytes(config, 1000) == 80_000


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_array_ckpt_runs_end_to_end_with_null_timings(trace):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    # a window shorter than one period: it closes at the first one, so
    # the run holds one save and the 24 lead-in steps' worth after it
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TINY,
         "--seed", str(SEED), "--seconds", "0.05", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 64
    for name in ("chain_mismatch_rows", "chain_rows_off", "chain_late_rows"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    metrics = line["metrics"]
    if trace == "0":
        assert set(metrics) == {"examples_per_s", "setup_s"}
        assert all(m["value"] is None for m in metrics.values())
        return
    assert metrics["train_compiles_in_window"]["value"] == 0
    rows = metrics["train_autosave_rows_per_save"]["value"]
    entry_rows = next(json.loads(text)["entry_rows"]
                      for text in out.stdout.splitlines()
                      if text.startswith('{"compared_at"'))
    assert rows == entry_rows[1] > 0            # one save, a table
    # the rows' bytes (80 of weights and accumulators, 16 of ids over
    # both tables) and the files' headers: not the table's
    megabytes = metrics["train_autosave_mb_per_save"]["value"]
    assert rows * 96 <= megabytes * 1e6 <= rows * 96 * 1.25
    for name in NEW_METRICS[:6]:
        if name in metrics:                     # a clock: null here
            assert metrics[name]["value"] is None
    assert "train_autosave_gather_device_ms_per_save" not in metrics
    window = next(json.loads(text) for text in out.stdout.splitlines()
                  if text.startswith('{"window_s"'))
    assert window["autosave"]["ckpt_delta_saves"] == 1
    assert window["autosave"]["trainer.autosave"]["calls"] == 1


@pytest.mark.parametrize("fault", autosave_controls.FAULTS)
def test_planted_fault_comes_out_not_correct(capsys, fault):
    assert autosave_controls.main(["tiny_array_ckpt", fault, str(SEED),
                                   "0.05"]) == 0
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["correct"] is False
    over = {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {
        "marks_dropped": {"chain_mismatch_rows", "chain_rows_off",
                          "chain_late_rows"},
        "snapshot_late": {"chain_rows_off", "chain_late_rows"},
        "stale_accumulator": {"chain_mismatch_rows", "chain_late_rows"},
    }[fault]


def test_readers_reduce_a_stored_trace_of_one_save():
    """48 steps around one save of the cell, recorded on a TPU v5e
    (``benchmark/testdata``, my chip run, PR 32, seed 3200000210): the two
    gather programs and the dense copy on the ``XLA Modules`` line, the
    step's own stages from the events inside its executions."""
    import gzip
    from benchmark import trace_reduce
    from benchmark.metrics import _offload
    data = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata")
    with gzip.open(os.path.join(data, "autosave_save.hlo.txt.gz"),
                   "rt") as f:
        hlo = f.read()
    lines = _offload.lines_of(trace_reduce.load(
        os.path.join(data, "autosave_save.xplane.pb.gz")))
    run_ = {"config": run.load("configs", "deepfm_dim9_array_ckpt"),
            "device_kind": "TPU v5 lite", "trace": {"steps": 44},
            "step_hlo": hlo, "device_lines": lines,
            "autosave": {"ckpt_delta_saves": 1.0,
                         "ckpt_delta_rows": 2 * 2_871_000.0}}
    seconds, saves = _autosave.gather_device_s(run_)
    assert saves == 1 and seconds == pytest.approx(0.19086, rel=1e-3)

    def read(name):
        return __import__(f"benchmark.metrics.{name}",
                          fromlist=["read"]).read(run_)

    assert read("train_autosave_gather_device_ms_per_save") \
        == pytest.approx(190.86, rel=1e-3)
    # 2.871M rows x 80 B over 819 GB/s over 0.19086 s
    assert read("train_autosave_gather_roofline") == pytest.approx(
        0.1469, rel=1e-2)
    # the step's own stages: the array cell's (PERF.md section 5), the
    # gather programs' events kept out
    assert read("train_autosave_step_apply_device_ms_per_step") \
        == pytest.approx(12.7, abs=0.2)  # the first traced step is cut
    assert read("train_autosave_step_dedup_device_ms_per_step") \
        == pytest.approx(7.9, abs=0.2)
    assert read("train_autosave_step_unattributed_share") < 0.1


def _event(name, start_ns, duration_ns):
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=duration_ns)


def test_readers_reduce_a_runs_spans_counters_and_modules():
    run_ = {
        "config": run.load("configs", "deepfm_dim9_array_ckpt"),
        "device_kind": "TPU v5 lite", "trace": {"steps": 200},
        "autosave": {
            "ckpt_delta_saves": 4.0, "ckpt_delta_rows": 8_000_000.0,
            "ckpt_delta_bytes": 704e6,
            "trainer.autosave": {"s": 0.2, "calls": 4},
            "ckpt.d2h": {"s": 2.0, "calls": 4},
            "ckpt.checksum": {"s": 0.4, "calls": 8},
            "ckpt.write": {"s": 1.2, "calls": 8},
            "ckpt.commit": {"s": 0.04, "calls": 4},
            "ckpt_commit_lag_s": {"s": 4.0, "calls": 4}},
        # one traced save on one chip: two gathers and the dense copy
        "device_lines": [([], [
            _event("jit_step_fn(1)", 0, 25_000_000),
            _event("jit_ckpt_gather(7)", 30_000_000, 300_000_000),
            _event("jit_ckpt_gather(8)", 330_000_000, 20_000_000),
            _event("jit_ckpt_gather_dense(9)", 350_000_000, 100_000)])]}

    def read(name):
        return __import__(f"benchmark.metrics.{name}",
                          fromlist=["read"]).read(run_)

    assert read("train_autosave_stall_ms_per_save") == pytest.approx(50.0)
    assert read("train_autosave_d2h_ms_per_save") == pytest.approx(500.0)
    assert read("train_autosave_write_ms_per_save") == pytest.approx(410.0)
    assert read("train_autosave_commit_lag_ms") == pytest.approx(1000.0)
    assert read("train_autosave_rows_per_save") == 1_000_000
    assert read("train_autosave_mb_per_save") == pytest.approx(176.0)
    assert read("train_autosave_gather_device_ms_per_save") \
        == pytest.approx(320.1)
    # 1M rows a table x 80 B over 819 GB/s over 0.3201 s
    assert read("train_autosave_gather_roofline") == pytest.approx(
        100 * 80e6 / 819e9 / 0.3201, rel=1e-6)
    # a program without the spans, counters or stage: nothing, no raise
    bare = {"config": run_["config"], "device_kind": "TPU v5 lite",
            "trace": {"steps": 200}, "device_lines": [([], [
                _event("jit_step_fn(1)", 0, 25_000_000)])],
            "autosave": {k: ({"s": 0.0, "calls": 0}
                             if isinstance(v, dict) else 0.0)
                         for k, v in run_["autosave"].items()}}
    for name in NEW_METRICS:
        reader = __import__(f"benchmark.metrics.{name}", fromlist=["read"])
        assert reader.read(bare) is None, name
        assert reader.read({"config": run_["config"]}) is None, name
    assert _autosave.saves({}) is None
