"""The sharded hash cell's halves on the CPU: ``tiny_hash_x4`` through the
entry point end to end on four virtual devices with ``null`` timings, the
new reader on a synthetic trace and HLO with and without a table copy, the
configuration against the hash configuration's widths, and what the
accepted tests' counts of seven cells stood for."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import run
from benchmark.metrics import _hash_x4

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepfm_dim9_hash_x4.train_zipf_keys"
ARRAY_X4 = "deepfm_dim9_array_x4.train_zipf"
HASH = "deepfm_dim9_hash.train_zipf"
KEYED = "deepfm_dim9_hash_offload.train_zipf_offload_keys"
TINY = "tiny_hash_x4.train_zipf_keys"
NEW_METRIC = "train_table_copy_device_ms_per_step"
SEED = 4000000019       # past 2**31, as the driver's are


def test_dry_resolves_eight_cells_and_two_ask_for_four_chips():
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--dry"],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    cells = run.manifest()["workloads"]
    assert len(lines) == len(cells) == 8
    assert lines[-1] == (
        f"{CELL}: configs/deepfm_dim9_hash_x4.json "
        "traffic/train_zipf_keys.json traffic_gen/zipf_train.py "
        "train_runner.py")
    # what test_bench_offload_keys.py's count of seven stood for
    assert lines[-2] == (
        f"{KEYED}: configs/deepfm_dim9_hash_offload.json "
        "traffic/train_zipf_offload_keys.json traffic_gen/zipf_train.py "
        "train_offload_keys_runner.py")
    assert [w["name"] for w in cells if w["chips"] == 4] == [ARRAY_X4, CELL]
    assert 4 * sum(w["chips"] == 4 for w in cells) <= len(cells)


def test_the_configuration_is_the_hash_configuration_over_a_2x2_mesh():
    hashed = run.load("configs", "deepfm_dim9_hash")
    x4 = run.load("configs", "deepfm_dim9_array_x4")
    config = run.load("configs", "deepfm_dim9_hash_x4")
    differ = {"name", "source", "deployment", "guarantees", "stands_for",
              "chips", "mesh", "hash_capacity", "prefill_ranks_per_feature",
              "assumed"}
    assert set(config) - set(hashed) == {"deployment"}
    assert {k: v for k, v in config.items() if k not in differ} \
        == {k: v for k, v in hashed.items() if k not in differ}
    # no width is cut
    for width in ("embedding_dim", "linear_dim", "dnn_units",
                  "sparse_features", "dense_features", "batch", "dtype"):
        assert config[width] == hashed[width] == x4[width]
    assert config["chips"] == x4["chips"] == 4 and config["mesh"] == x4["mesh"]
    assert config["hash_capacity"] == 4 * hashed["hash_capacity"] == 1 << 28
    assert config["prefill_ranks_per_feature"] == 5162220
    # half full, as the hash configuration: four times its keys
    keys = config["sparse_features"] * config["prefill_ranks_per_feature"]
    assert keys == 134217720
    assert abs(keys / config["hash_capacity"] - config["load_factor"]) < 1e-6
    assert config["guarantees"][:3] == hashed["guarantees"]
    assert len(config["guarantees"]) == 5
    assert "inserted once, at its owner" in config["guarantees"][3]
    assert "on every chip alike" in config["guarantees"][4]
    assert config["reduced"] == ["hash_capacity"]
    assert set(config["assumed"]) == set(hashed["assumed"])
    assert config["limits"] == hashed["limits"]
    # 153 B a slot (PERF.md): the table passes one chip, a quarter fits
    assert config["hash_capacity"] * 153 > 16 * 2 ** 30
    assert config["hash_capacity"] // 4 * 153 < 0.7 * 16 * 2 ** 30
    bench = run.manifest()
    entry = bench["configs"][-1]
    assert entry["name"] == config["name"] == "deepfm_dim9_hash_x4"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/deepfm_dim9_hash_x4.json"
    cell = bench["workloads"][-1]
    assert cell == {"name": CELL, "config": "deepfm_dim9_hash_x4",
                    "traffic": "train_zipf_keys", "chips": 4,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    traffic = run.load("traffic", "train_zipf_keys")
    control = run.load("traffic", "train_zipf")
    assert traffic["kind"] == "train" and traffic["pool_batches"] == 2048
    assert {k: v for k, v in traffic.items()
            if k not in ("why", "pool_batches")} \
        == {k: v for k, v in control.items()
            if k not in ("why", "pool_batches")}
    tiny = run.load("configs", "tiny_hash_x4")
    assert tiny["rehearsal"] and tiny["chips"] == 4
    assert tiny["mesh"] == config["mesh"]
    assert tiny["guarantees"] == config["guarantees"]


def test_the_new_cell_reports_what_its_two_controls_report_and_one_more():
    """Every list that holds the array x4 cell holds this one last, and so
    does the probe's, which the hash cell brought; the new metric comes
    last and lists it alone. What the accepted tests' counts stood for:
    the seven ``setup_*`` entries list every cell and nothing else moves
    ``setup_s``; the keyed offload cell's four metrics still list it
    alone, in front of the new one."""
    bench = run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    found = {m["name"]: m for m in bench["per_layer"]}
    assert len(found) == 68 and list(found)[-1] == NEW_METRIC
    assert [n for n in list(found)[-5:-1]] == [
        "train_offload_keys_index_host_ms_per_step",
        "train_offload_keys_fresh_per_step",
        "train_offload_keys_store_rows_m",
        "train_offload_keys_insert_roofline"]
    for name in list(found)[-5:-1]:
        assert found[name]["workloads"] == [KEYED]
    setup = [n for n, m in found.items() if m["moves"] == "setup_s"]
    assert setup == list(found)[-12:-5] and len(setup) == 7
    for name in setup:
        assert found[name]["workloads"] == cells
    listed = [m for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", ())]
    with_x4 = [m for m in bench["per_layer"] + bench["end_to_end"]
               if ARRAY_X4 in m.get("workloads", ())]
    assert len(with_x4) == 30 and all(m in listed for m in with_x4)
    assert [m["name"] for m in listed if m not in with_x4] == [
        "train_probe_device_ms_per_step", NEW_METRIC]
    for m in listed:
        assert m["workloads"][-1] == CELL
    assert found["train_probe_device_ms_per_step"]["workloads"] \
        == [HASH, CELL]
    assert found["train_collective_device_ms_per_step"]["workloads"] \
        == [ARRAY_X4, CELL]
    new = found[NEW_METRIC]
    assert new == {"name": NEW_METRIC, "unit": "ms", "better": "lower",
                   "source": "device_trace", "layer": "exchange planes",
                   "moves": "examples_per_s", "workloads": [CELL]}
    assert importlib.import_module(
        f"benchmark.metrics.{NEW_METRIC}").TIMING is True
    examples = [m for m in bench["end_to_end"]
                if m["name"] == "examples_per_s"][0]
    assert examples["workloads"] == cells


def test_what_the_accepted_counts_of_seven_cells_stood_for():
    """Four accepted tests describe the benchmark as PR 38 left it (seven
    cells, one of them on four chips, 67 per-layer metrics, the keyed
    offload configuration last) and are marked ``xfail`` from
    ``tests/conftest.py``. What they held beside those counts, held here:
    the keyed offload configuration is the offload cell's widths over the
    hash cell's keys, the array autosave configuration the array cell's
    plus the deployment, and their lists are as they were."""
    offload = run.load("configs", "deepfm_dim9_offload")
    hashed = run.load("configs", "deepfm_dim9_hash")
    keyed = run.load("configs", "deepfm_dim9_hash_offload")
    same = ("model", "sparse_features", "dense_features", "embedding_dim",
            "linear_dim", "dnn_units", "batch", "dtype", "plane", "adagrad",
            "dense_optimizer", "init_scale", "chips", "mesh", "table_kind",
            "cache_capacity", "occupancy_threshold", "keep_fraction",
            "pipeline_depth", "prefill_ranks_per_feature", "limits")
    assert {k: offload[k] for k in same} == {k: keyed[k] for k in same}
    assert keyed["fresh_rows"] == hashed["fresh_rows"]
    assert keyed["cache_capacity"] == hashed["hash_capacity"]
    assert keyed["reduced"] == ["store_keys_at_start", "cache_capacity"]
    bench = run.manifest()
    entry, cell = bench["configs"][-2], bench["workloads"][-2]
    assert entry["name"] == keyed["name"] and entry["source"] == keyed["source"]
    assert cell["name"] == KEYED and cell["chips"] == 1
    array = run.load("configs", "deepfm_dim9_array")
    ckpt = run.load("configs", "deepfm_dim9_array_ckpt")
    differs = {"name", "source", "stands_for", "guarantees", "reduced",
               "assumed"}
    assert {k: array[k] for k in array if k not in differs} \
        == {k: ckpt[k] for k in array if k not in differs}
    assert ckpt["reduced"] == ["rows_per_feature", "autosave_every",
                               "window_periods"]      # the last: PR 43
    found = {m["name"]: m for m in bench["per_layer"]}
    array_ckpt = "deepfm_dim9_array_ckpt.train_zipf_autosave"
    hash_ckpt = "deepfm_dim9_hash_ckpt.train_zipf_autosave_keys"
    for name in ("stall_ms_per_save", "gather_device_ms_per_save",
                 "gather_roofline", "d2h_ms_per_save", "write_ms_per_save",
                 "commit_lag_ms", "rows_per_save", "mb_per_save"):
        m = found[f"train_autosave_{name}"]
        assert m["layer"] == "checkpoint"
        assert m["workloads"] == [array_ckpt] + (
            [] if name == "gather_roofline" else [hash_ckpt])
    bounded = "deepfm_dim9_offload.train_zipf_offload"
    both = [m for m in bench["per_layer"] + bench["end_to_end"]
            if bounded in m.get("workloads", ())]
    # 32 until PR 43 took the bounded cell off the insert program's device
    # time, which its traced tail never runs (benchmark/README.md)
    assert len(both) == 31
    assert found["train_offload_insert_device_ms_per_step"]["workloads"] \
        == [KEYED]
    for m in both:
        assert m["workloads"].index(KEYED) > m["workloads"].index(bounded)
        assert m["workloads"][-1] in (KEYED, CELL)
    assert sum(n.startswith("train_offload_") and not n.startswith(
        "train_offload_keys_") for n in found) == 14


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_hash_x4_runs_end_to_end_with_null_timings(trace):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TINY,
         "--seed", str(SEED), "--seconds", "0.5", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["count"] == 4
    compared = line["compared"]
    assert compared["insert_failures"] == {"value": 0, "limit": 0}
    # four virtual devices against the plain reference, which knows no
    # mesh: a CPU's float32 leaves round-off alone
    for gap in ("loss_gap", "grad_gap", "delta_gap"):
        assert compared[gap]["value"] < 1e-5
    listed = {m["name"] for m in run.manifest()["per_layer"]
              + run.manifest()["end_to_end"]
              if CELL in m.get("workloads", (CELL,))}
    assert set(line["metrics"]) <= listed
    if trace == "0":
        assert set(line["metrics"]) == {"examples_per_s", "setup_s"}
        assert all(m["value"] is None for m in line["metrics"].values())
        return
    # a rehearsal has no device plane: the trace's readers, the new one
    # among them, find nothing and leave their metric out
    assert NEW_METRIC not in line["metrics"]
    assert line["metrics"]["train_compiles_in_window"]["value"] == 0
    assert line["metrics"]["setup_programs_loaded"]["value"] > 0
    for name, m in line["metrics"].items():
        if importlib.import_module(f"benchmark.metrics.{name}").TIMING:
            assert m["value"] is None, name


# --- the new reader on a synthetic trace and HLO ---------------------------

CONFIG = {"hash_capacity": 4 * 4096, "chips": 4,
          "embedding_dim": 9, "linear_dim": 1}
COPIED = """\
HloModule jit_step_fn, is_scheduled=true

%region_0.1 (p: s32[4096,2]) -> s32[4096,2] {
  %p = s32[4096,2]{0,1:T(2,128)} parameter(0)
  ROOT %copy.7 = s32[4096,2]{0,1:T(2,128)} copy(%p), metadata={op_name="jit(step_fn)/cond"}
}

ENTRY %main.9 (keys: s32[4096,2], w: f32[4096,9]) -> f32[4096,9] {
  %keys = s32[4096,2]{0,1:T(2,128)} parameter(0)
  %w = f32[4096,9]{1,0:T(8,128)} parameter(1)
  %copy.3 = f32[64,9]{1,0} copy(%small)
  %copy-start.1 = (f32[4096,9]{1,0:T(8,128)}, f32[4096,9]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%w)
  %copy-done.1 = f32[4096,9]{1,0:T(8,128)} copy-done(%copy-start.1)
  %copy.5 = s32[40960,2]{1,0} copy(%other)
  %fusion.2 = f32[4096,1]{1,0} fusion(%w), kind=kLoop, calls=%fused
  ROOT %add.1 = f32[4096,9]{1,0} add(%copy-done.1, %copy-done.1)
}
"""
CLEAN = "\n".join(line for line in COPIED.splitlines()
                  if "copy.7" not in line and "copy-" not in line)


def _event(name, start, duration):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=duration)


def _traced(hlo, steps=4):
    """A run as the entry point hands it to a reader: two device planes,
    each with the copies' events among others."""
    ops = [_event("%copy.7 = s32[4096,2]{0,1:T(2,128)} copy(%p)", 0, 3000),
           _event("%copy.3 = f32[64,9]{1,0} copy(%small)", 4000, 500),
           _event("%copy-start.1 = (f32[4096,9], ...) copy-start(%w)",
                  5000, 100),
           _event("%copy-done.1 = f32[4096,9] copy-done(%copy-start.1)",
                  6000, 900),
           _event("%copy.5 = s32[40960,2]{1,0} copy(%other)", 7000, 7000),
           _event("%fusion.2 = f32[4096,1]{1,0} fusion(%w)", 15000, 2000)]
    return {"config": CONFIG, "step_hlo": hlo,
            "trace": {"steps": steps, "scope_s": {}, "kind_s": {}},
            "device_lines": [(ops, []), (ops, [])]}


def test_the_reader_counts_the_copies_of_a_table_and_no_other():
    assert _hash_x4.table_shapes(CONFIG) == [
        "s32[4096,2]", "f32[4096,9]", "f32[4096,1]"]
    assert _hash_x4.table_copies(COPIED, _hash_x4.table_shapes(CONFIG)) == {
        "copy.7": "s32[4096,2]", "copy-start.1": "f32[4096,9]",
        "copy-done.1": "f32[4096,9]"}
    reader = importlib.import_module(f"benchmark.metrics.{NEW_METRIC}")
    # (3000 + 100 + 900) ns a plane over four steps, in ms
    assert reader.read(_traced(COPIED)) == pytest.approx(4000e-6 / 4)
    # a step whose text copies no table reads nought, not nothing
    assert _hash_x4.table_copies(CLEAN, _hash_x4.table_shapes(CONFIG)) == {}
    assert reader.read(_traced(CLEAN)) == 0.0


def test_the_reader_finds_nothing_without_a_trace_or_the_text():
    reader = importlib.import_module(f"benchmark.metrics.{NEW_METRIC}")
    no_trace = dict(_traced(COPIED), trace=None)
    assert reader.read(no_trace) is None
    assert reader.read(dict(_traced(COPIED), step_hlo=None)) is None
    assert reader.read(dict(_traced(COPIED), device_lines=[])) is None
