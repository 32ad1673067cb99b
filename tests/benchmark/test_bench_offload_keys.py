"""The keyed offload cell's halves on the CPU: ``tiny_hash_offload`` through
the entry point end to end with ``null`` timings, the tier against the
plain reference and against its own keyed store, the control and the four
planted faults and one eviction through ``benchmark.offload_keys_controls``,
each new reader on a recorded context and on a run with nothing to read,
and what the accepted tests' counts of six cells stood for."""

import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import (counts_offload_keys, offload_keys_controls,
                       offload_keys_system, reference_offload_keys, run)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepfm_dim9_hash_offload.train_zipf_offload_keys"
OFFLOAD_CELL = "deepfm_dim9_offload.train_zipf_offload"
TINY = "tiny_hash_offload.train_zipf_offload_keys"
NEW_METRICS = {"train_offload_keys_index_host_ms_per_step": ("ms", True),
               "train_offload_keys_fresh_per_step": ("keys", False),
               "train_offload_keys_store_rows_m": ("Mrows", False),
               "train_offload_keys_insert_roofline": ("%", True)}
SEED = 3800000019       # past 2**31, as the driver's are


def _per_layer():
    return {m["name"]: m for m in run.manifest()["per_layer"]}


def test_dry_resolves_seven_cells_each_to_its_own_runner():
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--dry"],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert len(lines) == len(run.manifest()["workloads"]) == 7
    assert lines[-1] == (
        f"{CELL}: configs/deepfm_dim9_hash_offload.json "
        "traffic/train_zipf_offload_keys.json traffic_gen/zipf_train.py "
        "train_offload_keys_runner.py")
    # what test_bench_autosave_keys.py's count of six stood for
    assert lines[-2] == (
        "deepfm_dim9_hash_ckpt.train_zipf_autosave_keys: "
        "configs/deepfm_dim9_hash_ckpt.json "
        "traffic/train_zipf_autosave_keys.json traffic_gen/zipf_train.py "
        "train_autosave_keys_runner.py")
    assert (f"{OFFLOAD_CELL}: configs/deepfm_dim9_offload.json "
            "traffic/train_zipf_offload.json traffic_gen/zipf_train.py "
            "train_offload_runner.py") in lines


def test_the_configuration_is_the_offload_cells_widths_over_the_hash_cells_keys():
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
    assert keyed["prefill_ranks_per_feature"] \
        == hashed["prefill_ranks_per_feature"]
    assert keyed["cache_capacity"] == hashed["hash_capacity"]
    assert "rows_per_feature" not in keyed      # no id is bounded
    assert keyed["guarantees"][:3] == offload["guarantees"][:3]
    assert len(keyed["guarantees"]) == 7
    assert keyed["reduced"] == ["store_keys_at_start", "cache_capacity"]
    assert set(keyed["assumed"]) >= {"seen_share_of_tail",
                                     "prefill_ranks_per_feature",
                                     "init_scale", "dense_optimizer",
                                     "dnn_units"}
    # as device arrays at the hash configuration's load factor the keys
    # pass the chip (153 B a slot, PERF.md)
    slots = 1 << (int(keyed["store_keys_at_start"]
                      / hashed["load_factor"]) - 1).bit_length()
    assert slots * 153 > 16 * 2 ** 30
    assert reference_offload_keys.store_ranks(keyed) == 3 * 2 ** 20
    bench = run.manifest()
    entry = bench["configs"][-1]
    assert entry["name"] == keyed["name"] and len(entry["source"]) <= 200
    assert entry["source"] == keyed["source"]
    assert entry["reduced"] == keyed["reduced"]
    cell = bench["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    traffic = run.load("traffic", "train_zipf_offload_keys")
    assert traffic["kind"] == "train_offload_keys"
    assert traffic["pool_batches"] == 1024 and traffic["zipf_a"] == 1.2


def test_the_new_cell_reports_what_the_offload_cell_reports_and_four_more():
    """Every metric that lists the bounded offload cell lists this one
    right after it; the four new ones come last and list it alone. What
    ``test_every_new_entry_moves_setup_s_in_all_six_cells`` stood for: the
    seven ``setup_*`` entries list every cell and nothing else moves
    ``setup_s``."""
    bench = run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    found = _per_layer()
    assert len(found) == 67 and list(found)[-4:] == list(NEW_METRICS)
    setup = list(found)[-11:-4]
    assert [n for n, m in found.items() if m["moves"] == "setup_s"] == setup
    for name in setup:
        assert found[name]["workloads"] == cells
    both = [m for m in bench["per_layer"] + bench["end_to_end"]
            if OFFLOAD_CELL in m.get("workloads", ())]
    assert len(both) == 32
    for m in both:
        assert m["workloads"][-1] == CELL
    assert sum(n.startswith("train_offload_") and not n.startswith(
        "train_offload_keys_") for n in found) == 14
    for name, (unit, timing) in NEW_METRICS.items():
        m = found[name]
        assert m["workloads"] == [CELL] and m["unit"] == unit
        assert m["layer"] == "offload tier"
        assert m["moves"] == "examples_per_s"
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.TIMING is timing


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_hash_offload_runs_end_to_end_with_null_timings(trace):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TINY,
         "--seed", str(SEED), "--seconds", "0.5", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for count in ("store_mismatch", "store_keys_shared", "insert_failures"):
        assert line["compared"][count] == {"value": 0, "limit": 0}
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    if trace == "0":
        assert set(metrics) == {"examples_per_s", "setup_s"}
        assert all(m["value"] is None for m in metrics.values())
        return
    assert metrics["train_compiles_in_window"]["value"] == 0
    assert 0 < metrics["train_offload_hit_share"]["value"] < 100
    assert metrics["train_offload_miss_rows_per_step"]["value"] > 0
    assert metrics["train_offload_keys_fresh_per_step"]["value"] > 0
    assert metrics["train_offload_keys_index_host_ms_per_step"] \
        == {"value": None, "unit": "ms"}        # a clock: null here
    assert "train_offload_keys_insert_roofline" not in metrics  # no device
    window = next(json.loads(text) for text in out.stdout.splitlines()
                  if text.startswith('{"window_s"'))
    assert window["offload"]["offload_evictions"] == 0
    assert window["offload"]["offload.key_index"]["calls"] > 0
    tiny = run.load("configs", "tiny_hash_offload")
    filled = window["filled"]
    assert filled["head_keys"] == tiny["store_keys_at_start"]
    assert filled["tail_keys"] > 0
    for gauges in window["store"].values():
        # the store grew in the window, by the keys it had not seen
        assert gauges["store_rows"] > filled["head_keys"] \
            + filled["tail_keys"]
        assert 0 < gauges["index_load"] <= 0.7
    assert metrics["train_offload_keys_store_rows_m"]["value"] \
        == max(g["store_rows"] for g in window["store"].values()) / 1e6


def _controls(capsys, *args):
    assert offload_keys_controls.main(
        ["tiny_hash_offload", *map(str, args)]) == 0
    lines = capsys.readouterr().out.splitlines()
    followed = next(json.loads(text) for text in lines
                    if text.startswith('{"compared_at"'))
    places = next((json.loads(text)["store_mismatch_at"] for text in lines
                   if text.startswith('{"store_mismatch_at"')), None)
    return json.loads(lines[-1]), followed, places


def test_tier_agrees_with_the_reference_and_with_its_keyed_store(capsys):
    """Three steps through ``Trainer.fit(offload=)`` that fetch rows by
    key and meet fresh keys, then ``flush``: the rows read through the
    cache and the rows the store holds under each key agree bit for bit,
    the fresh keys' among them, and both with the plain reference."""
    result, followed, places = _controls(capsys, "none", SEED)
    assert result["correct"] is True and result["evictions"] == 0
    compared = result["compared"]
    assert set(places) == {"store_at_start", "probed_before_steps",
                           "after_followed_flush"}
    assert compared["store_mismatch"]["value"] == 0
    assert compared["store_keys_shared"]["value"] == 0
    assert compared["loss_gap"]["value"] < 1e-5
    assert compared["delta_gap"]["value"] < 1e-5
    program, reference = followed["program"], followed["reference"]
    for table in ("fields", "linear"):
        assert program["store_delta"][table] == program["delta"][table] > 0
        assert program["delta"][table] == pytest.approx(
            reference["delta"][table], rel=1e-5)
    for gauges in result["store"].values():
        assert gauges["store_rows"] > 26 * 512      # keys were born


@pytest.mark.parametrize("fault", offload_keys_system.FAULTS)
def test_a_planted_fault_is_not_correct(capsys, fault):
    result, _, places = _controls(capsys, fault, SEED)
    assert result["correct"] is False
    assert result["compared"]["store_mismatch"]["value"] > 0
    where = {"miss_from_initializer": "probed_before_steps"}.get(
        fault, "after_followed_flush")
    assert places[where] > 0
    assert (result["compared"]["store_keys_shared"]["value"] > 0) \
        == (fault == "key_aliased")
    gaps_over = [k for k in ("loss_gap", "grad_gap", "delta_gap")
                 if result["compared"][k]["value"]
                 > result["compared"][k]["limit"]]
    # rows that never came from the store move the arithmetic too; rows
    # that never reached it, or reached another key's, leave it as it was
    assert bool(gaps_over) == (fault == "miss_from_initializer")


def test_the_bfloat16_control_is_not_correct(capsys):
    assert offload_keys_controls.main(
        ["tiny_hash_offload", "bfloat16", str(SEED)]) == 0
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["correct"] is False
    assert result["compared"]["grad_gap"]["value"] \
        > result["compared"]["grad_gap"]["limit"]


def test_an_eviction_writes_fresh_keys_back_under_their_keys(capsys):
    """``evict``: six more steps leave their rows dirty, keys born in them
    included; the cache is warmed to 64 rows under a budget of 11,468 and
    one more batch prepared: both tables evict, and the followed batches'
    rows read the same, bit for bit, before it, after it, and in the
    store under their keys."""
    result, _, _ = _controls(capsys, "evict", SEED, 16384, 6)
    assert result["evictions"] == 2
    evicted = result["evicted"]
    assert evicted["steps"] == 6 and evicted["evict_mismatch"] == 0
    assert all(rows > 0 for rows in evicted["dirty_rows"].values())
    assert all(v["calls"] == 1 for v in result["evict_span"].values())
    assert result["correct"] is True
    budget = int(0.7 * 16384)
    assert all(0 < g["resident_rows"] < budget
               for g in result["store"].values())


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start)


def _recorded():
    """A context as ``train_offload_keys_runner`` leaves it: 100 steps,
    10 of them traced, two executions of the insert program a step."""
    tier = {c: 0.0 for c in offload_keys_system.COUNTERS}
    tier.update({s: {"s": 0.0, "calls": 0}
                 for s in offload_keys_system.SPANS})
    tier.update(offload_unique_rows=7.0e6, offload_miss_rows=950_000.0,
                offload_fresh_keys=190_000.0)
    tier["offload.key_index"] = {"s": 0.45, "calls": 200}
    modules = []
    for step in range(10):
        at = step * 1_000_000       # a step every millisecond
        modules += [_event("jit_offload_insert(7)", at, at + 300_000),
                    _event("jit_offload_insert(8)", at + 300_000,
                           at + 400_000),
                    _event("jit_step_fn(3)", at + 400_000, at + 1_000_000)]
    config = run.load("configs", "deepfm_dim9_hash_offload")
    return {"steps": 100, "config": config, "device_kind": "TPU v5 lite",
            "trace": {"steps": 10}, "device_lines": [([], modules)],
            "offload": tier,
            "offload_store": {"fields": {"store_rows": 86.5e6},
                              "fields:linear": {"store_rows": 86.4e6}}}


def test_each_new_reader_on_a_recorded_context():
    run_ = _recorded()
    read = {name: importlib.import_module(
        f"benchmark.metrics.{name}").read(run_) for name in NEW_METRICS}
    assert read["train_offload_keys_index_host_ms_per_step"] \
        == pytest.approx(4.5)
    assert read["train_offload_keys_fresh_per_step"] == pytest.approx(950.0)
    assert read["train_offload_keys_store_rows_m"] == pytest.approx(86.5)
    # 9,500 rows a step over both tables at (2 x 8 + 2 x 40) / 2 = 48 B a
    # row a table, 10 traced steps, over 819 GB/s, over 4 ms of insert
    config = run_["config"]
    assert counts_offload_keys.inserted_row_bytes(config) == 96
    need_s = 9500 * 10 * 48 / 819e9
    assert read["train_offload_keys_insert_roofline"] == pytest.approx(
        100 * need_s / 4e-3)
    assert read["train_offload_keys_insert_roofline"] < 100


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_finds_nothing_where_the_program_has_no_keyed_tier(name):
    """On a program without a keyed tier's spans, counters or gauges (the
    parent, under this benchmark's files), a reader returns None and does
    not raise; the line then leaves the metric out."""
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    zeros = {c: 0.0 for c in ("offload_miss_rows", "offload_unique_rows")}
    zeros.update({"offload.host_prepare": {"s": 0.0, "calls": 0}})
    bounded = dict(zeros, offload_unique_rows=5.0e6,
                   offload_miss_rows=4.0e5)     # a tier, but no keys
    for run_ in ({"steps": 100, "trace": None, "trace_dir": None},
                 {"steps": 100, "trace": None, "trace_dir": None,
                  "offload": zeros},
                 {"steps": 100, "trace": None, "trace_dir": None,
                  "offload": bounded, "offload_store": {}}):
        assert reader.read(run_) is None


@pytest.mark.parametrize("name", [
    n for n, m in _per_layer().items()
    if n.startswith("train_offload_")
    and not n.startswith("train_offload_keys_")])
def test_an_accepted_offload_reader_lists_both_cells(name):
    """What fourteen cases of ``test_a_reader_finds_nothing_where_the_
    program_has_no_tier`` stood for, whose last line held each list to the
    bounded cell alone: the reader still finds nothing on a program with
    no tier, and its entry lists the bounded cell, then this one. But
    the insert program's device time (PR 43): the bounded cell's traced
    tail lies past its pool of 512 batches, where no row misses and the
    program does not run, so the reader finds nothing there and the
    driver flagged it on seven PRs; this cell, which reads 5.8 ms, keeps
    it, and a pool of 1,024 brings the bounded cell back."""
    reader = importlib.import_module(f"benchmark.metrics.{name}")
    assert reader.read({"steps": 100, "trace": None,
                        "trace_dir": None}) is None
    assert _per_layer()[name]["workloads"] == (
        [CELL] if name == "train_offload_insert_device_ms_per_step"
        else [OFFLOAD_CELL, CELL])


def test_stored_and_fresh_keys_are_told_apart_by_rank_and_by_hash():
    config = dict(run.load("configs", "tiny_hash_offload"))
    top = reference_offload_keys.store_ranks(config)
    assert top == 512
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1 << 62, 4000, dtype=np.int64).astype(np.uint64)
    feature = rng.randint(0, 26, 4000)
    ranks = np.where(np.arange(4000) < 1000, 7, top + 5)
    held = reference_offload_keys.stored(SEED, config, feature, ids, ranks)
    assert held[:1000].all()
    assert 0.75 < held[1000:].mean() < 0.85         # seen_share_of_tail
    again = reference_offload_keys.stored(SEED, config, feature, ids, ranks)
    assert (held == again).all()
    other = reference_offload_keys.stored(SEED + 1, config, feature, ids,
                                          ranks)
    assert (held != other).any()
    none = reference_offload_keys.stored(
        SEED, dict(config, seen_share_of_tail=0.0), feature, ids, ranks)
    assert not none[1000:].any()
