"""The hash autosave cell's halves on the CPU: the files resolve, the
configuration is the hash cell's plus the deployment, ``tiny_hash_ckpt``
rehearses the cell end to end, the four planted faults come out as not
correct, the checkpoint's plain reference imports nothing of the program,
and the new readers reduce what a run leaves them. Also what two accepted
assertions of ``test_bench_autosave.py`` stood for before the benchmark had
a sixth cell (``tests/conftest.py`` marks them as expected to fail). Since
PR 43 also what ``test_bench_autosave.py`` holds of the array cell: a window
of a stated number of periods, a trace started by step count, and a
comparison that no fold runs under."""

import functools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import (autosave_keys_controls, counts_chain_keys,
                       reference_chain_keys, run, train_autosave_runner)
from benchmark.metrics import _autosave_keys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepfm_dim9_hash_ckpt.train_zipf_autosave_keys"
ARRAY_CELL = "deepfm_dim9_array_ckpt.train_zipf_autosave"
TINY = "tiny_hash_ckpt.train_zipf_autosave_keys"
NEW_METRICS = ("train_autosave_step_probe_device_ms_per_step",
               "train_autosave_keys_gather_roofline",
               "train_autosave_find_device_ms_per_save",
               "train_autosave_keys_absent_per_save")
SEED = 3400000019       # past 2**31, as the driver's are


def test_dry_resolves_six_cells_each_to_its_own_runner():
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--dry"],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert len(lines) == len(run.manifest()["workloads"]) == 6
    assert lines[-1] == (
        f"{CELL}: configs/deepfm_dim9_hash_ckpt.json "
        "traffic/train_zipf_autosave_keys.json traffic_gen/zipf_train.py "
        "train_autosave_keys_runner.py")
    # what test_bench_autosave.py's count of five stood for
    assert lines[-2] == (
        f"{ARRAY_CELL}: configs/deepfm_dim9_array_ckpt.json "
        "traffic/train_zipf_autosave.json traffic_gen/zipf_train.py "
        "train_autosave_runner.py")
    assert (
        "deepfm_dim9_offload.train_zipf_offload: "
        "configs/deepfm_dim9_offload.json traffic/train_zipf_offload.json "
        "traffic_gen/zipf_train.py train_offload_runner.py") in lines


def test_the_array_autosave_cell_is_as_it_was_accepted():
    """What ``test_the_configuration_is_the_array_cells_plus_the_
    deployment`` stood for: its last assertion held the ``train_autosave_*``
    lists to the array cell alone, and this PR appends a cell to seven."""
    array = run.load("configs", "deepfm_dim9_array")
    ckpt = run.load("configs", "deepfm_dim9_array_ckpt")
    differs = {"name", "source", "stands_for", "guarantees", "reduced",
               "assumed"}
    assert {k: array[k] for k in array if k not in differs} \
        == {k: ckpt[k] for k in array if k not in differs}
    assert ckpt["guarantees"][:3] == array["guarantees"]
    assert len(ckpt["guarantees"]) == 7
    assert ckpt["reduced"] == ["rows_per_feature", "autosave_every"]
    bench = run.manifest()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    accepted = ("train_autosave_stall_ms_per_save",
                "train_autosave_gather_device_ms_per_save",
                "train_autosave_gather_roofline",
                "train_autosave_d2h_ms_per_save",
                "train_autosave_write_ms_per_save",
                "train_autosave_commit_lag_ms",
                "train_autosave_rows_per_save",
                "train_autosave_mb_per_save")
    found = {m["name"]: m for m in bench["per_layer"]}
    for name in accepted:
        assert found[name]["layer"] == "checkpoint"
        assert found[name]["workloads"][0] == ARRAY_CELL
        # 80 B a row without keys: not this cell's count
        assert found[name]["workloads"][1:] == (
            [] if name == "train_autosave_gather_roofline" else [CELL])


def test_the_configuration_is_the_hash_cells_plus_the_deployment():
    hashed = run.load("configs", "deepfm_dim9_hash")
    array_ckpt = run.load("configs", "deepfm_dim9_array_ckpt")
    ckpt = run.load("configs", "deepfm_dim9_hash_ckpt")
    differs = {"name", "source", "stands_for", "guarantees", "reduced",
               "assumed"}
    assert {k: hashed[k] for k in hashed if k not in differs} \
        == {k: ckpt[k] for k in hashed if k not in differs}
    assert ckpt["guarantees"][:3] == hashed["guarantees"]
    assert len(ckpt["guarantees"]) == 8
    assert "none missing, none extra" in ckpt["guarantees"][-1]
    # the array deployment's, in a window of four periods for its six
    assert ckpt["checkpoint"] == dict(array_ckpt["checkpoint"],
                                      window_periods=4)
    assert array_ckpt["checkpoint"]["window_periods"] == 6
    assert ckpt["reduced"] == ["hash_capacity", "autosave_every",
                               "window_periods"]
    assert set(ckpt["why_reduced"]) == set(ckpt["reduced"])
    assert {k: v for k, v in ckpt["assumed"].items()
            if k != "autosave_dir"} == hashed["assumed"]
    assert ckpt["assumed"]["autosave_dir"] and ckpt["stands_for"]
    assert len(ckpt["source"]) <= 200
    assert "--checkpoint" in ckpt["source"] \
        and "to_hash_bucket_fast" in ckpt["source"]
    traffic = run.load("traffic", "train_zipf_autosave_keys")
    same = run.load("traffic", "train_zipf_autosave")
    for key in ("generator", "zipf_a", "steps_in_flight", "warmup_steps",
                "lead_in_steps", "trace_periods"):
        assert traffic[key] == same[key]
    assert "trace_seconds" not in traffic
    assert traffic["kind"] == "train_autosave_keys"
    # the window's call: 24 + 4 periods, of which the last 56 steps lie
    # past the pool's first pass (left so: PERF.md section 7)
    to_hand, traced_from = train_autosave_runner.window_batches(
        ckpt, traffic)
    assert (to_hand, traced_from) == (824, 624)
    assert to_hand - traffic["pool_batches"] == 56
    assert "24 + 800 steps" in traffic["why"] and "last 56" in traffic["why"]
    bench = run.manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1 and len(cells[CELL]["why"]) <= 200
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "examples_per_s")["workloads"]
    reported = {m["name"] for m in run.metrics_of(
        bench, "per_layer", CELL, "train_zipf_autosave_keys")}
    assert reported >= set(NEW_METRICS) | {
        "train_step_mfu", "train_device_idle_share",
        "train_gather_roofline", "train_scatter_roofline",
        "train_autosave_stall_ms_per_save",
        "train_autosave_step_dedup_device_ms_per_step"}
    assert "train_autosave_gather_roofline" not in reported
    layers = {"train_autosave_step_probe_device_ms_per_step":
              "hash probe and insert"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["layer"] == layers.get(m["name"], "checkpoint")
            assert m["moves"] == "examples_per_s"
    assert "4 a window" in cells[CELL]["why"] \
        and "24 + 800 steps" in cells[CELL]["why"]
    tiny = run.load("configs", "tiny_hash_ckpt")
    assert tiny["rehearsal"] and tiny["checkpoint"]["autosave_every"] == 64
    assert tiny["checkpoint"]["window_periods"] == 1
    assert tiny["guarantees"] == ckpt["guarantees"]


def test_the_checkpoints_reference_imports_nothing_of_the_program():
    for module in (reference_chain_keys, counts_chain_keys):
        with open(module.__file__) as f:
            source = f.read()
        assert "openembedding" not in source and "jax" not in source
        imports = [line for line in source.splitlines()
                   if line.startswith(("import ", "from "))]
        assert all(line.startswith(("import os", "import numpy as np",
                                    "from .reference_chain import",
                                    "from . import counts"))
                   for line in imports), imports


def test_a_program_that_tracks_key_chunks_is_refused_before_the_tables():
    """The parent under this benchmark: its every delta of the cell's
    tables is a scan of 9.5 GiB on the host with the chip idle. The run
    ends at once, with another exit code than 0."""
    from benchmark import autosave_keys_system, system as system_lib
    system = system_lib.build(run.load("configs", "tiny_hash_ckpt"))
    arm = system.coll.enable_dirty_tracking
    system.coll.enable_dirty_tracking = lambda: arm(target_chunks=1024)
    with pytest.raises(SystemExit, match="not to the key"):
        autosave_keys_system.arm(system)
    system = system_lib.build(run.load("configs", "tiny_hash_ckpt"))
    autosave_keys_system.arm(system)            # to the key: armed
    assert set(system.coll.dirty_trackers) == set(system.coll.specs)
    assert all(t.dirty_count == 0           # the probe key is gone again
               for t in system.coll.dirty_trackers.values())


def test_counts_of_a_save_of_keys():
    config = run.load("configs", "deepfm_dim9_hash_ckpt")
    assert counts_chain_keys.saved_key_bytes(config) == 96
    assert counts_chain_keys.gather_bytes(config, 1000) == 96_000
    ids = np.array([[1, 5], [1, 6]], np.uint64)
    ranks = np.array([[1, 9], [1, 3]], np.int64)
    batches = [{"ids": ids, "ranks": ranks},
               {"ids": ids[:1], "ranks": ranks[:1]}]
    assert counts_chain_keys.distinct_keys(batches) == 3
    # ranks up to 3 were filled in: of feature 1's keys only id 6 is held
    held = counts_chain_keys.held_after(
        {"prefill_ranks_per_feature": 3}, [])
    assert counts_chain_keys.distinct_keys(batches, held) == 2
    # ... and what an earlier batch pushed
    held = counts_chain_keys.held_after(
        {"prefill_ranks_per_feature": 3},
        [{"ids": np.array([[7, 5]], np.uint64)}])
    assert counts_chain_keys.distinct_keys(batches, held) == 3


def _chain(tmp_path, base, entries):
    """A chain directory in the documented layout, written by hand."""
    path = str(tmp_path)
    os.makedirs(os.path.join(path, "var_0_t.d"))
    for field, rows in base.items():
        np.save(os.path.join(path, "var_0_t.d", f"{field}.npy"), rows)
    chain = []
    for seq, payload in enumerate(entries, 1):
        np.savez(os.path.join(path, f"delta_{seq:06d}_0.npz"), **payload)
        chain.append({"seq": seq, "step": seq, "vars": {
            "t": {"file": f"delta_{seq:06d}_0.npz"}}})
    with open(os.path.join(path, "delta_manifest"), "w") as f:
        json.dump({"format": 3, "chain": chain}, f)
    return path


@pytest.mark.parametrize("wide", [False, True], ids=["int32", "wide"])
def test_reference_replays_newest_wins_by_key(tmp_path, wide):
    def keys(values):
        values = np.asarray(values, np.int64)
        if not wide:
            return values.astype(np.int32)
        return np.stack([(values & 0xFFFFFFFF).astype(np.uint32)
                         .view(np.int32), (values >> 32).astype(np.int32)],
                        axis=1)

    big = (1 << 40) if wide else 1
    base = {"keys": keys([3 * big, 9 * big, 4 * big]),
            "weights": np.array([[1.], [2.], [3.]], np.float32)}
    entries = [{"keys": keys([9 * big, 7 * big]),
                "weights": np.array([[20.], [70.]], np.float32)},
               {"keys": keys([7 * big, 5 * big]),
                "weights": np.array([[71.], [50.]], np.float32)}]
    path = _chain(tmp_path, base, entries)
    index = reference_chain_keys.Index(path, 0)
    assert index.rows == 5 and index.new_keys == [1, 1]
    rows = reference_chain_keys.replayed(path, 0, "weights", index)
    at = index.at(np.array([3, 9, 4, 7, 5, 8], np.int64) * big)
    assert at[-1] == -1
    assert rows[at[:5], 0].tolist() == [1., 20., 3., 71., 50.]
    assert reference_chain_keys.entry_keys(path) == [{0: 2}, {0: 2}]
    # a live table of 8 slots: the same keys somewhere, empty slots between
    empty = np.iinfo(np.int32).min
    live_keys = np.full((8,) + base["keys"].shape[1:], empty, np.int32)
    live_rows = np.zeros((8, 1), np.float32)
    for slot, (key, row) in zip((6, 0, 3, 5, 2), (
            (3, 1.), (9, 20.), (4, 3.), (7, 71.), (5, 50.))):
        live_keys[slot] = keys([key * big])[0]
        live_rows[slot] = row

    def live(vid, field, lo, hi):
        return (live_keys if field == "keys" else live_rows)[lo:hi]

    found = reference_chain_keys.compare(path, live, 8, block=3)
    assert (found["mismatch_rows"], found["missing_keys"],
            found["extra_keys"]) == (0, 0, 0)
    assert found["new_keys"] == {0: [1, 1]}
    live_rows[3] = 3.5                          # key 4 moved on
    live_keys[1] = keys([11 * big])[0]          # a key no entry has
    live_keys[2] = empty                        # key 5 is gone
    found = reference_chain_keys.compare(path, live, 8, block=3)
    assert (found["mismatch_rows"], found["missing_keys"],
            found["extra_keys"]) == (1, 1, 1)
    # the first entry alone
    found = reference_chain_keys.compare(path, live, 8, entries=1)
    assert found["new_keys"] == {0: [1]}


@functools.lru_cache(maxsize=None)
def _tiny_run(seconds, trace):
    """One run of the rehearsal, once a test process (the file's tests run
    in one)."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TINY,
         "--seed", str(SEED), "--seconds", seconds, "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def _line_of(out, start):
    return next(json.loads(text) for text in out.stdout.splitlines()
                if text.startswith(start))


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_hash_ckpt_runs_end_to_end_with_null_timings(trace):
    # one period (``window_periods``): the run holds one save and the 24
    # lead-in steps' worth after it
    out = _tiny_run("0.05", trace)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 64
    for name in ("chain_mismatch_rows", "chain_missing_keys",
                 "chain_extra_keys", "chain_rows_off", "chain_late_rows",
                 "insert_failures"):
        assert line["compared"][name] == {"value": 0, "limit": 0}
    metrics = line["metrics"]
    if trace == "0":
        assert set(metrics) == {"examples_per_s", "setup_s"}
        assert all(m["value"] is None for m in metrics.values())
        return
    assert metrics["train_compiles_in_window"]["value"] == 0
    keys = metrics["train_autosave_rows_per_save"]["value"]
    compared = next(json.loads(text) for text in out.stdout.splitlines()
                    if text.startswith('{"compared_at"'))
    assert keys == compared["entry_keys"][1] > 0    # one save, a table
    # every entry after the warm one brought keys the chain did not have
    for new in compared["entry_new_keys"].values():
        assert new[0] == 0 and all(n > 0 for n in new[1:])
    # 96 B a key over both tables and the files' headers: not the table's
    megabytes = metrics["train_autosave_mb_per_save"]["value"]
    assert keys * 96 <= megabytes * 1e6 <= keys * 96 * 1.25
    assert metrics["train_autosave_keys_absent_per_save"]["value"] == 0
    for name in ("train_autosave_find_device_ms_per_save",
                 "train_autosave_keys_gather_roofline",
                 "train_autosave_gather_device_ms_per_save"):
        assert name not in metrics              # no device plane here
    warm = next(json.loads(text) for text in out.stdout.splitlines()
                if text.startswith('{"ckpt_dir"'))["warm"]
    assert warm["keys_absent"] > 0      # marked ahead of their push
    window = next(json.loads(text) for text in out.stdout.splitlines()
                  if text.startswith('{"window_s"'))
    assert window["autosave"]["ckpt_delta_saves"] == 1
    assert window["autosave"]["trainer.autosave"]["calls"] == 1
    assert window["autosave"]["ckpt_delta_keys_absent"] == 0


def test_the_window_holds_the_same_work_at_any_seconds():
    short, long = _tiny_run("0.05", "0"), _tiny_run("5", "0")
    assert long.returncode == 0, long.stderr[-2000:]
    lines = [json.loads(out.stdout.splitlines()[-1])
             for out in (short, long)]
    assert [line["attempted"] for line in lines] == [64, 64]
    assert all(line["correct"] is True for line in lines)
    windows = [_line_of(out, '{"window_s"') for out in (short, long)]
    assert [w["steps"] for w in windows] == [64, 64]
    assert [w["autosave"]["ckpt_delta_saves"] for w in windows] == [1, 1]
    compared = [_line_of(out, '{"compared_at"') for out in (short, long)]
    assert compared[0]["entry_keys"] == compared[1]["entry_keys"]
    assert len(compared[0]["entry_keys"]) == 3
    assert compared[0]["entry_new_keys"] == compared[1]["entry_new_keys"]
    tails = [_line_of(out, '{"tail_save"') for out in (short, long)]
    assert tails[0] == tails[1] and tails[0]["chain_after"] == 3


def test_the_traced_tail_holds_a_save_at_any_seconds():
    short, long = _tiny_run("0.05", "1"), _tiny_run("5", "1")
    assert long.returncode == 0, long.stderr[-2000:]
    metrics = [json.loads(out.stdout.splitlines()[-1])["metrics"]
               for out in (short, long)]
    assert set(metrics[0]) == set(metrics[1])
    per_save = {"train_autosave_stall_ms_per_save",
                "train_autosave_d2h_ms_per_save",
                "train_autosave_write_ms_per_save",
                "train_autosave_commit_lag_ms",
                "train_autosave_rows_per_save",
                "train_autosave_mb_per_save",
                "train_autosave_keys_absent_per_save"}
    assert per_save <= set(metrics[1])
    for name in ("train_autosave_rows_per_save",
                 "train_autosave_mb_per_save",
                 "train_autosave_keys_absent_per_save",
                 "train_compiles_in_window"):
        assert metrics[0][name] == metrics[1][name]
    assert metrics[1]["train_autosave_rows_per_save"]["value"] > 0
    assert os.path.isdir(os.path.join(
        ROOT, "benchmark", "out", f"{TINY}.{SEED}.trace"))


def _with_budget(monkeypatch, **budget):
    """Lower the budgets the program's saves start a fold at, in this
    process alone: its saves read them as ``begin_delta``'s defaults."""
    from openembedding_tpu import checkpoint_delta
    for name, value in budget.items():
        monkeypatch.setitem(checkpoint_delta.begin_delta.__kwdefaults__,
                            name, value)


# the rehearsal's chain: 2.42 MB (warm), 3.38 MB (the window's), 1.49 MB
# (the last steps') over a base of 51.6 MB
@pytest.mark.parametrize("budget", [{"compact_chain_len": 3},
                                    {"compact_bytes_ratio": 0.13}],
                         ids=["entries", "bytes"])
def test_the_runners_own_last_save_starts_no_fold(monkeypatch, capsys,
                                                  budget):
    """A budget that the last save's entry meets, and no save before it:
    as the array cell's stands (its last save is the chain's eighth
    entry). The runner's own save asks for no fold (a fold of the cell's
    base holds the machine 140 s and writes 6.5 GB: PERF.md), so the
    chain it leaves lists every entry and nothing writes the directory
    under the comparison."""
    _with_budget(monkeypatch, **budget)
    assert autosave_keys_controls.main(["tiny_hash_ckpt", "none",
                                        str(SEED), "0.05"]) == 0
    out = capsys.readouterr().out.splitlines()
    tail = next(json.loads(t) for t in out if t.startswith('{"tail_save"'))
    assert tail["tail_save"]["compaction"] is None
    assert tail["tail_save"]["seq"] == tail["chain_after"] == 3
    result = json.loads(out[-1])
    assert result["correct"] is True and result["attempted"] == 64
    for name in ("chain_mismatch_rows", "chain_missing_keys",
                 "chain_extra_keys", "chain_rows_off", "chain_late_rows"):
        assert result["compared"][name] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("budget", [{"compact_chain_len": 2},
                                    {"compact_bytes_ratio": 0.08}],
                         ids=["entries", "bytes"])
def test_a_budget_met_inside_the_window_ends_the_run_in_one_line(
        monkeypatch, capsys, budget):
    _with_budget(monkeypatch, **budget)
    with pytest.raises(SystemExit) as refused:
        autosave_keys_controls.main(["tiny_hash_ckpt", "none", str(SEED),
                                     "0.05"])
    said = str(refused.value)
    assert "\n" not in said and said.startswith("benchmark: ")
    assert "lists 0 entries where the warm save and the window made 2" \
        in said and "met the compactor's budget" in said
    entries = budget.get("compact_chain_len", 8)
    ratio = budget.get("compact_bytes_ratio", 0.5)
    assert f"a chain of {entries} entries, or {ratio} of the base's " \
        "51587328 bytes" in said
    assert "(5807296 bytes)" in said and "window_periods" in said
    assert '{"tail_save"' not in capsys.readouterr().out


def test_reference_replays_by_key_a_chain_a_fold_left_empty(tmp_path):
    base = {"keys": np.array([3, 9, 4], np.int32),
            "weights": np.array([[1.], [2.], [3.]], np.float32)}
    path = _chain(tmp_path, base, [])
    assert reference_chain_keys.entry_keys(path) == []
    index = reference_chain_keys.Index(path, 0)
    assert index.rows == 3 and index.new_keys == []
    assert index.at(np.array([9, 5], np.int64)).tolist() == [1, -1]
    np.testing.assert_array_equal(
        reference_chain_keys.replayed(path, 0, "weights", index),
        base["weights"])
    empty = np.iinfo(np.int32).min
    live_keys = np.array([empty, 4, empty, 3, 9, empty], np.int32)
    live_rows = np.array([[0.], [3.], [0.], [1.], [2.], [0.]], np.float32)

    def live(vid, field, lo, hi):
        return (live_keys if field == "keys" else live_rows)[lo:hi]

    found = reference_chain_keys.compare(path, live, 6, block=4)
    assert found == {"mismatch_rows": 0, "missing_keys": 0,
                     "extra_keys": 0, "new_keys": {0: []}}
    live_rows[4] = 2.5
    live_keys[0] = 11
    found = reference_chain_keys.compare(path, live, 6, block=4)
    assert (found["mismatch_rows"], found["missing_keys"],
            found["extra_keys"]) == (1, 1, 0)


def test_entry_keys_reads_the_chain_from_an_entry_on(tmp_path):
    base = {"keys": np.array([3], np.int32),
            "weights": np.array([[1.]], np.float32)}
    entries = [{"keys": np.array([9, 7], np.int32),
                "weights": np.array([[20.], [70.]], np.float32)},
               {"keys": np.array([5], np.int32),
                "weights": np.array([[50.]], np.float32)}]
    path = _chain(tmp_path, base, entries)
    assert reference_chain_keys.entry_keys(path) == [{0: 2}, {0: 1}]
    assert reference_chain_keys.entry_keys(path, first=1) == [{0: 1}]
    assert reference_chain_keys.entry_keys(path, first=2) == []
    assert train_autosave_runner.entries_off(
        reference_chain_keys.entry_keys(path, first=1), [1]) == 0


@pytest.mark.parametrize("fault", autosave_keys_controls.FAULTS)
def test_planted_fault_comes_out_not_correct(capsys, fault):
    assert autosave_keys_controls.main(["tiny_hash_ckpt", fault, str(SEED),
                                        "0.05"]) == 0
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["correct"] is False
    over = {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {
        "marks_dropped": {"chain_mismatch_rows", "chain_missing_keys",
                          "chain_rows_off", "chain_late_rows"},
        "snapshot_late": {"chain_rows_off", "chain_late_rows"},
        "stale_accumulator": {"chain_mismatch_rows", "chain_late_rows"},
        "fresh_keys_dropped": {"chain_missing_keys", "chain_rows_off",
                               "chain_late_rows"},
    }[fault]


def _event(name, start_ns, duration_ns):
    return types.SimpleNamespace(name=name, start_ns=start_ns,
                                 duration_ns=duration_ns)


HLO = """HloModule jit_ckpt_gather_keys

ENTRY %main (p: s32[8]) -> s32[8] {
  %while.%d = s32[8] while(%p), metadata={op_name="jit(ckpt_gather_keys)/jit(main)/jit(ckpt_find)/while"}
  %fusion.%d = s32[8] fusion(%p), metadata={op_name="jit(ckpt_gather_keys)/jit(main)/jit(ckpt_find)/while/body/gather"}
  %while.9 = f32[8] while(%p), metadata={op_name="jit(ckpt_gather_keys)/jit(main)/jit(ckpt_gather)/while"}
}
"""


def test_readers_reduce_a_runs_counters_and_modules():
    config = run.load("configs", "deepfm_dim9_hash_ckpt")
    ms = 1_000_000
    # one traced save on one chip: each table's program (its find first,
    # then its gather) and the dense copy; the second program numbers its
    # find's instructions as the first one's gather
    ops = [_event("%while.1 = s32[8] while(%p)", 30 * ms, 80 * ms),
           _event("%fusion.1 = s32[8] fusion(%p)", 31 * ms, 70 * ms),
           _event("%while.9 = f32[8] while(%p)", 110 * ms, 100 * ms),
           _event("%while.9 = s32[8] while(%p)", 215 * ms, 60 * ms),
           _event("%fusion.9 = s32[8] fusion(%p)", 216 * ms, 50 * ms),
           _event("%while.7 = f32[8] while(%p)", 275 * ms, 40 * ms)]
    second = HLO.replace("%d", "9").replace(
        "%while.9 = f32[8] while(%p), metadata={op_name=\"jit(ckpt_gather_"
        "keys)/jit(main)/jit(ckpt_gather)", "%while.7 = f32[8] while(%p), "
        "metadata={op_name=\"jit(ckpt_gather_keys)/jit(main)/jit(ckpt_"
        "gather)")
    run_ = {
        "config": config, "device_kind": "TPU v5 lite",
        "trace": {"steps": 200},
        "snapshot_hlo": [HLO.replace("%d", "1"), second],
        "autosave": {"ckpt_delta_saves": 3.0,
                     "ckpt_delta_rows": 6_000_000.0,
                     "ckpt_delta_keys_absent": 6.0},
        "device_lines": [(ops, [
            _event("jit_step_fn(1)", 0, 25 * ms),
            _event("jit_ckpt_gather_keys(7)", 30 * ms, 180 * ms),
            _event("jit_ckpt_gather_keys(8)", 215 * ms, 100 * ms),
            _event("jit_ckpt_gather_dense(9)", 320 * ms, ms // 10)])]}

    def read(name):
        return __import__(f"benchmark.metrics.{name}",
                          fromlist=["read"]).read(run_)

    seconds, saves = _autosave_keys.find_device_s(run_)
    assert saves == 1 and seconds == pytest.approx(0.140)
    assert read("train_autosave_find_device_ms_per_save") \
        == pytest.approx(140.0)
    assert read("train_autosave_keys_absent_per_save") == 2.0
    # 1M keys a table x 96 B over 819 GB/s over 0.2801 s
    assert read("train_autosave_keys_gather_roofline") == pytest.approx(
        100 * 96e6 / 819e9 / 0.2801, rel=1e-6)
    # a save whose programs the trace holds only in part is not counted
    run_["device_lines"][0][1].pop(1)
    assert _autosave_keys.find_device_s(run_) is None
    # a context without the spans, counters, texts or stage: nothing
    bare = {"config": config, "device_kind": "TPU v5 lite",
            "trace": {"steps": 200}, "step_hlo": None,
            "device_lines": [([], [_event("jit_step_fn(1)", 0, 25 * ms)])],
            "autosave": {"ckpt_delta_saves": 0.0, "ckpt_delta_rows": 0.0}}
    for name in NEW_METRICS:
        reader = __import__(f"benchmark.metrics.{name}", fromlist=["read"])
        assert reader.read(bare) is None, name
        assert reader.read({"config": config, "steps": 0}) is None, name
    no_stage = dict(run_, snapshot_hlo=["HloModule jit_ckpt_gather\n"])
    assert _autosave_keys.find_device_s(no_stage) is None
