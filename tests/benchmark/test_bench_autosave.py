"""The autosave cell's halves on the CPU: the files resolve, the
configuration is the array cell's plus the deployment, ``tiny_array_ckpt``
rehearses the cell end to end, the three planted faults come out as not
correct, the checkpoint's plain reference imports nothing of the program,
and the new readers reduce what a run leaves them. Since PR 43 also: the
window is a stated number of save periods whatever ``--seconds`` says, a
traced run starts the profiler by step count, and no comparison reads the
directory under a fold (the program's compaction budgets are lowered here,
in the test's own process, so that the runner's last save would meet them,
which asks for no fold, or a save inside the window does, which ends the
run in a line)."""

import functools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import (autosave_controls, counts_chain, reference_chain, run,
                       train_autosave_runner, train_runner)
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


class Probe:
    def __call__(self, x):
        return self

    def block_until_ready(self):
        return self


def test_the_window_is_the_configurations_periods_not_the_clocks():
    assert not hasattr(train_autosave_runner, "PeriodFeed")
    traffic = run.load("traffic", "train_zipf_autosave")
    assert "trace_seconds" not in traffic and traffic["trace_periods"] == 1
    # lead-in + periods x every handed out; the profiler before the last
    assert train_autosave_runner.window_batches(
        run.load("configs", "deepfm_dim9_array_ckpt"), traffic) \
        == (24 + 1200, 24 + 1000)
    assert train_autosave_runner.window_batches(
        run.load("configs", "tiny_array_ckpt"), traffic) == (24 + 64, 24)
    config = {"checkpoint": {"autosave_every": 5, "window_periods": 2}}
    to_hand, traced_from = train_autosave_runner.window_batches(
        config, {"lead_in_steps": 3, "trace_periods": 1})
    assert (to_hand, traced_from) == (13, 8)
    called = []
    feed = train_runner.Feed(
        list(range(7)), Probe(), lag=1, in_flight=2, steps=to_hand,
        lead_in=3, at_step=(traced_from, lambda: called.append(
            feed.handed)))
    handed = list(feed)
    assert len(handed) == 13 and feed.handed - feed.lead_in == 10
    assert handed[:8] == [0, 1, 2, 3, 4, 5, 6, 0]
    assert called == [8] and feed.called_at is not None


def test_the_step_counted_call_waits_for_the_window_to_open():
    """A window of one period starts its trace at its first step: not
    before the lead-in is done, which is when the clock starts."""
    called = []
    feed = train_runner.Feed(
        list(range(7)), Probe(), lag=1, in_flight=2, steps=9, lead_in=3,
        at_step=(3, lambda: called.append((feed.handed, feed.started))))
    assert len(list(feed)) == 9
    (at, started), = called
    assert at >= 3 and started is not None and feed.called_at[0] >= 0
    # the other cells' hook is as it was: by the clock, once
    feed = train_runner.Feed(
        list(range(7)), Probe(), lag=1, in_flight=2, seconds=0.0,
        at_seconds=(0.0, lambda: called.append("by the clock")))
    assert list(feed) == [] and called[-1] == "by the clock"


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
def test_tiny_array_ckpt_runs_end_to_end_with_null_timings(trace):
    # one period (``window_periods``): the run holds one save and the 24
    # lead-in steps' worth after it
    out = _tiny_run("0.05", trace)
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


def test_the_window_holds_the_same_work_at_any_seconds():
    """What ``--seconds`` decided before PR 43: 5 s were some thousand
    steps of the rehearsal and a save every 64 of them."""
    short, long = _tiny_run("0.05", "0"), _tiny_run("5", "0")
    assert long.returncode == 0, long.stderr[-2000:]
    lines = [json.loads(out.stdout.splitlines()[-1])
             for out in (short, long)]
    assert [line["attempted"] for line in lines] == [64, 64]
    assert all(line["correct"] is True for line in lines)
    windows = [_line_of(out, '{"window_s"') for out in (short, long)]
    assert [w["steps"] for w in windows] == [64, 64]
    assert [w["autosave"]["ckpt_delta_saves"] for w in windows] == [1, 1]
    # the same entries: the warm save's, the window's, the last steps'
    entries = [_line_of(out, '{"compared_at"')["entry_rows"]
               for out in (short, long)]
    assert entries[0] == entries[1] and len(entries[0]) == 3
    tails = [_line_of(out, '{"tail_save"') for out in (short, long)]
    assert tails[0] == tails[1] and tails[0]["chain_after"] == 3


def test_the_traced_tail_holds_a_save_at_any_seconds():
    """The profiler starts by step count: a traced run reports the
    per-save metrics it reported when ``--seconds`` placed the trace."""
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
                "train_autosave_mb_per_save"}
    assert per_save <= set(metrics[1])
    for name in ("train_autosave_rows_per_save",
                 "train_autosave_mb_per_save", "train_compiles_in_window"):
        assert metrics[0][name] == metrics[1][name]
    assert metrics[1]["train_autosave_rows_per_save"]["value"] > 0
    # the profiler ran: it left its directory
    assert os.path.isdir(os.path.join(
        ROOT, "benchmark", "out", f"{TINY}.{SEED}.trace"))


def _with_budget(monkeypatch, **budget):
    """Lower the budgets the program's saves start a fold at, in this
    process alone: its saves read them as ``begin_delta``'s defaults."""
    from openembedding_tpu import checkpoint_delta
    for name, value in budget.items():
        monkeypatch.setitem(checkpoint_delta.begin_delta.__kwdefaults__,
                            name, value)


# the rehearsal's chain: 3.28 MB (warm), 3.28 MB (the window's), 1.47 MB
# (the last steps') over a base of 34.1 MB
@pytest.mark.parametrize("budget", [{"compact_chain_len": 3},
                                    {"compact_bytes_ratio": 0.21}],
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
    assert autosave_controls.main(["tiny_array_ckpt", "none", str(SEED),
                                   "0.05"]) == 0
    out = capsys.readouterr().out.splitlines()
    tail = next(json.loads(t) for t in out if t.startswith('{"tail_save"'))
    assert tail["tail_save"]["compaction"] is None
    assert tail["tail_save"]["seq"] == tail["chain_after"] == 3
    result = json.loads(out[-1])
    assert result["correct"] is True and result["attempted"] == 64
    for name in ("chain_mismatch_rows", "chain_rows_off", "chain_late_rows"):
        assert result["compared"][name] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("budget", [{"compact_chain_len": 2},
                                    {"compact_bytes_ratio": 0.15}],
                         ids=["entries", "bytes"])
def test_a_budget_met_inside_the_window_ends_the_run_in_one_line(
        monkeypatch, capsys, budget):
    _with_budget(monkeypatch, **budget)
    with pytest.raises(SystemExit) as refused:
        autosave_controls.main(["tiny_array_ckpt", "none", str(SEED),
                                "0.05"])
    said = str(refused.value)
    assert "\n" not in said and said.startswith("benchmark: ")
    assert "lists 0 entries where the warm save and the window made 2" \
        in said and "met the compactor's budget" in said
    entries = budget.get("compact_chain_len", 8)
    ratio = budget.get("compact_bytes_ratio", 0.5)
    assert f"a chain of {entries} entries, or {ratio} of the base's " \
        "34079232 bytes" in said
    assert "(6553288 bytes)" in said and "window_periods" in said
    assert '{"tail_save"' not in capsys.readouterr().out


def _array_chain(tmp_path, base, entries):
    """A chain directory of one array variable in the documented layout,
    written by hand."""
    path = str(tmp_path)
    os.makedirs(os.path.join(path, "var_0_t.d"))
    for field, rows in base.items():
        np.save(os.path.join(path, "var_0_t.d", f"{field}.npy"), rows)
    chain = []
    for seq, (ids, payload) in enumerate(entries, 1):
        np.savez(os.path.join(path, f"delta_{seq:06d}_0.npz"),
                 chunks=np.asarray(ids, np.int64), rows_per_chunk=1,
                 vocab=len(base["weights"]), **payload)
        chain.append({"seq": seq, "step": seq, "vars": {
            "t": {"file": f"delta_{seq:06d}_0.npz"}}})
    with open(os.path.join(path, "delta_manifest"), "w") as f:
        json.dump({"format": 2, "chain": chain}, f)
    return path


def test_reference_replays_a_chain_a_fold_left_empty(tmp_path):
    base = {"weights": np.arange(12, dtype=np.float32).reshape(6, 2),
            "slot_sum": np.full((6, 1), 0.1, np.float32)}
    path = _array_chain(tmp_path / "folded", base, [])
    assert reference_chain.manifest(path)["chain"] == []
    assert reference_chain.entry_rows(path) == []
    assert reference_chain.base_bytes(path) == sum(
        os.path.getsize(os.path.join(path, "var_0_t.d", f"{f}.npy"))
        for f in base)
    np.testing.assert_array_equal(
        reference_chain.replayed(path, 0, "weights"), base["weights"])
    live = {f: a.copy() for f, a in base.items()}

    def read(vid, field, lo, hi):
        return live[field][lo:hi]

    assert reference_chain.mismatch_rows(path, read, block=4) == 0
    live["slot_sum"][4] = 0.2
    assert reference_chain.mismatch_rows(path, read, block=4) == 1
    # and a chain that is listed, from its ``first`` entry on
    path = _array_chain(tmp_path / "listed", base, [
        ([1, 4], {"weights": np.ones((2, 2), np.float32),
                  "slot_sum": np.ones((2, 1), np.float32)}),
        ([4], {"weights": np.zeros((1, 2), np.float32),
               "slot_sum": np.zeros((1, 1), np.float32)})])
    assert reference_chain.entry_rows(path) == [{0: 2}, {0: 1}]
    assert reference_chain.entry_rows(path, first=1) == [{0: 1}]
    assert reference_chain.replayed(path, 0, "weights")[[1, 4]].tolist() \
        == [[1., 1.], [0., 0.]]


def test_entries_are_counted_against_what_the_feed_handed_out():
    off = train_autosave_runner.entries_off
    assert off([{0: 5, 1: 5}, {0: 3, 1: 3}], [5, 3]) == 0
    assert off([{0: 5, 1: 5}, {0: 3, 1: 4}], [5, 3]) == 1
    assert off([{0: 5, 1: 5}, {}], [5, 3]) == 1     # an entry of no file
    assert off([{0: 5, 1: 5}], [5, 3]) == 2         # an entry too few
    assert off([], []) == 0


def test_settle_joins_the_compactor_before_it_reads_the_manifest(tmp_path):
    base = {"weights": np.zeros((4, 2), np.float32)}
    path = _array_chain(tmp_path, base, [
        ([1], {"weights": np.ones((1, 2), np.float32)})])
    order = []

    class Adapter:
        @staticmethod
        def join_compactor(at):
            order.append(("joined", at, os.path.isfile(
                os.path.join(at, "delta_manifest"))))

        @staticmethod
        def compaction_budget():
            return 8, 0.5

    chain = train_autosave_runner.settle(Adapter, path)
    assert [e["seq"] for e in chain] == [1]
    assert train_autosave_runner.settle(Adapter, path, made=1,
                                        made_bytes=7) == chain
    assert order == [("joined", path, True)] * 2
    with pytest.raises(SystemExit, match="lists 1 entries where the warm "
                       "save and the window made 3 .7 bytes."):
        train_autosave_runner.settle(Adapter, path, made=3, made_bytes=7)


def test_the_last_save_is_counted_and_a_chain_folded_anyway_is_off(
        tmp_path, capsys):
    base = {"weights": np.zeros((4, 2), np.float32)}
    one = ([1], {"weights": np.ones((1, 2), np.float32)})
    two = ([0, 2], {"weights": np.ones((2, 2), np.float32)})

    class Adapter:
        join_compactor = staticmethod(lambda at: None)

        def __init__(self, path):
            self.path = path

        def save_last(self, system, state, path, step):
            assert (system, state, path, step) == ("sys", "st", self.path, 9)
            return {"seq": 2, "rows": 2, "bytes": 16}

    def tail(path, want):
        return train_autosave_runner.save_the_tail(
            Adapter(path), reference_chain.entry_rows, "sys", "st", path, 9,
            made=1, want=want)

    listed = _array_chain(tmp_path / "listed", base, [one, two])
    assert tail(listed, 2) == 0 and tail(listed, 3) == 1
    said = json.loads(capsys.readouterr().out.splitlines()[0])
    assert said == {"tail_save": {"seq": 2, "rows": 2, "bytes": 16,
                                  "compaction": None}, "chain_after": 2}
    # a program that folds all the same: no entry to count, not a crash
    assert tail(_array_chain(tmp_path / "folded", base, []), 2) == 1
    # ... or that kept no entry of the save at all
    assert tail(_array_chain(tmp_path / "short", base, [one]), 2) == 1


def test_the_adapter_reads_the_budget_a_save_runs_under(monkeypatch,
                                                        tmp_path):
    from benchmark import autosave_keys_system, autosave_system
    assert autosave_system.compaction_budget() == (8, 0.5)
    assert autosave_keys_system.compaction_budget is \
        autosave_system.compaction_budget
    assert autosave_keys_system.join_compactor is \
        autosave_system.join_compactor
    assert autosave_keys_system.save_last is autosave_system.save_last
    _with_budget(monkeypatch, compact_chain_len=3)
    assert autosave_system.compaction_budget() == (3, 0.5)
    autosave_system.join_compactor(str(tmp_path))   # none runs: returns


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
