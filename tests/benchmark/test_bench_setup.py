"""The ``setup_*`` readers on the CPU: the manifest's seven entries, the
rehearsals' two counts beside five null timings, and the adapter's two
ways of finding nothing to read. Also what three accepted cases of
``test_bench_run.py::test_rehearsal_runs_end_to_end_with_null_timings``
stood for before a rehearsal reported counts of set-up (``tests/conftest.py``
marks them as expected to fail): every timing of a traced rehearsal is
``null``, and the one count it named reads 0."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import run, setup_system
from benchmark.metrics import _setup

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COUNTS = ("setup_programs_loaded", "setup_cache_misses")
TIMINGS = ("setup_import_s", "setup_cache_fetch_s",
           "setup_backend_compile_s", "setup_trace_lower_s",
           "setup_warm_fit_s")
SEED = 3600000019       # past 2**31, as the driver's are


def test_every_new_entry_moves_setup_s_in_all_six_cells():
    bench = run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 6
    found = {m["name"]: m for m in bench["per_layer"]}
    assert list(found)[-7:] == [TIMINGS[0], *COUNTS, *TIMINGS[1:]]
    for name in COUNTS + TIMINGS:
        m = found[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["workloads"] == cells
        assert m["layer"] == ("trainer host loop"
                              if name == "setup_warm_fit_s"
                              else "device selection, cache")
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.TIMING is (name in TIMINGS)
        assert m["unit"] == ("s" if reader.TIMING else "count")
    # nothing that was there says it moves set-up, and nothing was taken
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == list(found)[-7:]
    assert len(found) == 63


@pytest.mark.parametrize("cell,seconds", [
    ("tiny_array.train_zipf", "0.5"), ("tiny_hash.train_zipf", "0.5"),
    ("tiny_array_x4.train_zipf", "0.5"),
    ("tiny_hash_ckpt.train_zipf_autosave_keys", "0.05")])
def test_a_traced_rehearsal_counts_set_up_and_times_nothing(cell, seconds):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", str(SEED), "--seconds", seconds, "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    loaded, missed = (metrics[name]["value"] for name in COUNTS)
    assert isinstance(loaded, int) and isinstance(missed, int)
    # no persistent cache in a rehearsal: every program was compiled
    assert 0 < missed <= loaded
    marks = [json.loads(text) for text in out.stdout.splitlines()
             if text.startswith('{"set_up"')]
    before_window = [m for m in marks if m["set_up"] in (
        "warm", "saves_warm")][-1]
    assert loaded >= before_window["programs"] > 0
    for name in TIMINGS:
        assert metrics[name] == {"value": None, "unit": "s"}
    # what the accepted rehearsal test held: a count of 0, no clock read
    assert metrics["train_compiles_in_window"]["value"] == 0
    assert all(m["value"] is None for name, m in metrics.items()
               if name not in COUNTS and importlib.import_module(
                   f"benchmark.metrics.{name}").TIMING)
    assert any(m["value"] is None for m in metrics.values())


def _program(calls):
    """A stand-in for ``analysis.retrace``: a ledger whose log holds
    ``calls`` as (start, end, steps, programs at entry)."""
    totals = {"programs": 0, "hits": 0, "misses": 0, "off": 0,
              "trace_s": 0.5, "lower_s": 0.25, "fetch_s": 2.0,
              "compile_s": 0.0, "saved_s": 0.0}
    log = [types.SimpleNamespace(start=s, end=e, steps=n,
                                 totals=dict(totals, programs=p, hits=p))
           for s, e, n, p in calls]
    return types.SimpleNamespace(LEDGER=types.SimpleNamespace(
        fit_calls=log, import_s=9.5))


def test_the_adapter_cuts_the_ledger_at_the_windows_call():
    program = _program([(10.0, 11.0, 1, 30), (11.5, 12.0, 12, 41),
                        (13.0, 40.0, 724, 44), (50.0, None, 724, 60),
                        (45.0, 46.0, 1, 50)])
    read = setup_system.at_window(724, program)
    assert read["import_s"] == 9.5 and read["at"] == 13.0
    assert read["totals"]["programs"] == 44
    assert read["warm_fit_s"] == 1.5        # the calls that had returned
    run_ = {"steps": 700, "traffic": {"lead_in_steps": 24}}
    assert _setup.ledger({"steps": None}) is None
    # a reader goes to the real program; its log holds no such call
    assert _setup.ledger(run_) is None and run_["setup"] is None
    run_["setup"] = read
    assert _setup.total(run_, "programs") == 44
    assert _setup.total(run_, "trace_s", "lower_s") == 0.75
    for name, want in (("setup_import_s", 9.5), ("setup_warm_fit_s", 1.5),
                       ("setup_programs_loaded", 44),
                       ("setup_cache_misses", 0),
                       ("setup_cache_fetch_s", 2.0),
                       ("setup_backend_compile_s", 0.0),
                       ("setup_trace_lower_s", 0.75)):
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read(run_) == want, name


def test_no_matching_call_and_no_ledger_read_none():
    program = _program([(10.0, 11.0, 1, 30), (13.0, None, 724, 44)])
    assert setup_system.at_window(724, program) is None     # still running
    assert setup_system.at_window(5, program) is None
    assert setup_system.at_window(1, types.ModuleType("retrace")) is None
    for name in COUNTS + TIMINGS:
        reader = importlib.import_module(f"benchmark.metrics.{name}")
        assert reader.read({"steps": 5, "setup": None}) is None, name
        assert reader.read({}) is None, name
