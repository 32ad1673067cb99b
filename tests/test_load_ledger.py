"""The load ledger: every program JAX traces, lowers, fetches or compiles
makes one entry; totals stay exact when old entries go; the cache's
nameless events stay on their own thread's program; ``RetraceGuard`` counts
from it; ``/metrics`` shows it; ``Trainer.fit`` logs its calls beside it."""

import os
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from openembedding_tpu.analysis import retrace, scope
from openembedding_tpu.utils import observability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GAP_S = 3 * retrace._SLACK_S        # apart enough to be told apart


def _entries(name):
    return [e for e in retrace.LEDGER.entries if e.name == name]


def _program(ledger, name, cache=None, inner=()):
    """The events of one program as JAX fires them, ``GAP_S`` apart;
    ``inner`` functions are traced inside the trace."""
    t0 = time.perf_counter()
    for fn in inner:
        ledger.on_duration(retrace._TRACE, 1e-4, fun_name=fn)
        time.sleep(GAP_S)
    ledger.on_duration(retrace._TRACE, time.perf_counter() - t0 + 1e-4,
                       fun_name=name)
    time.sleep(GAP_S)
    ledger.on_duration(retrace._LOWER, 2e-4, fun_name=f"jit({name})")
    time.sleep(GAP_S)
    if cache == "hit":
        ledger.on_event(retrace._HIT)
        ledger.on_duration(retrace._SAVED, 1.5)
        ledger.on_duration(retrace._FETCH, 2e-4)
    elif cache == "miss":
        ledger.on_event(retrace._MISS)
    ledger.on_duration(retrace._BACKEND, 3e-4, fun_name=f"jit({name})")


@pytest.fixture
def persistent_cache(tmp_path):
    """The persistent compile cache in a directory of the test's own, with
    the thresholds ``benchmark/run.py`` sets; off again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], -1)
    compilation_cache.reset_cache()
    try:
        yield str(tmp_path)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_the_package_installs_the_ledger_once():
    from jax._src import monitoring
    ledger = retrace.LEDGER
    assert retrace.install() is ledger and retrace.install() is ledger
    assert monitoring.get_event_duration_listeners().count(
        ledger.on_duration) == 1
    assert monitoring.get_event_listeners().count(ledger.on_event) == 1
    # interpreter start, import jax, the package: seconds, not a clock
    assert 0 < ledger.import_s < 3600
    assert ledger.installed_at <= time.perf_counter()


def test_one_registration_with_jax_monitoring_in_the_program():
    found = []
    for top in ("openembedding_tpu", "chip_smoke.py"):
        path = os.path.join(ROOT, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith(".py")]
        for file in files:
            with open(file) as f:
                if re.search(r"monitoring\.register_", f.read()):
                    found.append(os.path.relpath(file, ROOT))
    assert found == ["openembedding_tpu/analysis/retrace.py"]


def test_a_program_makes_one_entry_a_second_shape_a_second():
    @jax.jit
    def ledger_probe_one(x):
        return jnp.tanh(x) * 3

    before = retrace.LEDGER.totals()
    ledger_probe_one(jnp.ones((12,)))
    ledger_probe_one(jnp.ones((12,)))       # in memory: fires nothing
    (entry,) = _entries("ledger_probe_one")
    assert entry.trace_s > 0 and entry.lower_s > 0 and entry.backend_s > 0
    assert entry.cache == "off" and entry.fetch_s == 0.0
    assert entry.start < entry.end <= time.perf_counter()
    assert entry.end - entry.start >= \
        entry.trace_s + entry.lower_s + entry.backend_s - 3e-3
    ledger_probe_one(jnp.ones((13,)))
    assert len(_entries("ledger_probe_one")) == 2
    after = retrace.LEDGER.totals()
    assert after["off"] - before["off"] >= 2
    assert after["programs"] - before["programs"] \
        == after["off"] - before["off"]
    assert after["compile_s"] > before["compile_s"]
    assert after["fetch_s"] == before["fetch_s"]


def test_a_lowering_that_is_never_compiled_keeps_no_backend():
    def ledger_probe_lowered(x):
        return x + 2

    jax.jit(ledger_probe_lowered).lower(jnp.ones((3,)))
    (entry,) = _entries("ledger_probe_lowered")
    assert entry.lower_s > 0 and entry.backend_s is None \
        and entry.cache is None


def test_miss_then_hit_with_the_persistent_cache(persistent_cache):
    @jax.jit
    def ledger_probe_cached(x):
        return jnp.sin(x) + 41

    before = retrace.LEDGER.totals()
    ledger_probe_cached(np.ones(7, np.float32))
    (first,) = _entries("ledger_probe_cached")
    assert first.cache == "miss" and first.fetch_s == 0.0
    assert os.listdir(persistent_cache)
    jax.clear_caches()
    with retrace.RetraceGuard(budget=1 << 30) as guard:
        ledger_probe_cached(np.ones(7, np.float32))
    first, second = _entries("ledger_probe_cached")
    assert second.cache == "hit" and second.fetch_s > 0
    assert second.trace_s > 0 and second.backend_s >= second.fetch_s
    # a fetched program is a program the loop stopped to load
    assert guard.compiles == 1
    after = retrace.LEDGER.totals()
    assert after["hits"] - before["hits"] >= 1
    assert after["misses"] - before["misses"] >= 1
    assert after["fetch_s"] - before["fetch_s"] >= second.backend_s


def test_two_threads_keep_their_own_hit_and_miss():
    ledger = retrace.LoadLedger()
    gate = threading.Barrier(2, timeout=30)

    def load(name, cache):
        ledger.on_duration(retrace._LOWER, 1e-4, fun_name=f"jit({name})")
        gate.wait()
        ledger.on_event(retrace._HIT if cache == "hit" else retrace._MISS)
        gate.wait()     # both caches have spoken, neither backend has
        if cache == "hit":
            ledger.on_duration(retrace._FETCH, 0.25)
        gate.wait()
        ledger.on_duration(retrace._BACKEND, 0.5, fun_name=f"jit({name})")

    threads = [threading.Thread(target=load, args=a)
               for a in (("fetched", "hit"), ("built", "miss"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    by_name = {e.name: e for e in ledger.entries}
    assert by_name["fetched"].cache == "hit"
    assert by_name["fetched"].fetch_s == 0.25
    assert by_name["built"].cache == "miss"
    assert by_name["built"].fetch_s == 0.0
    totals = ledger.totals()
    assert (totals["programs"], totals["hits"], totals["misses"]) == (2, 1, 1)
    assert not ledger._threads          # nothing left waiting to be joined


def test_many_threads_lose_no_program():
    import sys
    ledger = retrace.LoadLedger(keep=64)
    workers, each = 4 * (os.cpu_count() or 2), 40

    def load(w):
        for i in range(each):
            module = f"jit(w{w}_{'hit' if i % 2 else 'miss'})"
            ledger.on_duration(retrace._LOWER, 1e-6, fun_name=module)
            ledger.on_event(retrace._HIT if i % 2 else retrace._MISS)
            ledger.on_duration(retrace._BACKEND, 1e-6, fun_name=module)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=load, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    totals = ledger.totals()
    assert totals["programs"] == workers * each
    assert totals["hits"] == totals["misses"] == workers * each // 2
    assert len(ledger.entries) == 64
    assert all(e.name.endswith(e.cache) for e in ledger.entries)


def test_the_bound_drops_old_entries_and_keeps_totals():
    ledger = retrace.LoadLedger(keep=2)
    for i, cache in enumerate(("miss", "hit", None, "hit", "miss")):
        _program(ledger, f"p{i}", cache)
    assert [e.name for e in ledger.entries] == ["p3", "p4"]
    totals = ledger.totals()
    assert (totals["programs"], totals["hits"], totals["misses"],
            totals["off"]) == (5, 2, 2, 1)
    assert totals["fetch_s"] == pytest.approx(2 * 3e-4)
    assert totals["compile_s"] == pytest.approx(3 * 3e-4)
    assert totals["saved_s"] == pytest.approx(3.0)
    assert totals["lower_s"] == pytest.approx(5 * 2e-4)
    for _ in range(4):
        ledger.fit_returned(ledger.fit_began())
    assert len(ledger.fit_calls) == 2


def test_inner_traces_count_once_and_the_outer_names_the_program():
    ledger = retrace.LoadLedger()
    _program(ledger, "step_fn", inner=("multiply", "pull", "add"))
    (entry,) = ledger.entries
    assert entry.name == "step_fn" and entry.cache == "off"
    assert entry.trace_s >= 3 * GAP_S
    assert ledger.totals()["trace_s"] == pytest.approx(entry.trace_s)
    # a function traced by a lowering rule is part of that lowering
    ledger.on_duration(retrace._TRACE, 1e-4, fun_name="outer")
    time.sleep(GAP_S)
    t0 = time.perf_counter()
    ledger.on_duration(retrace._TRACE, 1e-4, fun_name="_threefry")
    time.sleep(GAP_S)
    ledger.on_duration(retrace._LOWER, time.perf_counter() - t0 + 2e-4,
                       fun_name="jit(outer)")
    outer = ledger.entries[-1]
    assert outer.name == "outer" and outer.trace_s == 1e-4
    assert ledger.totals()["trace_s"] == pytest.approx(
        entry.trace_s + 1e-4)


def test_table_lists_programs_by_cost_and_cuts_at_a_time():
    ledger = retrace.LoadLedger()
    _program(ledger, "small", "hit")
    ledger.on_duration(retrace._LOWER, 1e-4, fun_name="jit(large)")
    ledger.on_duration(retrace._BACKEND, 9.0, fun_name="jit(large)")
    cut = time.perf_counter()
    time.sleep(GAP_S)
    _program(ledger, "late", "miss")
    lines = ledger.table().splitlines()
    assert lines[0].split() == ["program", "calls", "trace_s", "lower_s",
                                "fetch_s", "read_s", "compile_s", "saved_s",
                                "hit", "miss"]
    assert [line.split()[0] for line in lines[1:4]] == ["large", "small",
                                                        "late"] \
        or [line.split()[0] for line in lines[1:4]] == ["large", "late",
                                                        "small"]
    assert lines[-1].startswith("all 3 names")
    assert lines[1].split()[1:] == ["1", "0.000", "0.000", "0.000", "0.000",
                                    "9.000", "0.000", "0", "1"]
    small = next(line for line in lines if line.startswith("small"))
    assert small.split()[7:] == ["1.500", "1", "0"]     # what the hit saved
    cut_lines = ledger.table(until=cut, top=1).splitlines()
    assert len(cut_lines) == 3 and cut_lines[1].startswith("large")
    assert cut_lines[-1].startswith("all 2 names")


def test_retrace_guard_trips_at_budget_plus_one():
    @jax.jit
    def ledger_probe_guarded(x):
        return x - 5

    with retrace.RetraceGuard(budget=1) as within:
        ledger_probe_guarded(np.ones(21, np.int32))
    assert within.compiles == 1 and not within.exceeded
    with pytest.raises(retrace.RetraceBudgetExceeded, match="2 XLA"):
        with retrace.RetraceGuard(budget=1):
            ledger_probe_guarded(np.ones(22, np.int32))
            ledger_probe_guarded(np.ones(23, np.int32))
    assert within.compiles == 1         # closed: what it saw, for good


def test_metrics_text_carries_the_compile_series():
    jax.jit(lambda x: x * 7)(jnp.ones((19,)))
    text = observability.prometheus_text()
    assert "# TYPE oe_compile_seconds histogram" in text
    for phase in ("trace", "lower", "compile"):
        assert f'oe_compile_seconds_count{{phase="{phase}"}}' in text
    assert re.search(r'oe_compile_programs_total\{cache="off"\} \d', text)
    assert scope.HISTOGRAMS.counter("compile_programs") >= 1


def test_fit_logs_its_calls_with_the_steps_they_dispatched(devices8):
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh

    coll = EmbeddingCollection(
        deepctr.make_feature_specs(("f",), 64, 4),
        create_mesh(2, 4, devices8),
        default_optimizer={"category": "sgd", "learning_rate": 0.1})
    trainer = Trainer(deepctr.LogisticRegression(feature_names=("f",)),
                      coll, optax.sgd(1e-2))
    rng = np.random.RandomState(0)

    def batches(n):
        for _ in range(n):
            ids = rng.randint(0, 64, 16).astype(np.int32)
            yield {"label": (ids % 2).astype(np.float32), "dense": None,
                   "sparse": {"f": ids, "f:linear": ids}}

    first = next(batches(1))
    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(first))
    logged = len(retrace.LEDGER.fit_calls)
    t0 = time.perf_counter()
    state, _ = trainer.fit(state, batches(3))
    programs = retrace.LEDGER.programs
    state, _ = trainer.fit(state, batches(5))
    three, five = list(retrace.LEDGER.fit_calls)[logged:]
    assert (three.steps, five.steps) == (3, 5)
    assert t0 <= three.start < three.end <= five.start < five.end
    # the first call built the step; the second began with it built
    assert three.totals["programs"] < five.totals["programs"] == programs

    def broken():
        yield from batches(2)
        raise KeyError("the source broke")

    with pytest.raises(KeyError):
        trainer.fit(state, broken())
    failed = retrace.LEDGER.fit_calls[-1]
    assert failed.end is not None and failed.steps <= 2


def test_setup_table_lays_the_ledger_beside_the_runners_marks():
    from tools import setup_table
    ledger = retrace.LoadLedger()
    t0 = time.perf_counter()
    _program(ledger, "fill", "hit")
    mark_tables = time.perf_counter() - t0
    time.sleep(GAP_S)
    _program(ledger, "step_fn", "miss")
    window = ledger.fit_began()
    time.sleep(GAP_S)
    _program(ledger, "late", "miss")
    marks = [{"set_up": "tables", "at_s": mark_tables},
             {"set_up": "after_window", "at_s": 3600.0}]
    rows = setup_table.phases(ledger, marks, t0, window.start)
    assert [name for name, _, _ in rows] == ["tables", "to_window_call"]
    (_, wall0, fill), (_, wall1, step) = rows
    assert wall0 == pytest.approx(mark_tables)
    assert wall0 + wall1 == pytest.approx(window.start - t0)
    assert (fill["programs"], fill["hits"]) == (1, 1)
    assert fill["fetch_s"] == 3e-4 and fill["compile_s"] == 0.0
    assert (step["programs"], step["hits"]) == (1, 0)
    assert step["compile_s"] == 3e-4 and step["trace_lower_s"] > 2e-4
