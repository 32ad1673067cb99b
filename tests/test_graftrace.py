"""graftrace static plane: every seeded violation fires, the package is
clean, and each rule's semantic edges hold on minimal sources.

The fixture (tests/fixtures/graftrace_violations.py) marks each intended
violation with ``# expect: JGxxx``; the analyzer must report EXACTLY
that set — nothing missed (rules work), nothing extra (sanctioned
patterns: guarded accesses, consistent lock order, joined non-daemon
workers, inline suppressions). The runtime detector and the
interleaving harness have their own lanes (test_traced_locks.py,
test_interleaving.py).
"""

import os
import re

from openembedding_tpu.analysis import concurrency

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "fixtures", "graftrace_violations.py")


def _expected(source):
    out = set()
    for i, line in enumerate(source.splitlines(), start=1):
        for rule in re.findall(r"# expect: (JG\d+)", line):
            out.add((i, rule))
    return out


def test_every_seeded_violation_fires():
    with open(FIXTURE) as fh:
        src = fh.read()
    expected = _expected(src)
    # JG100 (parse failure) cannot live in a parseable fixture; it has
    # its own unit test below
    assert {r for _ln, r in expected} == set(concurrency.RULES) - {"JG100"}
    got = {(v.line, v.rule) for v in concurrency.trace_source(src, FIXTURE)}
    assert got == expected, (
        f"missed: {expected - got}; spurious: {got - expected}")


def test_shipped_package_is_clean():
    """The CI gate, enforced from inside the suite as well: zero
    lock-discipline violations in openembedding_tpu/."""
    pkg = os.path.join(ROOT, "openembedding_tpu")
    violations = concurrency.trace_paths([pkg])
    assert violations == [], "\n".join(str(v) for v in violations)


def test_cli_exit_codes():
    from tools.graftrace import main
    assert main([os.path.join(ROOT, "openembedding_tpu")]) == 0
    assert main([FIXTURE]) == 1
    assert main([FIXTURE, "--rules", "JG102"]) == 1


def test_parse_failure_is_jg100_and_unfilterable(tmp_path):
    got = concurrency.trace_source("def broken(:\n", "bad.py")
    assert [v.rule for v in got] == ["JG100"]
    # inconsistent dedent raises IndentationError (a SyntaxError, NOT a
    # TokenError) out of tokenize inside the suppression scan — must
    # still land as JG100, not a traceback
    bad_indent = "def f():\n        x = 1\n    y = 2\n"
    got = concurrency.trace_source(bad_indent, "bad.py")
    assert [v.rule for v in got] == ["JG100"]
    from tools.graftrace import main
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    assert main([str(bad), "--rules", "JG104"]) == 1


# --- JG101 semantics ---------------------------------------------------------

_RACY = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def _run(self):
        self.count += 1

    def start(self):
        threading.Thread(target=self._run).start()

    def read(self):
        with self._lock:
            return self.count
"""


def test_jg101_fires_on_lockfree_write_in_thread():
    got = concurrency.trace_source(_RACY)
    assert [v.rule for v in got] == ["JG101"]
    assert "self.count" in got[0].message


def test_jg101_needs_a_thread_spawn():
    # same lockset inconsistency, but the class spawns nothing: callers'
    # threads are invisible to the static pass (the runtime plane's job)
    src = _RACY.replace("threading.Thread(target=self._run).start()",
                        "self._run()")
    assert concurrency.trace_source(src) == []


def test_jg101_spares_join_protocol_fields():
    # a field NEVER locked anywhere has no lockset discipline to violate
    # (offload's host store: guarded by thread joins, not locks)
    src = _RACY.replace("with self._lock:\n            return self.count",
                        "return self.count")
    assert concurrency.trace_source(src) == []


def test_jg101_no_common_lock():
    src = """
import threading

class C:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()
        self.n = 0

    def _run(self):
        with self._a:
            self.n += 1

    def start(self):
        threading.Thread(target=self._run).start()

    def read(self):
        with self._b:
            return self.n
"""
    got = concurrency.trace_source(src)
    assert [v.rule for v in got] == ["JG101"]
    assert "no COMMON lock" in got[0].message


def test_jg101_interprocedural_entry_held():
    # a method invoked ONLY from inside `with self._lock:` blocks is
    # analyzed with the lock held — the offload._evict pattern
    src = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def _bump(self):
        self.n += 1

    def _run(self):
        with self._lock:
            self._bump()

    def start(self):
        threading.Thread(target=self._run).start()

    def read(self):
        with self._lock:
            return self.n
"""
    assert concurrency.trace_source(src) == []


# --- JG102 / JG103 semantics -------------------------------------------------

def test_jg102_consistent_order_is_clean():
    src = """
import threading
A = threading.Lock()
B = threading.Lock()

def f():
    with A:
        with B:
            pass

def g():
    with A:
        with B:
            pass
"""
    assert concurrency.trace_source(src) == []
    bad = src.replace("def g():\n    with A:\n        with B:",
                      "def g():\n    with B:\n        with A:")
    got = concurrency.trace_source(bad)
    assert {v.rule for v in got} == {"JG102"}


def test_jg103_condition_wait_is_sanctioned():
    # Condition.wait RELEASES its lock while blocked — the one sanctioned
    # block-under-lock pattern (SerialSchedule uses it)
    src = """
import threading

class C:
    def __init__(self):
        self._cv = threading.Condition()

    def waiter(self):
        with self._cv:
            self._cv.wait(1.0)
"""
    assert concurrency.trace_source(src) == []


def test_jg103_thread_join_under_lock():
    src = """
import threading
LOCK = threading.Lock()

class C:
    def __init__(self):
        self._t = threading.Thread(target=print)

    def stop(self):
        with LOCK:
            self._t.join()
"""
    got = concurrency.trace_source(src)
    assert [v.rule for v in got] == ["JG103"]


def test_jg103_str_join_is_not_blocking():
    src = """
import threading
LOCK = threading.Lock()

def render(parts):
    with LOCK:
        return ", ".join(parts)
"""
    assert concurrency.trace_source(src) == []


# --- JG104 semantics / suppression -------------------------------------------

def test_jg104_joined_daemon_is_clean():
    src = """
import threading

class C:
    def __init__(self):
        self._t = threading.Thread(target=print, daemon=True)

    def close(self):
        self._t.join(5)
"""
    assert concurrency.trace_source(src) == []


def test_suppression_scopes():
    src = """
import threading
LOCK = threading.Lock()
import time

def f():
    with LOCK:
        time.sleep(1)  # graftrace: disable=JG103

def g():  # graftrace: disable
    with LOCK:
        time.sleep(1)

def h():
    with LOCK:
        time.sleep(1)
"""
    got = concurrency.trace_source(src)
    assert [(v.rule, v.line) for v in got] == [("JG103", 16)]


def test_suppression_rule_list_fails_closed():
    base = ("import threading\n"
            "LOCK = threading.Lock()\n"
            "import time\n"
            "def f():\n"
            "    with LOCK:\n"
            "        time.sleep(1)  # graftrace: disable={}\n")
    # lowercase rule names normalize (suppressed)
    assert concurrency.trace_source(base.format("jg103")) == []
    # a typo'd/unknown rule list must NOT widen into suppress-all:
    # the violation still fires and CI points at the bad comment
    for junk in ("jg1o3", "garbage", "", "JG103 because reasons"):
        got = concurrency.trace_source(base.format(junk))
        assert [v.rule for v in got] == ["JG103"], junk


# --- thread-spawning inventory ----------------------------------------------

def _package_inventory():
    """(relpath, class, lock fields) for every thread-spawning class,
    plus every thread name literal, straight from the analyzer index."""
    import ast

    pkg = os.path.join(ROOT, "openembedding_tpu")
    classes = {}
    names = set()
    for root, _dirs, files in os.walk(pkg):
        if "__pycache__" in root:
            continue
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            rel = os.path.relpath(path, ROOT)
            a = concurrency.Analyzer(path, src)
            a._index(ast.parse(src))
            for cls in a.classes:
                if cls.spawns_thread:
                    classes[(rel, cls.name)] = tuple(
                        sorted(cls.lock_fields))
            for n in ast.walk(ast.parse(src)):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr == "Thread":
                    for kw in n.keywords:
                        if kw.arg != "name":
                            continue
                        v = kw.value
                        if isinstance(v, ast.Constant):
                            names.add(v.value)
                        elif isinstance(v, ast.JoinedStr):
                            names.add("".join(
                                p.value if isinstance(p, ast.Constant)
                                else "*" for p in v.values))
    return classes, names


def test_thread_spawning_inventory_is_pinned():
    """Every class that spawns a thread is visible to the lockset audit
    (JG101's thread-reachability is keyed off this index) and carries
    the lock fields the audit reasons over. Pins in particular the two
    post-audit arrivals: the ``AdaptiveBatchTuner`` sampler (PR 17,
    ``_lock``-guarded decision rounds) and the chaos-armed checkpoint
    writer/compactor threads (PR 16 — module-function spawns, so they
    appear as named threads, not classes). A NEW spawn site failing
    this test is the point: extend the pin AND the lockset audit."""
    classes, names = _package_inventory()
    assert classes == {
        ("openembedding_tpu/data/stream.py", "ShardStream"): ("_cv",),
        ("openembedding_tpu/offload.py", "ShardedOffloadedTable"):
            ("_book",),
        ("openembedding_tpu/serving/batcher.py", "AdaptiveBatchTuner"):
            ("_lock",),
        ("openembedding_tpu/serving/batcher.py", "LookupBatcher"):
            ("_cv",),
        ("openembedding_tpu/serving/registry.py", "ModelRegistry"):
            ("_lock",),
        ("openembedding_tpu/serving/rest.py", "ControllerServer"): (),
        ("openembedding_tpu/training.py", "Trainer"): (),
        # PR 32: one autosave's writer thread; its ``err`` is written by
        # the thread and read after ``join``, so it needs no lock
        ("openembedding_tpu/training.py", "_BackgroundSave"): (),
        ("openembedding_tpu/utils/observability.py", "Reporter"):
            ("_lock",),
    }
    # every thread in the package is named (chaos pins faults to
    # thread-name patterns; an anonymous thread is untargetable)
    assert names == {
        "oe-ckpt-writer-*", "oe-ckpt-compact", "oe-ckpt-autosave",
        "oe-writeback-*",
        "oe-persist-*", "oe-prep", "oe-ingest-*", "oe-batcher-*",
        "oe-plan-*", "oe-model-load-*", "oe-rest-*", "oe-reporter",
    }
