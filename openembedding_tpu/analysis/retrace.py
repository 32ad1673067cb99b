"""The load ledger and the retrace guard: what JAX built, and a budget on it.

**The load ledger.** The process's one ``jax.monitoring`` registration
(:func:`install`, run when the package is imported) records every program
JAX builds: its name, the seconds it was traced, lowered and handed to
the backend, whether the persistent compile cache held it, and where all
that lies on ``time.perf_counter()``, the clock ``scope.Span`` stamps.
:data:`LEDGER` keeps the newest entries and exact totals; the same
samples feed ``scope.HISTOGRAMS`` (``compile_seconds{phase=}``,
``compile_programs{cache=}``), so the serving ``GET /metrics`` shows them.
``print(LEDGER.table())`` is what an operator reads after a run: where
set-up went, by program. ``Trainer.fit`` notes each of its calls beside
it (:meth:`LoadLedger.fit_began`), so the ledger can be cut at a call.

What the events are, in JAX 0.9.0. ``jaxpr_trace_duration`` fires once a
function is traced, inner ``jit`` s included and before the outer one
closes; it names the function (``step_fn``).
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration`` name
the module (``jit(step_fn)``). The backend event wraps
``compile_or_get_cached``: it fires for a program XLA compiled AND for
one fetched from the persistent cache, and the cache's own events
(``cache_hits``, ``cache_misses``, ``cache_retrieval_time_sec``,
``compile_time_saved_sec``) carry no name and fire inside it, on its
thread, before it closes. So a program's events are joined by their
order on a thread: a lowering takes the trace that closed last before it
(a trace that lies inside another trace, or inside a lowering, is an inner
function's and is dropped for it), a
backend event the lowering of its module, and the cache's events belong
to the backend event that closes next. A program that is in JAX's
in-memory caches fires nothing at all.

**The retrace guard.** A steady-state training loop should build
NOTHING: every step reuses the jitted step program, every pull/push
program is cached by its static config. A recompile per step — a shape
wobble from an unpadded last batch, a Python value smuggled into a traced
signature, an lru_cache key that includes a per-step object — silently
turns a ~ms step into a ~second step. :class:`RetraceGuard` counts the
ledger's backend events over a scope (compiled or fetched: either way the
loop stopped to load a program) and fails when they exceed the declared
budget. Wired into :meth:`Trainer.fit` (``retrace_budget=``) and the
deepctr example (``--retrace_budget``).

Usage::

    with RetraceGuard(budget=0, name="steady-state loop"):
        for batch in batches:
            state, metrics = trainer.train_step(state, batch)

Nesting is supported; each guard counts every backend event that happens
while it is open (an inner guard's are also the outer one's).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax

from . import scope

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_DURATIONS = frozenset((_TRACE, _LOWER, _BACKEND, _FETCH, _SAVED))

KEEP = 4096             # entries (and calls of fit) a ledger keeps
_PENDING = 4096         # traces, or lowerings, a thread may leave unjoined
_SLACK_S = 1e-3         # JAX times on time.time(), the ledger on perf_counter


@dataclasses.dataclass
class Entry:
    """One program. ``start``/``end`` are on ``time.perf_counter()``;
    ``backend_s`` and ``cache`` stay None until the backend event (a
    program lowered and never compiled keeps them so). ``cache`` is
    ``"hit"``, ``"miss"`` (compiled, then written) or ``"off"`` (compiled,
    and the persistent cache was not asked or kept nothing). Of a hit's
    ``backend_s``, ``fetch_s`` is what the cache says reading the
    executable took (the rest deserialises and loads it), and ``saved_s``
    what it says the fetch saved: the compile that wrote the entry, less
    the reading. :meth:`LoadLedger.table` prints both."""
    name: str
    start: float
    end: float
    trace_s: float = 0.0
    lower_s: float = 0.0
    backend_s: Optional[float] = None
    cache: Optional[str] = None
    fetch_s: float = 0.0
    saved_s: float = 0.0


@dataclasses.dataclass
class FitCall:
    """One call of ``Trainer.fit``: entry and return on
    ``time.perf_counter()`` (``end`` None while it runs), the steps it
    dispatched, and the ledger's totals as they stood at entry."""
    start: float
    totals: Dict[str, float]
    end: Optional[float] = None
    steps: int = 0


class _Thread:
    """What one thread has fired and nothing has claimed yet."""
    __slots__ = ("traces", "lowered", "cache")

    def __init__(self):
        self.traces: List[Entry] = []
        self.lowered: Dict[str, Entry] = {}
        self.cache: Dict[str, float] = {}


def _new_totals() -> Dict[str, float]:
    return {"programs": 0, "hits": 0, "misses": 0, "off": 0,
            "trace_s": 0.0, "lower_s": 0.0, "fetch_s": 0.0,
            "compile_s": 0.0, "saved_s": 0.0}


class LoadLedger:
    """Entries in order (the newest ``keep``), exact totals, fit's calls.

    ``totals``: ``programs`` counts backend events, split into ``hits``,
    ``misses`` and ``off``; ``fetch_s`` sums the backend seconds of the
    hits, ``compile_s`` those of the rest, ``saved_s`` what the cache
    says the hits saved; ``trace_s`` sums every
    outermost trace, lowered after or not (``jax.eval_shape`` traces and
    stops), ``lower_s`` every lowering. A small program JAX builds while
    it traces a large one counts in both."""

    def __init__(self, keep: int = KEEP):
        self._lock = threading.Lock()
        self._threads: Dict[int, _Thread] = {}
        self._totals = _new_totals()
        self.entries: Deque[Entry] = deque(maxlen=keep)
        self.fit_calls: Deque[FitCall] = deque(maxlen=keep)
        self.installed_at: Optional[float] = None   # perf_counter
        self.import_s: Optional[float] = None       # OS process start to it

    # -- the listeners' side ------------------------------------------------

    def on_duration(self, event: str, secs: float, **kw) -> None:
        """A ``jax.monitoring`` duration event, on the thread it fired."""
        if event not in _DURATIONS:
            return
        now, tid = time.perf_counter(), threading.get_ident()
        done = None
        with self._lock:
            mine = self._threads.setdefault(tid, _Thread())
            if event == _TRACE:
                self._traced(mine, kw.get("fun_name", "?"), now, secs)
            elif event == _LOWER:
                self._lowered(mine, kw.get("fun_name", "?"), now, secs)
            elif event == _BACKEND:
                done = self._loaded(mine, kw.get("fun_name", "?"), tid, now,
                                    secs)
            elif event == _FETCH:
                mine.cache["fetch_s"] = secs
            elif event == _SAVED:
                mine.cache["saved_s"] = secs
        if done is not None:            # the registry has a lock of its own
            for phase, s in (("trace", done.trace_s), ("lower", done.lower_s)):
                if s:
                    scope.HISTOGRAMS.observe("compile_seconds", s, phase=phase)
            scope.HISTOGRAMS.observe(
                "compile_seconds", done.backend_s,
                phase="fetch" if done.cache == "hit" else "compile")
            scope.HISTOGRAMS.inc("compile_programs", cache=done.cache)

    def on_event(self, event: str, **_kw) -> None:
        """A plain ``jax.monitoring`` event: the cache's hit or miss."""
        if event != _HIT and event != _MISS:
            return
        tid = threading.get_ident()
        with self._lock:
            mine = self._threads.setdefault(tid, _Thread())
            mine.cache["hit" if event == _HIT else "miss"] = 1

    def _traced(self, mine, name, now, secs):
        start = now - secs
        # traces that closed inside this one are its inner functions':
        # their seconds are part of its own
        while mine.traces and mine.traces[-1].start >= start - _SLACK_S:
            self._totals["trace_s"] -= mine.traces.pop().trace_s
        if len(mine.traces) >= _PENDING:
            del mine.traces[:_PENDING // 2]
        mine.traces.append(Entry(name, start, now, trace_s=secs))
        self._totals["trace_s"] += secs

    def _lowered(self, mine, module, now, secs):
        start = now - secs
        entry = None
        while mine.traces and entry is None:
            last = mine.traces[-1]
            if last.end <= start + _SLACK_S and last.name in module:
                entry = mine.traces.pop()       # this program's own trace
                entry.end, entry.lower_s = now, secs
            elif last.start >= start - _SLACK_S:
                # traced by a lowering rule: part of the lowering's seconds
                self._totals["trace_s"] -= mine.traces.pop().trace_s
            else:
                break                           # an older trace, not ours
        if entry is None:   # traced long ago: only the lowering is new
            entry = Entry(_function(module), start, now, lower_s=secs)
        if len(mine.lowered) >= _PENDING:
            mine.lowered.clear()
        mine.lowered[module] = entry
        self.entries.append(entry)
        self._totals["lower_s"] += secs

    def _loaded(self, mine, module, tid, now, secs):
        entry = mine.lowered.pop(module, None)
        if entry is None:
            entry = Entry(_function(module), now - secs, now)
            self.entries.append(entry)
        took, mine.cache = mine.cache, {}
        if not mine.traces and not mine.lowered:
            del self._threads[tid]      # thread ids come and go
        entry.end, entry.backend_s = now, secs
        entry.fetch_s = took.get("fetch_s", 0.0)
        entry.saved_s = took.get("saved_s", 0.0)
        entry.cache = "hit" if "hit" in took else \
            "miss" if "miss" in took else "off"
        totals = self._totals
        totals["programs"] += 1
        totals["saved_s"] += entry.saved_s
        if entry.cache == "hit":
            totals["hits"] += 1
            totals["fetch_s"] += secs
        else:
            totals["misses" if entry.cache == "miss" else "off"] += 1
            totals["compile_s"] += secs
        return entry

    # -- the readers' side --------------------------------------------------

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    @property
    def programs(self) -> int:
        """Backend events so far: programs compiled or fetched."""
        return self._totals["programs"]

    def fit_began(self) -> FitCall:
        call = FitCall(time.perf_counter(), self.totals())
        self.fit_calls.append(call)
        return call

    def fit_returned(self, call: FitCall) -> None:
        call.end = time.perf_counter()

    def table(self, until: Optional[float] = None,
              top: Optional[int] = None) -> str:
        """Programs by name, the costliest first: calls, seconds by phase,
        hits and misses. ``fetch_s`` is the backend seconds of the hits,
        ``read_s`` the part of it the cache spent reading them, ``saved_s``
        what the cache says they saved: a warm run's estimate of the same
        run cold. ``until`` (``perf_counter``) leaves out what ended
        later, ``top`` all but the first rows. The last line sums the
        entries shown or cut, which are the newest ``keep``."""
        with self._lock:
            entries = [dataclasses.replace(e) for e in self.entries]
        rows: Dict[str, List[float]] = {}
        for e in entries:
            if until is not None and e.end > until:
                continue
            row = rows.setdefault(e.name, [0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0,
                                           0, 0])
            row[0] += 1
            row[1] += e.trace_s
            row[2] += e.lower_s
            if e.cache == "hit":
                row[3] += e.backend_s
                row[4] += e.fetch_s
                row[6] += e.saved_s
                row[7] += 1
            elif e.cache is not None:
                row[5] += e.backend_s
                row[8] += 1
        order = sorted(rows, key=lambda n: -sum(
            rows[n][i] for i in (1, 2, 3, 5)))
        total = [sum(r[i] for r in rows.values()) for i in range(9)]
        head = f"{'program':<40} {'calls':>5} {'trace_s':>8} {'lower_s':>8} " \
               f"{'fetch_s':>8} {'read_s':>8} {'compile_s':>9} " \
               f"{'saved_s':>8} {'hit':>4} {'miss':>4}"

        def line(name, r):
            return f"{name[:40]:<40} {r[0]:>5} {r[1]:>8.3f} {r[2]:>8.3f} " \
                   f"{r[3]:>8.3f} {r[4]:>8.3f} {r[5]:>9.3f} {r[6]:>8.3f} " \
                   f"{r[7]:>4} {r[8]:>4}"

        shown = order if top is None else order[:top]
        return "\n".join([head] + [line(n, rows[n]) for n in shown]
                         + [line(f"all {len(order)} names", total)])


def _function(module: str) -> str:
    """``jit(step_fn)`` -> ``step_fn``: the name the trace event gives."""
    inner = module.partition("(")[2]
    return inner[:-1] if inner.endswith(")") else module


def _since_process_start() -> Optional[float]:
    """Seconds since the operating system started this process: its start
    time in ``/proc/self/stat`` (field 22, clock ticks after boot) against
    the boot clock. None where the platform has neither."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


LEDGER = LoadLedger()
_install_lock = threading.Lock()


def install() -> LoadLedger:
    """Register :data:`LEDGER` with ``jax.monitoring``, once a process
    (``jax.monitoring`` has no public unregister), and note how long the
    process took to get here: interpreter start, ``import jax``, the
    package's imports. The package's import calls this, so the ledger is
    in force before any entry point's first compile."""
    with _install_lock:
        if LEDGER.installed_at is None:
            jax.monitoring.register_event_duration_secs_listener(
                LEDGER.on_duration)
            jax.monitoring.register_event_listener(LEDGER.on_event)
            LEDGER.import_s = _since_process_start()
            LEDGER.installed_at = time.perf_counter()
    return LEDGER


class RetraceBudgetExceeded(RuntimeError):
    """More programs were built inside the guard than budgeted."""


class RetraceGuard:
    """Context manager failing when the programs XLA compiled, or fetched
    from the persistent cache, inside it exceed ``budget``.

    ``budget`` is the number ALLOWED inside the scope (0 = a steady-state
    loop that must build nothing). ``on_exceed``: ``"raise"`` (default)
    raises :class:`RetraceBudgetExceeded` on exit; ``"warn"`` prints one
    warning and continues — the mode the example wires in so a budget trip
    shows up in CI logs without killing a run mid-epoch.
    """

    def __init__(self, budget: int = 0, *, name: str = "",
                 on_exceed: str = "raise"):
        if on_exceed not in ("raise", "warn"):
            raise ValueError(f"on_exceed must be 'raise' or 'warn', "
                             f"got {on_exceed!r}")
        self.budget = int(budget)
        self.name = name
        self.on_exceed = on_exceed
        self._from: Optional[int] = None    # the ledger's count at entry
        self._compiles = 0

    @property
    def compiles(self) -> int:
        """Programs compiled or fetched so far inside this guard."""
        if self._from is not None:
            return LEDGER.programs - self._from
        return self._compiles

    @property
    def exceeded(self) -> bool:
        return self.compiles > self.budget

    def __enter__(self) -> "RetraceGuard":
        if self._from is not None:
            raise RuntimeError("RetraceGuard is not reentrant; create a "
                               "new guard per scope")
        self._from = LEDGER.programs
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._compiles, self._from = self.compiles, None
        if exc_type is not None:
            return False            # the original error is the story
        if self.exceeded:
            label = f" [{self.name}]" if self.name else ""
            msg = (f"retrace budget exceeded{label}: {self._compiles} "
                   f"XLA compilation(s) > budget {self.budget} — "
                   "something in the loop retraces per step (shape "
                   "wobble, Python value in a traced signature, or a "
                   "program-cache key churning)")
            if self.on_exceed == "raise":
                raise RetraceBudgetExceeded(msg)
            import warnings
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return False


def compile_count(fn, *args, **kwargs) -> int:
    """Run ``fn(*args, **kwargs)`` and return how many programs it made
    XLA compile or fetch (a measurement helper for tests and
    diagnostics)."""
    with RetraceGuard(budget=1 << 30) as g:
        fn(*args, **kwargs)
        n = g.compiles
    return n


def assert_no_recompiles(fn, *args, warmup: int = 1, **kwargs) -> None:
    """Call ``fn`` ``warmup`` times, then once more under a zero-budget
    guard: the steady-state invocation must be compile-free."""
    for _ in range(max(0, warmup)):
        fn(*args, **kwargs)
    with RetraceGuard(budget=0, name=getattr(fn, "__name__", "fn")):
        fn(*args, **kwargs)
