"""graftscope: span tracing, latency histograms, and the byte ledger.

The reference dedicates a whole plane to performance accounting
(``evaluate_performance``, per-op pull/push timing, the TF-Serving
metrics exporter — SURVEY §5.1); our observability plane was flat
counter sums plus a per-plane wall-time split. This module is the
measurement substrate underneath it, in three parts:

**1. Span API** — ``with span("pull", plane="a2a"): ...`` records one
timed interval into a lock-free-per-thread ring buffer (each thread
appends only to its own ring; a registry lock is taken once per thread,
at ring creation) and into the histogram registry. Spans are
``under_trace``-guarded: a span opened while JAX is tracing records the
event once, tagged ``trace_time`` (the body runs per COMPILE there, and
a trace-time duration must never pollute the per-step latency
histograms). When a ``jax.profiler`` trace is active the span also
enters a ``TraceAnnotation`` (``step_span`` a ``StepTraceAnnotation``),
so host spans nest inside device profiles. ``export_chrome_trace``
writes the rings as Chrome-trace/Perfetto JSON (open in
https://ui.perfetto.dev or ``chrome://tracing``).

**2. Histogram metrics** — fixed log-spaced buckets
(:data:`BUCKET_BOUNDS`, 4 per decade over 1e-7..1e8) shared by every
series, with p50/p95/p99 estimates by geometric interpolation inside
the hit bucket (error bounded by one bucket ratio,
:data:`BUCKET_RATIO`). Every closed span feeds
``span_<kind>_seconds``; ``utils/observability.record_batch_stats``
feeds the per-table ``pull_rows`` / ``pull_unique_ratio`` /
``pull_key_skew`` distributions. ``prometheus_lines()`` renders proper
``_bucket``/``_sum``/``_count`` series — surfaced on the serving
``GET /metrics`` endpoint through ``observability.prometheus_text``.

**3. Expected-vs-measured byte ledger** — reuse the
:mod:`.programs` lowering + :mod:`.contracts` HLO cost analysis to
compute each plane's per-step expected collective bytes (the same
numbers the contracts bound), pair them with the measured pull/push
span quantiles, and report achieved GB/s per exchange.
``python -m tools.graftscope`` drives an N-step capture and prints the
per-plane/per-stage table.

Import discipline: stdlib + :mod:`.concurrency` only at module level;
jax is looked up lazily (and only if something else already imported
it), so the graftlint/graftrace CLIs and host-only callers never pay
for it.
"""

from __future__ import annotations

import bisect
import contextlib
import contextvars
import dataclasses
import json
import os
import re
import sys
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Any, Dict, List, Mapping, Optional, Tuple

from .concurrency import make_lock

# ---------------------------------------------------------------------------
# enablement
# ---------------------------------------------------------------------------

_TRACE_ENV = "OE_SCOPE_TRACE"
_tracing_forced: Optional[bool] = None


def set_tracing(on: Optional[bool]) -> None:
    """Force span-ring recording on/off; ``None`` restores the
    environment default (``OE_SCOPE_TRACE``). Histograms are always fed
    (they are aggregate metrics, one bucket bump per span); only the
    per-event ring buffers are gated."""
    global _tracing_forced
    _tracing_forced = on


def tracing_enabled() -> bool:
    if _tracing_forced is not None:
        return _tracing_forced
    return os.environ.get(_TRACE_ENV, "").lower() in ("1", "true", "yes",
                                                      "on")


def _trace_state_clean() -> bool:
    """False while JAX is tracing (the span is running at trace time,
    once per compile — not once per step). True when jax was never even
    imported: host-only processes cannot be under a trace."""
    if "jax" not in sys.modules:
        return True
    # no public spelling in jax 0.9.0; if this one moves too the import
    # raises — a wrong "clean" would feed trace-time spans into the
    # latency histograms
    from jax._src import core
    return core.trace_state_clean()


def _profiler():
    """``jax.profiler`` iff jax is already imported, else None — the
    TraceAnnotation pass-through must never be the thing that drags jax
    into a host-only process."""
    jax = sys.modules.get("jax")
    return getattr(jax, "profiler", None) if jax is not None else None


# ---------------------------------------------------------------------------
# request-scoped trace ids
# ---------------------------------------------------------------------------

# the active trace/request id: set by the serving clients at request
# entry and by the REST handlers from the X-OE-Trace header, read by
# record_span so every span closed on the request path carries the same
# ``trace`` arg in the exported Perfetto trace. A contextvar (not a
# bare thread-local) so async frameworks hosting the client still
# scope it per task; plain threads each start with the default (None).
_TRACE_ID: "contextvars.ContextVar[Optional[str]]" = \
    contextvars.ContextVar("oe_trace_id", default=None)

# trace ids are for stitching, not identity — 16 hex chars keep trace
# args short while collisions stay vanishingly rare per capture window
TRACE_ID_CHARS = 16


def new_trace_id() -> str:
    return uuid.uuid4().hex[:TRACE_ID_CHARS]


def current_trace_id() -> Optional[str]:
    """The trace id of the enclosing :func:`trace_context`, or None."""
    return _TRACE_ID.get()


@contextlib.contextmanager
def trace_context(trace_id: Optional[str] = None):
    """Scope a trace/request id: spans recorded inside carry it as the
    ``trace`` arg in the exported trace, so one request's client span,
    router fan-out spans, and server-side lookup spans stitch into one
    story. With no argument, the ENCLOSING id is reused if one is
    active (a sharded fan-out keeps its parent's id) and a fresh id is
    minted otherwise. Propagate across processes via the ``X-OE-Trace``
    HTTP header (serving/rest.py reads it back into this context)."""
    tid = str(trace_id) if trace_id else (_TRACE_ID.get() or new_trace_id())
    token = _TRACE_ID.set(tid)
    try:
        yield tid
    finally:
        _TRACE_ID.reset(token)


# ---------------------------------------------------------------------------
# per-thread span rings
# ---------------------------------------------------------------------------

RING_CAPACITY = 65536

# module-level time origin: every ring's timestamps share it, so the
# exported trace is cross-thread consistent
_EPOCH = time.perf_counter()

_REG_LOCK = make_lock("scope.rings")
_RINGS: List["_Ring"] = []
# events of rings whose owner thread has exited (the Trainer's per-batch
# lookahead threads, HTTP handler threads): their spans must survive
# into the export, but the ring OBJECTS must not accumulate forever —
# dead rings are folded into this bounded deque as (tid, name, event)
_RETIRED: "deque" = deque(maxlen=RING_CAPACITY)
_retired_total = 0       # ever retired — minus len(_RETIRED) = dropped
_TLS = threading.local()


class _Ring:
    """One thread's span events; only the owner thread appends (GIL
    makes the single-slot writes safe to snapshot from the exporter)."""

    __slots__ = ("buf", "n", "tid", "name", "owner")

    def __init__(self, owner: threading.Thread):
        self.buf: List[tuple] = []
        self.n = 0          # total appended (>= len(buf) once wrapped)
        self.tid = owner.ident or 0
        self.name = owner.name
        self.owner = weakref.ref(owner)

    def append(self, ev: tuple) -> None:
        # operate on a LOCAL snapshot of the buffer: a concurrent
        # reset() swaps self.buf out, and a check-then-index against the
        # live attribute could hit the freshly emptied list (a metrics
        # reset must never raise out of instrumented production code —
        # a write into the swapped-out buffer is simply discarded)
        buf = self.buf
        if len(buf) < RING_CAPACITY:
            buf.append(ev)
        else:
            try:
                buf[self.n % RING_CAPACITY] = ev
            except IndexError:
                buf.append(ev)
        self.n += 1

    @property
    def dropped(self) -> int:
        return max(0, self.n - RING_CAPACITY)


def _retire_dead_locked() -> None:
    """Fold rings of exited threads into the bounded retired deque
    (caller holds ``_REG_LOCK``). A dead thread can never append again,
    so its buffer snapshot is final."""
    global _retired_total
    alive = []
    for ring in _RINGS:
        t = ring.owner()
        if t is not None and t.is_alive():
            alive.append(ring)
        else:
            for ev in list(ring.buf):
                _RETIRED.append((ring.tid, ring.name, ev))
                _retired_total += 1
    _RINGS[:] = alive


def _my_ring() -> _Ring:
    ring = getattr(_TLS, "ring", None)
    if ring is None:
        ring = _TLS.ring = _Ring(threading.current_thread())
        with _REG_LOCK:
            _retire_dead_locked()
            _RINGS.append(ring)
    return ring


def reset() -> None:
    """Drop every recorded span event (test isolation). Rings stay
    registered — live threads still hold their thread-locals."""
    global _retired_total
    with _REG_LOCK:
        for ring in _RINGS:
            ring.buf = []
            ring.n = 0
        _RETIRED.clear()
        _retired_total = 0


# nominal bytes per buffered span event (7-tuple + small label dict):
# an estimate for the memory gauges, not an exact accounting — the
# rings are bounded (RING_CAPACITY) so the estimate's error is too
EVENT_NOMINAL_BYTES = 160


def ring_stats() -> Dict[str, int]:
    """Live span-ring memory gauges for ``observability.memory_stats``:
    buffered event count (live rings + the retired deque), events
    dropped by ring wrap/retirement eviction, and the approximate bytes
    those buffers hold."""
    with _REG_LOCK:
        events = sum(len(r.buf) for r in _RINGS) + len(_RETIRED)
        dropped = sum(r.dropped for r in _RINGS) \
            + (_retired_total - len(_RETIRED))
    return {"events": events, "dropped": max(0, dropped),
            "approx_bytes": events * EVENT_NOMINAL_BYTES}


# ---------------------------------------------------------------------------
# histogram registry
# ---------------------------------------------------------------------------

# fixed log-spaced bounds shared by every histogram: 4 buckets per
# decade over [1e-7, 1e12] — microsecond spans, multi-minute checkpoint
# saves, and BYTE-valued series (grouped exchanges reach hundreds of MB
# at production scale; a 1e8 cap would saturate them into +Inf)
BUCKET_RATIO = 10.0 ** 0.25
BUCKET_BOUNDS: Tuple[float, ...] = tuple(10.0 ** (e / 4.0)
                                         for e in range(-28, 49))


class _Hist:
    __slots__ = ("counts", "sum", "count")

    def __init__(self):
        self.counts = [0] * (len(BUCKET_BOUNDS) + 1)   # +1: overflow
        self.sum = 0.0
        self.count = 0


def _labels_key(labels: Mapping[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(items: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(v)}"' for k, v in items]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class HistogramRegistry:
    """Named histograms + labeled counters over the shared bucket grid.

    Thread-safe via one registry lock (observations are a dict lookup +
    a bisect + three adds — nanoseconds next to the spans they measure).
    """

    def __init__(self):
        self._lock = make_lock("scope.metrics")
        self._hists: Dict[Tuple[str, tuple], _Hist] = {}
        self._counters: Dict[Tuple[str, tuple], float] = {}

    def observe(self, name: str, value: float, **labels) -> None:
        key = (name, _labels_key(labels))
        idx = bisect.bisect_left(BUCKET_BOUNDS, value)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = _Hist()
            h.counts[idx] += 1
            h.sum += value
            h.count += 1

    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        key = (name, _labels_key(labels))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def count(self, name: str, **labels) -> int:
        with self._lock:
            h = self._hists.get((name, _labels_key(labels)))
            return h.count if h is not None else 0

    def counter(self, name: str, **labels) -> float:
        """Current value of one labeled counter (0.0 when never bumped)
        — with no labels, the SUM across every label set of ``name``
        (the serving clients label connection/request counters by
        endpoint; callers usually want the fleet total)."""
        with self._lock:
            if labels:
                return self._counters.get((name, _labels_key(labels)), 0.0)
            return sum(v for (n, _l), v in self._counters.items()
                       if n == name)

    def sum(self, name: str, **labels) -> float:
        with self._lock:
            h = self._hists.get((name, _labels_key(labels)))
            return h.sum if h is not None else 0.0

    def quantile(self, name: str, q: float, **labels) -> float:
        """Quantile estimate by geometric interpolation inside the hit
        bucket — error bounded by one :data:`BUCKET_RATIO` factor. NaN
        when the series is empty or unknown."""
        with self._lock:
            h = self._hists.get((name, _labels_key(labels)))
            if h is None or h.count == 0:
                return float("nan")
            counts = list(h.counts)
            total = h.count
        target = max(1.0, q * total)
        cum = 0.0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= target:
                if i >= len(BUCKET_BOUNDS):      # overflow bucket
                    return BUCKET_BOUNDS[-1]
                hi = BUCKET_BOUNDS[i]
                lo = (BUCKET_BOUNDS[i - 1] if i > 0
                      else BUCKET_BOUNDS[0] / BUCKET_RATIO)
                frac = (target - cum) / c
                return lo * (hi / lo) ** frac
            cum += c
        return BUCKET_BOUNDS[-1]

    def series(self) -> List[Tuple[str, Dict[str, str]]]:
        with self._lock:
            return [(name, dict(labels))
                    for name, labels in sorted(self._hists)]

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()
            self._counters.clear()

    def prometheus_lines(self, prefix: str = "oe") -> List[str]:
        """Render every histogram as ``_bucket``/``_sum``/``_count``
        series and every counter as a ``_total``. Zero-count buckets are
        elided (the cumulative values present are complete information);
        the ``+Inf`` bucket is always emitted."""
        with self._lock:
            hists = {k: (list(h.counts), h.sum, h.count)
                     for k, h in self._hists.items()}
            counters = dict(self._counters)
        lines: List[str] = []
        last_name = None
        for (name, labels) in sorted(hists):
            counts, total_sum, total_count = hists[(name, labels)]
            base = f"{prefix}_{name}"
            if name != last_name:
                lines.append(f"# HELP {base} graftscope histogram "
                             f"`{name}` (log-spaced buckets)")
                lines.append(f"# TYPE {base} histogram")
                last_name = name
            cum = 0
            for i, c in enumerate(counts[:len(BUCKET_BOUNDS)]):
                if c == 0:
                    continue
                cum += c
                lab = _fmt_labels(labels,
                                  f'le="{BUCKET_BOUNDS[i]:.4g}"')
                lines.append(f"{base}_bucket{lab} {cum}")
            lab = _fmt_labels(labels, 'le="+Inf"')
            lines.append(f"{base}_bucket{lab} {total_count}")
            lab = _fmt_labels(labels)
            lines.append(f"{base}_sum{lab} {total_sum:.10g}")
            lines.append(f"{base}_count{lab} {total_count}")
        last_name = None
        for (name, labels) in sorted(counters):
            base = f"{prefix}_{name}_total"
            if name != last_name:
                lines.append(f"# HELP {base} graftscope counter "
                             f"`{name}`")
                lines.append(f"# TYPE {base} counter")
                last_name = name
            lines.append(f"{base}{_fmt_labels(labels)} "
                         f"{counters[(name, labels)]:.10g}")
        return lines


HISTOGRAMS = HistogramRegistry()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _hist_name(kind: str) -> str:
    return "span_" + re.sub(r"[^0-9A-Za-z]", "_", kind) + "_seconds"


def record_span(kind: str, t0: float, dt: float,
                labels: Optional[Mapping[str, Any]] = None, *,
                error: Optional[str] = None,
                trace_time: bool = False,
                detail: Optional[Mapping[str, Any]] = None) -> None:
    """Record one finished interval: histogram sample (skipped for
    trace-time spans — compile time is not step latency) + ring event
    when tracing is on. The direct entry point for callers that already
    timed the work themselves (``observability.plane_timed``)."""
    labels = labels or {}
    if not trace_time:
        HISTOGRAMS.observe(_hist_name(kind), dt, **labels)
        if error is not None:
            HISTOGRAMS.inc("span_errors", kind=kind, **labels)
    if tracing_enabled():
        det = dict(detail) if detail else None
        # the active request trace id rides in the trace args ONLY —
        # per-request ids in histogram labels would explode the registry
        tid = _TRACE_ID.get()
        if tid is not None and (det is None or "trace" not in det):
            det = dict(det or {})
            det["trace"] = tid
        _my_ring().append((kind, t0, dt, dict(labels) or None, error,
                           trace_time, det))


class Span:
    """Context manager for one timed interval (see :func:`span`)."""

    __slots__ = ("kind", "labels", "detail", "t0", "_ann", "_trace_time")

    def __init__(self, kind: str, labels: Optional[dict] = None,
                 detail: Optional[dict] = None,
                 annotation: Optional[Any] = None):
        self.kind = kind
        self.labels = labels
        self.detail = detail
        self._ann = annotation

    def set_label(self, key: str, value: Any) -> "Span":
        """Attach/overwrite one histogram label BEFORE the span closes
        (labels are read at exit) — how the HTTP handlers stamp the
        response status code onto the request span they run under."""
        if self.labels is None:
            self.labels = {}
        self.labels[str(key)] = value
        return self

    def __enter__(self) -> "Span":
        self._trace_time = not _trace_state_clean()
        if self._ann is not None:
            # best-effort like construction: a profiler-session failure
            # must never take down the instrumented production path
            try:
                self._ann.__enter__()
            except Exception:  # noqa: BLE001
                self._ann = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self.t0
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001 — the span record below
                pass           # must still land
        record_span(self.kind, self.t0, dt, self.labels,
                    error=exc_type.__name__ if exc_type else None,
                    trace_time=self._trace_time, detail=self.detail)
        return False


def span(kind: str, detail: Optional[Mapping[str, Any]] = None,
         **labels) -> Span:
    """Open a span: ``with span("pull", plane="a2a", table="user"): ...``

    ``labels`` become histogram labels AND trace args — keep them
    low-cardinality (plane, table, method). ``detail`` goes to the trace
    event only (signs, paths, step numbers), in the ring and as keywords
    of the profiler's ``TraceAnnotation``. Error exits are recorded
    with the exception type and re-raised. Under a JAX trace the event
    is recorded once, tagged ``trace_time``, and skips the histograms.
    """
    ann = None
    prof = _profiler()
    if prof is not None:
        try:
            ann = prof.TraceAnnotation(kind, **(detail or {}))
        except Exception:  # noqa: BLE001 — annotation is best-effort
            ann = None
    return Span(kind, dict(labels) or None,
                dict(detail) if detail else None, ann)


def step_span(step: int, name: str = "step") -> Span:
    """Span for one whole train step, with ``StepTraceAnnotation``
    pass-through so device profilers attribute work to step numbers."""
    ann = None
    prof = _profiler()
    if prof is not None:
        try:
            ann = prof.StepTraceAnnotation(name, step_num=int(step))
        except Exception:  # noqa: BLE001 — annotation is best-effort
            ann = None
    return Span(name, None, {"step": int(step)}, ann)


def stage(name: str):
    """Name one stage of a jitted program: ``stage("dedup")(fn)(*arrays)``.

    Under a JAX trace ``fn`` runs as an inner ``jax.jit`` of a function
    called ``name``, the way the planes' ``pull_a2a`` / ``push_a2a`` are
    named: the name becomes a function symbol of the lowered module (so
    it is part of the persistent compile cache's key, which strips
    ``jax.named_scope`` metadata) and a component of every instruction's
    ``op_name`` in the optimized HLO, where a device trace is read back
    to stages. XLA inlines the call; the program computes what it
    computed. ``fn`` takes arrays (pytrees) only and closes over anything
    static. Called eagerly there is no program to name, and ``fn`` is
    returned as it is.
    """
    def wrap(fn):
        if _trace_state_clean():
            return fn
        import jax

        def staged(*args):
            return fn(*args)
        staged.__name__ = staged.__qualname__ = name
        return jax.jit(staged)
    return wrap


# ---------------------------------------------------------------------------
# Chrome-trace / Perfetto export
# ---------------------------------------------------------------------------

def export_chrome_trace(path: Optional[str] = None, *,
                        process_name: Optional[str] = None
                        ) -> Dict[str, Any]:
    """Snapshot every thread's ring as Chrome-trace JSON (Perfetto- and
    ``chrome://tracing``-loadable). Returns the trace dict; writes it to
    ``path`` when given. Timestamps are microseconds from the module's
    load-time origin; per-thread metadata events carry thread names,
    and ``process_name`` labels this process in the viewer. The
    ``oeEpoch`` key records the origin on the system-wide monotonic
    clock so multi-process captures (serving replicas + load
    generator) merge onto ONE timeline (``merge_chrome_traces``)."""
    pid = os.getpid()
    events: List[Dict[str, Any]] = []
    if process_name:
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": str(process_name)}})

    def _event(tid: int, ev: tuple) -> Dict[str, Any]:
        kind, t0, dt, labels, error, trace_time, detail = ev
        args: Dict[str, Any] = dict(labels or {})
        if detail:
            args.update(detail)
        if error:
            args["error"] = error
        if trace_time:
            args["trace_time"] = True
        return {"name": kind, "ph": "X", "cat": "graftscope",
                "ts": (t0 - _EPOCH) * 1e6, "dur": dt * 1e6,
                "pid": pid, "tid": tid, "args": args}

    with _REG_LOCK:
        _retire_dead_locked()
        rings = [(r.tid, r.name, r.dropped, list(r.buf)) for r in _RINGS]
        retired = list(_RETIRED)
        retired_dropped = _retired_total - len(_RETIRED)
    if retired_dropped > 0:
        # the bounded retired deque evicted old dead-thread spans — the
        # trace must say so, like the per-ring dropped markers below
        events.append({"ph": "M", "name": "graftscope_dropped",
                       "pid": pid, "tid": 0,
                       "args": {"retired_dropped": retired_dropped}})
    named = set()
    for tid, name, dropped, buf in rings:
        named.add((tid, name))
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})
        if dropped:
            events.append({"ph": "M", "name": "graftscope_dropped",
                           "pid": pid, "tid": tid,
                           "args": {"dropped": dropped}})
        events.extend(_event(tid, ev) for ev in buf)
    for tid, name, ev in retired:
        if (tid, name) not in named:
            named.add((tid, name))
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": name}})
        events.append(_event(tid, ev))
    events.sort(key=lambda e: e.get("ts", -1.0))
    trace = {"traceEvents": events, "displayTimeUnit": "ms",
             "oeEpoch": _EPOCH}
    if path:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f)
    return trace


def merge_chrome_traces(base: Dict[str, Any],
                        others: List[Dict[str, Any]],
                        path: Optional[str] = None) -> Dict[str, Any]:
    """Fold traces captured by OTHER processes (serving replicas) into
    ``base`` (the client's capture) on one timeline: each process's
    ``oeEpoch`` offsets its microsecond timestamps onto the base
    origin. ``time.perf_counter`` is the system-wide monotonic clock on
    Linux, so cross-process spans line up for real — a request's
    server-side span sits inside its client span in Perfetto. Distinct
    pids keep per-process tracks separate; the shared ``trace`` args
    stitch one request's story across them."""
    base_epoch = float(base.get("oeEpoch", 0.0))
    events = list(base.get("traceEvents", []))
    for tr in others:
        off_us = (float(tr.get("oeEpoch", base_epoch)) - base_epoch) * 1e6
        for e in tr.get("traceEvents", []):
            e = dict(e)
            if "ts" in e:
                e["ts"] = e["ts"] + off_us
            events.append(e)
    events.sort(key=lambda e: e.get("ts", -1.0))
    merged = {"traceEvents": events, "displayTimeUnit": "ms",
              "oeEpoch": base_epoch}
    if path:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(merged, f)
    return merged


# ---------------------------------------------------------------------------
# expected-vs-measured byte ledger
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExpectedBytes:
    """One plane program's HLO-derived per-device collective cost."""

    plane: str
    program: str                       # "pull" | "push"
    total: int                         # sum of per-op largest buffers
    per_op: Mapping[str, Tuple[int, int]]   # op -> (count, bytes)
    params: Mapping[str, int]
    # compiled memory ledger (graftwatch: jaxcompat.compiled_memory_stats
    # of the SAME program) — None when the backend exposes no analysis
    memory: Optional[Mapping[str, int]] = None


def expected_collective_bytes(hlo_text: str
                              ) -> Tuple[int, Dict[str, Tuple[int, int]]]:
    """(total, per-op) expected collective bytes of one compiled
    program: per instance the LARGEST single buffer (async ``-start``
    tuples carry operand and result — summing every buffer would
    double-count), summed per op via ``contracts.summarize(largest=
    True)`` — the same accounting ``contracts.OpBudget.max_total``
    bounds."""
    from . import contracts
    per_op = contracts.summarize(hlo_text, largest=True)
    return sum(b for _c, b in per_op.values()), per_op


def plane_expected_bytes(mesh, plane: str, program: str, *,
                         batch: int = 1024, dim: int = 16,
                         use_hash: bool = False, tables: int = 3,
                         check: bool = True) -> ExpectedBytes:
    """Lower one plane's pull/push exactly as the training path runs it
    (:mod:`.programs`) and cost-account its collectives. With ``check``
    the program is also audited against its registered contract, so the
    ledger's expected bytes provably sit inside the bounds
    ``contracts.py`` enforces."""
    from . import contracts, programs
    from ..utils import jaxcompat
    if plane == "a2a+grouped":
        build = (programs.compile_grouped_pull if program == "pull"
                 else programs.compile_grouped_push)
        compiled, params = build(mesh, tables=tables, batch=batch,
                                 dim=dim, use_hash=use_hash)
    else:
        build = (programs.compile_pull if program == "pull"
                 else programs.compile_push)
        compiled, params = build(mesh, plane, batch=batch, dim=dim,
                                 use_hash=use_hash)
    txt = compiled.as_text()
    if check:
        contracts.check_program(txt, plane, program, **params)
    total, per_op = expected_collective_bytes(txt)
    return ExpectedBytes(plane=plane, program=program, total=total,
                         per_op=per_op, params=params,
                         memory=jaxcompat.compiled_memory_stats(compiled))


def ledger_rows(expected: List[ExpectedBytes]) -> List[Dict[str, Any]]:
    """Join expected bytes with the measured pull/push span histograms
    (``span_pull_seconds{plane=...}`` etc.): per row calls, p50/p95
    latency, expected collective bytes, achieved GB/s at the p50, and
    the program's expected per-device HBM peak (graftwatch memory
    ledger; None when the backend exposes no memory analysis)."""
    rows = []
    for e in expected:
        name = _hist_name(e.program)
        calls = HISTOGRAMS.count(name, plane=e.plane)
        p50 = HISTOGRAMS.quantile(name, 0.5, plane=e.plane)
        p95 = HISTOGRAMS.quantile(name, 0.95, plane=e.plane)
        gbps = (e.total / p50 / 1e9) if calls and p50 == p50 and p50 > 0 \
            else float("nan")
        rows.append({"plane": e.plane, "stage": e.program,
                     "calls": calls, "p50_ms": p50 * 1e3,
                     "p95_ms": p95 * 1e3, "expected_bytes": e.total,
                     "per_op": dict(e.per_op), "gbps_p50": gbps,
                     "hbm_peak_bytes": (e.memory or {}).get("peak_bytes"),
                     "temp_bytes": (e.memory or {}).get("temp_bytes")})
    return rows


def format_ledger(rows: List[Dict[str, Any]]) -> str:
    """Fixed-width per-plane/per-stage table for terminals and logs."""
    head = (f"{'plane':<14}{'stage':<7}{'calls':>6}{'p50_ms':>10}"
            f"{'p95_ms':>10}{'expected_B':>12}{'GB/s@p50':>10}"
            f"{'HBM_MiB':>9}")
    out = [head, "-" * len(head)]
    for r in rows:
        peak = r.get("hbm_peak_bytes")
        hbm = f"{peak / (1 << 20):.2f}" if peak is not None else "n/a"
        out.append(
            f"{r['plane']:<14}{r['stage']:<7}{r['calls']:>6}"
            f"{r['p50_ms']:>10.3f}{r['p95_ms']:>10.3f}"
            f"{r['expected_bytes']:>12}{r['gbps_p50']:>10.4f}"
            f"{hbm:>9}")
    return "\n".join(out)
