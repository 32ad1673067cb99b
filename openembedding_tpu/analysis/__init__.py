"""graftcheck: static analysis of the compiled programs and the source.

Three enforcement layers, all mechanical (ISSUE 3):

* :mod:`.contracts` — declarative per-plane contracts over compiled HLO
  text: which collectives each data plane's pull/push/step program may
  contain and how big their buffers may be, plus cross-cutting audits
  (no f64 leaks, donation honored, no host transfers inside the step).
* :mod:`.lint` — a jit-purity AST linter over the package's own source
  (host-state mutation under trace, tracer materialization, retrace-risk
  branches, undonated step functions). CLI: ``python -m tools.graftlint``.
* :mod:`.concurrency` — graftrace (ISSUE 4): a lock-discipline linter
  over the threaded host planes (rules JG101-JG104, CLI
  ``python -m tools.graftrace``), runtime TracedLock/TracedRLock
  wrappers with lock-order-cycle (potential-deadlock) detection and
  contention counters, and the deterministic interleaving harness
  (``sync_point``/``SerialSchedule``/``PointGate``).
* :mod:`.retrace` — the load ledger (every program JAX traces, lowers,
  fetches from the compile cache or compiles, from the process's one
  ``jax.monitoring`` listener) and the runtime guard that counts its
  programs around a training loop and fails past a declared budget.
* :mod:`.scope` — graftscope (ISSUE 6): span tracing into per-thread
  ring buffers (Chrome-trace/Perfetto export), the log-bucket histogram
  registry behind the ``/metrics`` ``_bucket``/``_sum``/``_count``
  series, and the expected-vs-measured collective-byte ledger (CLI
  ``python -m tools.graftscope``).
* :mod:`.memwatch` — graftwatch (ISSUE 7): the per-plane compiled-
  program MEMORY ledger (``memory_analysis`` argument/output/temp/alias
  bytes via the jaxcompat shim) with the peak-temp-bytes contract, and
  the substrate under the ``tools/graftwatch.py`` bench-trajectory
  regression gate.
* :mod:`.protomodel` — graftproto (ISSUE 13): explicit-state BFS model
  checker + faithful models of the four shipped host protocols (delta
  chain, serving hot-swap, DirtyTracker claims, HA registry), each
  action bridged to real ``sync_point`` names so counterexample
  schedules replay against the implementation. CLI:
  ``python -m tools.graftproto``.

Import discipline: ``contracts``, ``lint``, ``concurrency``, and
``scope`` are stdlib-only at import time and imported eagerly, so every
subsystem module (and the graftlint/graftrace CLIs) can use
``@host_fn`` / ``make_lock`` / ``sync_point`` / ``span`` without paying
for jax (``scope`` looks jax up lazily, and only when something else
already imported it). ``retrace`` (imports jax) and ``programs``
(lowers real programs) load lazily via module ``__getattr__`` — the
public surface is unchanged.
"""

from . import concurrency, contracts, lint, protomodel, scope
from .concurrency import (TraceViolation, TracedLock, TracedRLock,
                          make_lock, make_rlock, sync_point,
                          trace_paths, trace_source)
from .contracts import (ContractViolation, ProgramContract, OpBudget,
                        REGISTRY, check_program, collect_collectives,
                        summarize, check_a2a_pull_hlo)
from .lint import LintViolation, host_fn, lint_paths, lint_source
from .scope import (HISTOGRAMS, HistogramRegistry, Span,
                    export_chrome_trace, span, step_span)

_LAZY = {
    "retrace": ".retrace", "programs": ".programs",
    "memwatch": ".memwatch",
    "RetraceBudgetExceeded": ".retrace", "RetraceGuard": ".retrace",
}


def __getattr__(name):  # PEP 562: defer the jax-importing submodules
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(_LAZY[name], __name__)
        if name in ("retrace", "programs", "memwatch"):
            return mod
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "concurrency", "contracts", "lint", "retrace", "programs", "scope",
    "memwatch", "protomodel",
    "HISTOGRAMS", "HistogramRegistry", "Span", "export_chrome_trace",
    "span", "step_span",
    "ContractViolation", "ProgramContract", "OpBudget", "REGISTRY",
    "check_program", "collect_collectives", "summarize",
    "check_a2a_pull_hlo",
    "LintViolation", "host_fn", "lint_paths", "lint_source",
    "TraceViolation", "TracedLock", "TracedRLock", "make_lock",
    "make_rlock", "sync_point", "trace_paths", "trace_source",
    "RetraceBudgetExceeded", "RetraceGuard",
]
