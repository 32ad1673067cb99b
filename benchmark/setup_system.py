"""The adapter to the program's own record of set-up: the load ledger of
``openembedding_tpu.analysis.retrace`` (every program JAX traced, lowered,
fetched from the persistent cache or compiled, with the process's start)
and the log of ``Trainer.fit``'s calls beside it. Beside ``system.py``,
``offload_system.py`` and ``autosave_system.py``, which import the program
for the runners; this one imports it for the ``setup_*`` readers.

Set-up ends inside the window's call of ``fit`` (the lead-in steps are
its last part), so the ledger is cut where that call began: every runner
hands ``fit`` the window's feed once, and the steps that call dispatched
are the run's steps plus the mix's lead-in. A program without the ledger,
or a log in which no returned call dispatched that many, gives ``None``."""


def at_window(dispatched, program=None):
    """The ledger as it stood when the call of ``fit`` that dispatched
    ``dispatched`` steps began (the last such call), or None: ``at``
    (when that was, on ``time.perf_counter()``), ``import_s`` (the
    operating system's start of the process to the ledger's install),
    ``totals`` (programs, hits, misses, off, and seconds by phase) and
    ``warm_fit_s`` (seconds inside the calls of ``fit`` that had returned
    by then). ``program`` stands in for the
    program's ``analysis.retrace`` module."""
    if program is None:
        try:
            from openembedding_tpu.analysis import retrace as program
        except ImportError:
            return None
    ledger = getattr(program, "LEDGER", None)
    if ledger is None:
        return None
    calls = [c for c in ledger.fit_calls if c.end is not None]
    window = next((c for c in reversed(calls) if c.steps == dispatched),
                  None)
    if window is None:
        return None
    return {"at": window.start, "import_s": ledger.import_s,
            "totals": window.totals,
            "warm_fit_s": sum(c.end - c.start for c in calls
                              if c.end <= window.start)}
