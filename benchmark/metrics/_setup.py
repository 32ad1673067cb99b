"""What the ``setup_*`` readers share: the program's load ledger cut at the
window's call of ``Trainer.fit`` (``setup_system.at_window``). The window's
call dispatched the run's steps and the mix's lead-in before them. A
program without the ledger, or a log without that call, gives every reader
nothing to read: ``None``, never a raise."""

from .. import setup_system


def ledger(run):
    """``setup_system.at_window`` of this run, read once."""
    if "setup" not in run:
        dispatched = run.get("steps")
        lead_in = (run.get("traffic") or {}).get("lead_in_steps", 0)
        run["setup"] = None if dispatched is None else \
            setup_system.at_window(dispatched + lead_in)
    return run["setup"]


def total(run, *keys):
    """The sum of the named totals of the ledger, or None."""
    read = ledger(run)
    return read and sum(read["totals"][k] for k in keys)
