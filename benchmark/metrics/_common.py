"""What several readers share: the table of peaks, the traced step time,
device time under a scope, the window's distinct rows."""

import json
import os

from .. import counts


def peaks(run):
    """This device kind's row of ``peaks.json``; an unknown kind is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    kind = run["device_kind"]
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def traced_step_s(run):
    """Seconds per step over the traced window, from the device trace."""
    trace = run.get("trace")
    if not trace or not trace["steps"]:
        return None
    return trace["window_s"] / trace["steps"]


def scope_ms_per_step(run, scope):
    """Device milliseconds per step under one of the program's named
    functions, from the device trace."""
    trace = run.get("trace")
    if not trace or not trace["steps"] or scope not in trace["scope_s"]:
        return None
    return trace["scope_s"][scope] * 1e3 / trace["steps"]


def unique_rows(run):
    """Mean distinct (feature, id) rows of the window's own batches,
    counted once per run."""
    if "unique_rows" not in run:
        run["unique_rows"] = counts.mean_unique_rows(
            run["raw_window_batches"])
    return run["unique_rows"]
