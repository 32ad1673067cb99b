"""What the autosave readers share: the window's save counters and span
seconds (``run["autosave"]``, which ``train_autosave_runner`` fills from
the program's ``analysis/scope`` registry and ``observability`` counters)
and the snapshot programs' device time. A program without the spans,
counters or stage gives every reader nothing to read: ``None``, never a
raise."""

from . import _offload

GATHER_STAGE = "ckpt_gather"        # the snapshot programs' name in a trace
DENSE_COPY = "ckpt_gather_dense"    # once a save: counts the traced saves
TABLES = 2                          # fields and fields:linear, the same ids


def saves(run):
    """Delta saves committed inside the window, or None."""
    read = run.get("autosave") or {}
    return read.get("ckpt_delta_saves") or None


def span_ms_per_save(run, *spans):
    """Milliseconds per save inside the named spans together; None where
    the program has none of them or committed no save."""
    read, n = run.get("autosave") or {}, saves(run)
    found = [read[s] for s in spans if read.get(s, {}).get("calls")]
    if not n or not found:
        return None
    return sum(s["s"] for s in found) * 1e3 / n


def counter_per_save(run, counter):
    read, n = run.get("autosave") or {}, saves(run)
    if not n or not read.get(counter):
        return None
    return read[counter] / n


def gather_device_s(run):
    """(device seconds in the snapshot programs, saves they belong to)
    over the traced window, from the ``XLA Modules`` line, averaged over
    the device planes; None without a trace or without the stage."""
    lines = _offload._device_lines(run)
    if not run.get("trace") or not lines:
        return None
    total_ns = traced = 0
    for _, modules in lines:
        for e in modules:
            if GATHER_STAGE in e.name:
                total_ns += e.duration_ns
                traced += DENSE_COPY in e.name
    if not total_ns or not traced:
        return None
    return total_ns * 1e-9 / len(lines), traced / len(lines)
