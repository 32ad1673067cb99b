"""What the offload tier's readers share: the window's counters and span
seconds (``run["offload"]``, which ``train_offload_runner`` fills from the
program's ``analysis/scope`` registry), the insert program's device time,
and the stage table of the step program alone. A program without the
tier's spans, counters or stage gives every reader nothing to read:
``None``, never a raise.

Why a stage table of its own: beside the step the tier runs a second
program, the insert, twice a step, and the trace names a device event by
its instruction alone. ``stage_reduce`` and ``trace_reduce`` look every
event up in the step's HLO, where ``fusion.12`` of the insert program is
some other ``fusion.12``: 5.4 of its 5.75 ms a step read ``unattributed``
and the rest under stages it never ran. ``step_stages`` keeps the events
that lie inside an execution of the step program on the trace's ``XLA
Modules`` line and gives those to ``stage_reduce``'s own attribution.
"""

import bisect
import collections

from .. import stage_reduce, trace_reduce

INSERT_STAGE = "offload_insert"     # the insert program's name in a trace


def span_s(run, span):
    """Seconds of the window inside one of the tier's spans, both tables
    together; None where the program has no such span."""
    read = (run.get("offload") or {}).get(span)
    return read["s"] if read and read["calls"] else None


def per_step_ms(run, seconds):
    if seconds is None or not run["steps"]:
        return None
    return seconds * 1e3 / run["steps"]


def counter_per_step(run, counter):
    tier = run.get("offload")
    if not tier or not run["steps"] or not tier.get("offload_unique_rows"):
        return None
    return tier[counter] / run["steps"]


def lines_of(data):
    """[(operations, modules)]: the two lines of every device plane of a
    loaded trace that has both."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        modules = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
        if ops is not None and modules is not None:
            lines.append((list(ops.events), list(modules.events)))
    return lines


def _device_lines(run):
    """``lines_of`` the run's own trace, read once a run."""
    if "device_lines" not in run:
        path = run.get("trace_dir") and \
            trace_reduce.find_xplane(run["trace_dir"])
        run["device_lines"] = lines_of(trace_reduce.load(path)) \
            if path else []
    return run["device_lines"]


def insert_device_ms_per_step(run):
    """Device milliseconds per step in executions of the insert program
    (its module is named after the stage), from the ``XLA Modules`` line
    of the traced window, averaged over the device planes."""
    trace = run.get("trace")
    lines = _device_lines(run)
    if not trace or not trace["steps"] or not lines:
        return None
    total_ns = sum(e.duration_ns for _, modules in lines for e in modules
                   if INSERT_STAGE in e.name)
    if not total_ns:
        return None
    return total_ns * 1e-6 / len(lines) / trace["steps"]


def step_stages(run):
    """{"steps", "busy_s", "stage_s", "scope_s"} of the step program's own
    device events, averaged over the device planes; None without a trace,
    the step's HLO or a stage name in it."""
    if "step_stages" not in run:
        run["step_stages"] = _step_stages(run)
    return run["step_stages"]


def _step_stages(run):
    hlo, lines = run.get("step_hlo"), _device_lines(run)
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    if not stages or not lines:
        return None
    stage_ns, scope_ns = collections.Counter(), collections.Counter()
    busy_ns = steps = 0
    for n, (ops, modules) in enumerate(lines):
        ran = sorted((e.start_ns, e.start_ns + e.duration_ns)
                     for e in modules
                     if trace_reduce.STEP_PROGRAM in e.name)
        starts = [a for a, _ in ran]
        if n == 0:
            steps = len(ran)

        def in_step(e):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            return i >= 0 and e.start_ns < ran[i][1]

        events = [e for e in ops if in_step(e)]
        intervals = [(e.start_ns, e.start_ns + e.duration_ns)
                     for e in events]
        busy_ns += trace_reduce._union(intervals)[0]
        under = collections.defaultdict(list)
        for e, at, own in zip(events, intervals,
                              stage_reduce.self_times(intervals)):
            instruction = e.name.split(" = ", 1)[0].strip().lstrip("%")
            stage_ns[stages.get(instruction,
                                stage_reduce.UNATTRIBUTED)] += own
            for scope in trace_reduce.SCOPES:   # ``hash_pull_a2a`` too
                if any(part.endswith(scope) for part in
                       paths.get(instruction, "").split("/")):
                    under[scope].append(at)
        for scope, at in under.items():
            scope_ns[scope] += trace_reduce._union(at)[0]
    if not steps:
        return None
    chips = len(lines)
    return {"steps": steps, "busy_s": busy_ns * 1e-9 / chips,
            "stage_s": {k: v * 1e-9 / chips for k, v in stage_ns.items()},
            "scope_s": {k: v * 1e-9 / chips for k, v in scope_ns.items()}}


def step_stage_ms_per_step(run, *names):
    """Device milliseconds per step in the named stages of the step
    program together."""
    table = step_stages(run)
    if not table:
        return None
    return sum(table["stage_s"].get(n, 0.0) for n in names) * 1e3 \
        / table["steps"]


def step_scope_ms_per_step(run, scope):
    """Device milliseconds per step under one of the step program's named
    functions (``pull_a2a``, ``push_a2a``)."""
    table = step_stages(run)
    if not table or scope not in table["scope_s"]:
        return None
    return table["scope_s"][scope] * 1e3 / table["steps"]
