"""From a profiler trace and the step's HLO to time per named stage.

The program runs each stage of its step as an inner jitted function named
after the stage (``openembedding_tpu.analysis.scope.stage``), so the name is
a component of every instruction's ``op_name``. ``reduce(path, hlo_text)``
gives every event of the device's ``XLA Ops`` line to one stage and sums
*self time*: a ``while``, ``conditional`` or ``call`` event counts only the
part of its interval that no event inside it covers, so the stages and
``unattributed`` add up to the device's busy time. Averaged over the device
planes, like ``trace_reduce``:

``stage_s``     device seconds per stage, ``unattributed`` among them
``branch_s``    device seconds of all events under ``push_routed`` and
                under ``push_spilled``: which branch of the push ran
``host_s``      seconds of the trainer's host spans that begin inside the
                device window, by name, and ``host_steps``, the ``step``
                spans that do
``busy_s``, ``steps``   as ``trace_reduce`` reads them

    python3 -m benchmark.stage_reduce <file.xplane.pb> <step.hlo.txt>

prints the whole table.
"""

import collections
import json
import re
import sys
import time

from . import trace_reduce

STAGES = ("dedup", "route", "exchange", "push_routed", "push_spilled",
          "resolve", "probe", "init_rows", "apply_gather", "apply_update",
          "apply_scatter", "expand", "dense_fwd", "dense_bwd",
          "dense_update")
BRANCHES = ("push_routed", "push_spilled")
UNATTRIBUTED = "unattributed"
STEP_SPAN = "step"
HOST_SPANS = ("trainer.next_batch", "trainer.place_batch",
              "trainer.dispatch", "trainer.bookkeeping")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_REFERENCE = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")


def deepest_stage(path):
    """The last component of a ``scope/path`` that is a stage, or None."""
    for part in reversed(path.split("/")):
        if part in STAGES:
            return part
    return None


def instruction_stages(hlo_text, paths=None):
    """{instruction: stage} for an optimized HLO module (``paths``: its
    ``trace_reduce.scope_names``, where the caller has read them).

    An instruction belongs to the deepest stage in its own ``op_name``
    path. The compiler makes instructions whose metadata names no stage
    (the passes of an expanded cumulative sum, the slices around an
    all-to-all, the body of a fusion): such an instruction takes the stage
    of the instruction that calls its computation, else of the first of
    its operands that has one. The text lists a computation before its
    callers and an operand before its users, so one pass settles it.
    """
    stages = {}
    if paths is None:
        paths = trace_reduce.scope_names(hlo_text)
    for instruction, path in paths.items():
        stage = deepest_stage(path)
        if stage:
            stages[instruction] = stage
    computations, caller = [], {}
    body = None
    for text in (hlo_text or "").splitlines():
        if body is not None and text.startswith("}"):
            body = None
            continue
        found = _INSTRUCTION.match(text)
        if body is None or not found:
            opened = _COMPUTATION.match(text)
            if opened and " = " not in text:
                body = []
                computations.append((opened.group(1), body))
            continue
        instruction, rest = found.groups()
        body.append((instruction, _REFERENCE.findall(rest)))
        for single, several in _CALLED.findall(rest):
            for name in [single] if single else \
                    _REFERENCE.findall(several):
                caller[name] = instruction
    for name, body in reversed(computations):
        inherited = stages.get(caller.get(name))
        for instruction, references in body:
            if instruction in stages:
                continue
            stage = inherited or next(
                (stages[r] for r in references if r in stages), None)
            if stage:
                stages[instruction] = stage
    return stages


def self_times(intervals):
    """Self time of each ``(start, end)``: its length less the part that
    the intervals inside it cover. Returned in the order given."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [0] * len(intervals)
    stack = []          # [index, end, end of what its children cover]

    def close():
        index, end, _ = stack.pop()
        if stack:       # its parent's children now cover up to its end
            parent = stack[-1]
            start = max(intervals[index][0], parent[2])
            clipped = min(end, parent[1])
            if clipped > start:
                own[parent[0]] -= clipped - start
                parent[2] = clipped

    for i in order:
        start, end = intervals[i]
        while stack and stack[-1][1] <= start:
            close()
        own[i] += end - start
        stack.append([i, end, start])
    while stack:
        close()
    return own


def reduce(path, hlo_text):
    """The stage table of one trace, or None without a device plane or
    without a stage name in the HLO (a program older than the stages)."""
    paths = trace_reduce.scope_names(hlo_text)
    stages = instruction_stages(hlo_text, paths)
    if not stages:
        return None
    data = trace_reduce.load(path)
    planes = [p for p in data.planes
              if p.name.startswith(trace_reduce.DEVICE_PLANE)
              and trace_reduce._line(p, trace_reduce.OPS_LINE) is not None]
    stage_ns = collections.Counter()
    branch_ns = collections.Counter()
    busy_ns = steps = 0
    window = None
    for n, plane in enumerate(planes):
        events = list(trace_reduce._line(plane, trace_reduce.OPS_LINE).events)
        if not events:
            continue
        intervals = [(e.start_ns, e.start_ns + e.duration_ns)
                     for e in events]
        busy_ns += trace_reduce._union(intervals)[0]
        for e, own in zip(events, self_times(intervals)):
            instruction = e.name.split(" = ", 1)[0].strip().lstrip("%")
            stage_ns[stages.get(instruction, UNATTRIBUTED)] += own
            for branch in BRANCHES:
                if branch in paths.get(instruction, "").split("/"):
                    branch_ns[branch] += own
        if n == 0:
            window = (min(a for a, _ in intervals),
                      max(b for _, b in intervals))
            modules = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
            steps = sum(1 for e in (modules.events if modules else ())
                        if trace_reduce.STEP_PROGRAM in e.name)
    if window is None:
        return None
    host_ns = collections.Counter()
    for name, start, end in trace_reduce._host_spans(data):
        if window[0] <= start < window[1] and \
                (name in HOST_SPANS or name == STEP_SPAN):
            host_ns[name] += end - start
            if name == STEP_SPAN:
                host_ns["steps"] += 1
    chips = len(planes)
    return {
        "chips": chips, "steps": steps, "busy_s": busy_ns * 1e-9 / chips,
        "stage_s": {k: v * 1e-9 / chips for k, v in stage_ns.items()},
        "branch_s": {k: v * 1e-9 / chips for k, v in branch_ns.items()},
        "host_steps": host_ns.pop("steps", 0),
        "host_s": {k: v * 1e-9 for k, v in host_ns.items()},
    }


def reduce_run(run):
    """The stage table of a traced run's own trace, read once per run."""
    if "stages" not in run:
        run["stages"] = None
        path = run.get("trace_dir") and \
            trace_reduce.find_xplane(run["trace_dir"])
        if path and run.get("step_hlo"):
            began = time.perf_counter()
            run["stages"] = reduce(path, run["step_hlo"])
            print(json.dumps({"stage_reduce_s": round(
                time.perf_counter() - began, 2)}), flush=True)
    return run["stages"]


def stage_ms_per_step(run, *names):
    """Device milliseconds per step in the named stages together; None
    where the trace or the HLO has nothing to read."""
    table = reduce_run(run)
    if not table or not table["steps"]:
        return None
    return sum(table["stage_s"].get(n, 0.0) for n in names) * 1e3 \
        / table["steps"]


def host_ms_per_step(run, name):
    """Host milliseconds per step in one of the trainer's spans."""
    table = reduce_run(run)
    if not table or not table["host_steps"] or name not in table["host_s"]:
        return None
    return table["host_s"][name] * 1e3 / table["host_steps"]


def format_table(table):
    """The stage table as text, milliseconds per step."""
    if not table:
        return "no device plane, or no stage name in the HLO"
    steps = table["steps"] or 1
    busy = table["busy_s"]
    lines = [f"{table['chips']} chip(s), {table['steps']} steps, busy "
             f"{busy * 1e3 / steps:.3f} ms a step",
             f"{'stage':<16}{'ms/step':>10}{'share':>8}"]
    for name, s in sorted(table["stage_s"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<16}{s * 1e3 / steps:>10.3f}"
                     f"{100 * s / busy:>7.2f}%")
    total = sum(table["stage_s"].values())
    lines.append(f"{'sum':<16}{total * 1e3 / steps:>10.3f}"
                 f"{100 * total / busy:>7.2f}%")
    for name, s in table["branch_s"].items():
        lines.append(f"under {name}: {s * 1e3 / steps:.3f} ms a step")
    host_steps = table["host_steps"] or 1
    lines.append(f"host spans over {table['host_steps']} steps:")
    for name, s in sorted(table["host_s"].items()):
        lines.append(f"{name:<24}{s * 1e3 / host_steps:>10.3f} ms a step")
    return "\n".join(lines)


if __name__ == "__main__":
    import gzip
    opener = gzip.open if sys.argv[2].endswith(".gz") else open
    with opener(sys.argv[2], "rt") as f:
        print(format_table(reduce(sys.argv[1], f.read())))
