"""From a profiler trace (``.xplane.pb``) to numbers.

``reduce(path)`` reads the trace with ``jax.profiler.ProfileData`` and
returns, averaged over the device planes it finds:

``busy_s`` / ``window_s``   union of the intervals in which an operation
                            ran on the device, and the span from the first
                            to the last device event
``steps``                   executions of the step program in the window
``scope_s``                 device seconds under each named scope of the
                            program (``pull_a2a``, ``push_a2a``, ...)
``kind_s``                  device seconds by kind of operation: gather,
                            scatter, sort, and collective (transfers
                            between chips, on the asynchronous line too)
``breakdown``               the ten device operations with most time, and
                            the longest idle gaps by the host span that
                            covered them

    python3 -m benchmark.trace_reduce <file.xplane.pb> [step.hlo.txt]
"""

import collections
import glob
import gzip
import json
import os
import re
import sys

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "step_fn"
SCOPES = ("pull_a2a", "push_a2a")
ASYNC_LINE = "Async XLA Ops"    # where the chip-to-chip transfers run
KINDS = {                       # first match wins: all-gather is no gather
    "collective": ("all-to-all", "all-gather", "all-reduce",
                   "collective-permute", "reduce-scatter"),
    "gather": ("gather",),
    "scatter": ("scatter",),
    "sort": ("sort",),
}
MIN_GAP_S = 20e-6           # shorter pauses between two operations are not
TOP = 10                    # attributed to the host


def load(path):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^\w+\((.*)\)$")      # jit(x), jvp(x), transpose(x)


def scope_names(hlo_text):
    """{instruction: 'scope/path'} from an optimized HLO module's
    ``metadata={op_name=...}``: the program's named functions and scopes
    and the primitive, each taken out of JAX's wrappers
    (``jit(push_a2a)`` -> ``push_a2a``, ``transpose(jvp(DeepFM))`` ->
    ``DeepFM``)."""
    names = {}
    for text in (hlo_text or "").splitlines():
        instruction = _INSTRUCTION.match(text)
        op_name = _OP_NAME.search(text)
        if not instruction or not op_name:
            continue
        parts = []
        for part in op_name.group(1).split("/"):
            while _WRAPPED.match(part):
                part = _WRAPPED.match(part).group(1)
            if part:
                parts.append(part)
        if parts:
            names[instruction.group(1)] = "/".join(parts)
    return names


def op_label(event_name, names):
    """``scope/path/instruction`` of a device operation. The trace names an
    operation by its HLO text, ``%fusion.13 = f32[...] fusion(...)``."""
    instruction = event_name.split(" = ", 1)[0].strip().lstrip("%")
    scope = names.get(instruction)
    return f"{scope}/{instruction}" if scope else instruction


def _union(intervals):
    """Total length and the merged list of [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _host_spans(data):
    """[(name, start, end)] of annotations on host threads."""
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    spans.append((e.name, e.start_ns, e.start_ns
                                  + e.duration_ns))
    return spans


def _gap_owners(spans, mids):
    """Name of the shortest host span that covers each instant."""
    if not spans:
        return ["no_host_span"] * len(mids)
    names = [s[0] for s in spans]
    start = np.array([s[1] for s in spans], np.float64)
    end = np.array([s[2] for s in spans], np.float64)
    width = end - start
    out = []
    for at in mids:
        covering = np.nonzero((start <= at) & (at < end))[0]
        out.append(names[covering[np.argmin(width[covering])]]
                   if covering.size else "no_host_span")
    return out


def reduce(path, hlo_text=None):
    data = load(path)
    names = scope_names(hlo_text)
    planes = [p for p in data.planes if p.name.startswith(DEVICE_PLANE)
              and _line(p, OPS_LINE) is not None]
    if not planes:
        return None
    busy = window = 0.0
    steps = 0
    scope_s = collections.Counter()
    kind_s = collections.Counter()
    op_s = collections.Counter()
    first_gaps = None
    for n, plane in enumerate(planes):
        events = [(e.start_ns, e.start_ns + e.duration_ns, e)
                  for e in _line(plane, OPS_LINE).events]
        if not events:
            continue
        total, merged = _union([(a, b) for a, b, _ in events])
        busy += total * 1e-9
        window += (merged[-1][1] - merged[0][0]) * 1e-9
        if n == 0:
            first_gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                          if (b[0] - a[1]) * 1e-9 >= MIN_GAP_S]
        # intervals, not sums of durations: a ``while`` holds its body's
        # operations, and all of them are events
        scope_at = collections.defaultdict(list)
        kind_at = collections.defaultdict(list)
        for a, b, e in events:
            label = op_label(e.name, names)
            seconds = (b - a) * 1e-9
            op_s[label] += seconds
            low = label.lower()
            for scope in SCOPES:       # ``hash_pull_a2a`` is a pull too
                if any(p.endswith(scope) for p in label.split("/")):
                    scope_at[scope].append((a, b))
            for kind, words in KINDS.items():
                if any(w in low for w in words):
                    kind_at[kind].append((a, b))
                    break
        asyncs = _line(plane, ASYNC_LINE)
        for e in (asyncs.events if asyncs is not None else ()):
            name = e.name.split(" = ", 1)[0].lower()
            if any(w in name for w in KINDS["collective"]):
                kind_at["collective"].append(
                    (e.start_ns, e.start_ns + e.duration_ns))
        for k, v in scope_at.items():
            scope_s[k] += _union(v)[0] * 1e-9
        for k, v in kind_at.items():
            kind_s[k] += _union(v)[0] * 1e-9
        modules = _line(plane, MODULES_LINE)
        if modules is not None and n == 0:
            steps = sum(1 for e in modules.events if STEP_PROGRAM in e.name)
    chips = len(planes)
    gaps = collections.Counter()
    if first_gaps:
        owners = _gap_owners(_host_spans(data),
                             [(a + b) / 2 for a, b in first_gaps])
        for (a, b), owner in zip(first_gaps, owners):
            gaps[owner] += (b - a) * 1e-9
    return {
        "chips": chips, "busy_s": busy / chips, "window_s": window / chips,
        "steps": steps,
        "scope_s": {k: v / chips for k, v in scope_s.items()},
        "kind_s": {k: v / chips for k, v in kind_s.items()},
        "breakdown": {
            "device_ops": [[k, v / chips] for k, v in op_s.most_common(TOP)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(TOP)],
        },
    }


def reduce_run(context):
    """The reduction of a traced run's own trace, or None without one (a
    rehearsal on the CPU has no device plane)."""
    if not context.get("trace_dir"):
        return None
    path = find_xplane(context["trace_dir"])
    return reduce(path, context.get("step_hlo")) if path else None


if __name__ == "__main__":
    hlo = open(sys.argv[2]).read() if len(sys.argv) > 2 else None
    print(json.dumps(reduce(sys.argv[1], hlo), indent=1))
