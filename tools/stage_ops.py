"""Which instructions a stage's device time is made of.

    python -m tools.stage_ops <trace.xplane.pb> <step.hlo.txt[.gz]> [top]

``benchmark.stage_reduce`` gives every event of a traced run's ``XLA Ops``
line to a stage and sums self time by stage; this prints the same
attribution one instruction a line: under each stage, costliest first,
the ``top`` (default 12) instructions with their device ms a step per chip
and the end of their ``op_name`` path. A ``--trace 1`` run of
``benchmark.run`` leaves both files under ``benchmark/out/`` (the trace
under ``<cell>.<seed>.trace/plugins/profile/*/``). PERF.md section 5's
per-instruction figures were read this way.
"""

from __future__ import annotations

import collections
import gzip
import sys

from benchmark import stage_reduce, trace_reduce


def instruction_ms(trace_path: str, hlo_text: str):
    """``(chips, steps, {stage: [(ms a step per chip, instruction, path)]})``
    of one trace, by ``stage_reduce``'s own attribution."""
    paths = trace_reduce.scope_names(hlo_text)
    stages = stage_reduce.instruction_stages(hlo_text, paths)
    data = trace_reduce.load(trace_path)
    planes = [p for p in data.planes
              if p.name.startswith(trace_reduce.DEVICE_PLANE)
              and trace_reduce._line(p, trace_reduce.OPS_LINE) is not None]
    own_ns = collections.Counter()
    steps = 0
    for n, plane in enumerate(planes):
        events = list(trace_reduce._line(plane, trace_reduce.OPS_LINE).events)
        intervals = [(e.start_ns, e.start_ns + e.duration_ns)
                     for e in events]
        for e, own in zip(events, stage_reduce.self_times(intervals)):
            own_ns[e.name.split(" = ", 1)[0].strip().lstrip("%")] += own
        if n == 0:
            modules = trace_reduce._line(plane, trace_reduce.MODULES_LINE)
            steps = sum(1 for e in (modules.events if modules else ())
                        if trace_reduce.STEP_PROGRAM in e.name)
    by_stage = collections.defaultdict(list)
    for instruction, ns in own_ns.items():
        by_stage[stages.get(instruction, stage_reduce.UNATTRIBUTED)].append(
            (ns * 1e-6 / max(len(planes), 1) / max(steps, 1), instruction,
             paths.get(instruction, "")))
    return len(planes), steps, by_stage


def main(argv):
    opener = gzip.open if argv[1].endswith(".gz") else open
    with opener(argv[1], "rt") as f:
        chips, steps, by_stage = instruction_ms(argv[0], f.read())
    top = int(argv[2]) if len(argv) > 2 else 12
    print(f"{chips} chip(s), {steps} steps")
    for stage, rows in sorted(by_stage.items(),
                              key=lambda kv: -sum(r[0] for r in kv[1])):
        print(f"== {stage} {sum(r[0] for r in rows):.3f} ms a step, "
              f"{len(rows)} instructions")
        for ms, instruction, path in sorted(rows, reverse=True)[:top]:
            print(f"   {ms:7.3f} {instruction:<28} {path[-110:]}")


if __name__ == "__main__":
    main(sys.argv[1:])
