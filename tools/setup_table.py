"""Where a benchmark run's set-up went: the load ledger laid beside the
runner's ``set_up`` marks.

    python -m tools.setup_table --workload <cell> --seed N --seconds S \\
        --trace 0|1

runs ``benchmark.run`` with the arguments as given (its lines pass through
unchanged, the result line included) and then prints, from
``analysis.retrace.LEDGER``: one line a set-up phase (a runner's
``{"set_up": phase, "at_s"}`` mark closes a phase; the window's call of
``Trainer.fit`` closes the last), with the phase's wall seconds and the
programs and seconds by kind that the ledger holds inside it; the log of
``fit``'s calls; ``LEDGER.table()`` cut where the window's call began,
the ten costliest programs by name; and the eight costliest one by one. All of it is on ``time.perf_counter()``,
as the marks are. The cut is the benchmark's own
(``benchmark.setup_system.at_window``, through the ``setup_*`` readers,
whose values are printed too, whatever ``--trace`` says); the phases are
summed from the ledger's entries, which are the newest 4096, and the
exact totals at the cut stand under them. The output's last line is a
JSON object with the same numbers, after the run's own result line.
"""

from __future__ import annotations

import importlib
import io
import json
import sys


class _Tee(io.TextIOBase):
    """Passes standard output through and keeps the runner's marks, its
    line of the window and the result line."""

    def __init__(self, out):
        self.out, self.marks, self.lines, self._line = out, [], {}, ""

    def write(self, text):
        self.out.write(text)
        self._line += text
        *whole, self._line = self._line.split("\n")
        for line in whole:
            if line.startswith('{"set_up"'):
                self.marks.append(json.loads(line))
            elif line.startswith(('{"window_s"', '{"cell"', '{"correct"')):
                self.lines[line[2:line.index('"', 2)]] = json.loads(line)
        return len(text)

    def flush(self):
        self.out.flush()


def phases(ledger, marks, t_process, cut):
    """[(phase, wall_s, {programs, hits, trace_lower_s, fetch_s,
    compile_s})] from the process's start to ``cut``, where the window's
    call of ``fit`` began (``perf_counter``)."""
    edges = [(m["set_up"], t_process + m["at_s"]) for m in marks
             if t_process + m["at_s"] <= cut]
    edges.append(("to_window_call", cut))
    out, lo = [], t_process
    for name, hi in edges:
        held = {"programs": 0, "hits": 0, "trace_lower_s": 0.0,
                "fetch_s": 0.0, "compile_s": 0.0}
        for e in ledger.entries:
            if not lo < e.end <= hi:
                continue
            held["trace_lower_s"] += e.trace_s + e.lower_s
            if e.cache is not None:
                held["programs"] += 1
                held["hits"] += e.cache == "hit"
                held["fetch_s" if e.cache == "hit" else "compile_s"] \
                    += e.backend_s
        out.append((name, hi - lo, held))
        lo = hi
    return out


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.metrics import _setup
    tee = _Tee(sys.stdout)
    sys.stdout = tee
    try:
        rc = run.main(argv)
    finally:
        sys.stdout = tee.out
    if rc:
        return rc
    from openembedding_tpu.analysis.retrace import LEDGER
    # the context the benchmark hands its readers, as far as they read it
    traffic = run.load("traffic", run.split_cell(tee.lines["cell"]["cell"])[1])
    context = {"steps": tee.lines["window_s"]["steps"], "traffic": traffic}
    read = _setup.ledger(context)
    if read is None:
        print("setup_table: no call of Trainer.fit dispatched the window's "
              "steps", file=sys.stderr)
        return 1
    metrics = {m["name"]: importlib.import_module(
        f"benchmark.metrics.{m['name']}").read(context)
        for m in run.manifest()["per_layer"] if m["moves"] == "setup_s"}
    setup_s = tee.lines["correct"]["metrics"].get("setup_s", {}).get("value")
    t0, cut, totals = run.T_PROCESS, read["at"], read["totals"]
    rows = phases(LEDGER, tee.marks, t0, cut)
    print(f"\nsetup_s {setup_s} (a --trace 1 run's line has none); the "
          f"window's call of fit began at {cut - t0:.2f} s")
    print(f"process start (OS) to the ledger's install: "
          f"{LEDGER.import_s:.2f} s; benchmark.run's first line to it: "
          f"{LEDGER.installed_at - t0:.2f} s")
    for name, value in metrics.items():
        print(f"  {name} {value}")
    print(f"{'phase':<16} {'wall_s':>8} {'programs':>8} {'hits':>5} "
          f"{'trace+lower':>11} {'fetch_s':>8} {'compile_s':>9} "
          f"{'residue_s':>9}")

    def row(name, wall, programs, hits, trace_lower, fetch, compiled):
        print(f"{name:<16} {wall:>8.2f} {programs:>8} {hits:>5} "
              f"{trace_lower:>11.2f} {fetch:>8.2f} {compiled:>9.2f} "
              f"{wall - trace_lower - fetch - compiled:>9.2f}")

    for name, wall, h in rows:
        row(name, wall, h["programs"], h["hits"], h["trace_lower_s"],
            h["fetch_s"], h["compile_s"])
    # exact, where the phases above are of the entries the ledger kept
    row("totals at cut", cut - t0, totals["programs"], totals["hits"],
        totals["trace_s"] + totals["lower_s"], totals["fetch_s"],
        totals["compile_s"])
    print(f"the cache says the hits saved {totals['saved_s']:.2f} s of "
          "compiling")
    print("\ncalls of Trainer.fit (start_s, seconds, steps, programs "
          "loaded before it):")
    calls = [c for c in LEDGER.fit_calls if c.end is not None]
    for c in calls:
        print(f"  {c.start - t0:>8.2f} {c.end - c.start:>8.2f} "
              f"{c.steps:>6} {c.totals['programs']:>5}")
    print("\n" + LEDGER.table(until=cut, top=10))
    # a name can stand for many programs (the harness jits lambdas)
    print("\nthe costliest single programs (name, began at_s, trace + "
          "lower, backend seconds, cache, what the cache says it saved):")
    loaded = [e for e in LEDGER.entries if e.end <= cut and e.cache]
    for e in sorted(loaded, key=lambda e: -(e.trace_s + e.lower_s
                                            + e.backend_s + e.saved_s))[:8]:
        print(f"  {e.name[:32]:<32} {e.start - t0:>8.2f} "
              f"{e.trace_s + e.lower_s:>7.2f} {e.backend_s:>8.2f} "
              f"{e.cache:<4} {e.saved_s:>8.2f}")
    print(json.dumps({
        "cell": tee.lines["cell"]["cell"], "seed": tee.lines["cell"]["seed"],
        "setup_s": setup_s, "metrics": metrics,
        "import_s": LEDGER.import_s,
        "installed_at_s": LEDGER.installed_at - t0,
        "window_call_at_s": cut - t0,
        "totals_at_window": totals,
        "phases": [{"phase": n, "wall_s": w, **h} for n, w, h in rows],
        "fit_calls": [{"at_s": c.start - t0, "s": c.end - c.start,
                       "steps": c.steps} for c in calls]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
