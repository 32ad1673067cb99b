"""graftfuzz CLI: differential fuzzing + sanitizer gate.

    python -m tools.graftfuzz --seed 0                 # one full sweep
    python -m tools.graftfuzz --seed 0 --iters 60      # longer run
    python -m tools.graftfuzz --lanes wire,ingest      # no native builds
    python -m tools.graftfuzz --regress                # pinned corpus
    python -m tools.graftfuzz --json out.json          # CI artifact

Fifth leg of the static-analysis gate (graftlint / graftrace /
graftcheck / graftproto / graftfuzz): where the first four reason about
the package's OWN code and models, this leg attacks the parsers that
consume bytes the package did not write — the native checkpoint reader
(under ASan AND UBSan builds, each probe contained in a subprocess),
the Python delta/checkpoint readers, the ``encode_delta`` wire codec
behind ``POST /models/<sign>/delta``, and the TFRecord/TSV ingest
framers. Structure-aware mutators (bit flips, truncations, zip
central-directory/local-header field surgery, manifest field fuzz,
wire-header fuzz, TFRecord length/crc corruption) run from a seeded
PRNG: **two runs with the same --seed produce byte-identical reports**
(no wall-clock, no absolute paths in the output).

Oracle = differential trichotomy: every reader must load-and-bit-agree,
refuse TYPED, or recover to the same documented version — never
SIGSEGV, never UB, never hang past --deadline, never an untyped Python
exception, never a silent Python-vs-native divergence.

Exit is nonzero on ANY violation OR any declared mutation class that
never fired (a run that looks green must actually have explored every
class — the graftproto no-hollow-exploration discipline). ``--regress``
instead replays the pinned corpus (tests/fixtures/fuzz_corpus.py):
known-bad shapes from PR 12 (crafted name_len / offset overflow),
graftchaos torn writes, compaction, codec refusals — each must produce
EXACTLY its pinned per-reader disposition under plain, ASan and UBSan
native builds.

Implementation lives in ``openembedding_tpu/analysis/fuzz.py``; this
wrapper only parses flags, prints the coverage table and sets exit
status. Unlike the other gate legs this one necessarily imports the
package (the Python probes ARE the system under test), so it pins
JAX_PLATFORMS=cpu before the first package import.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, _ROOT)

LANES = ("ckpt", "wire", "ingest")


def _print_coverage(report) -> None:
    classes = report["classes"]
    w = max(len(n) for n in classes) if classes else 10
    print(f"\n{'class':<{w}}  fired  viol  outcomes")
    for name in sorted(classes):
        c = classes[name]
        ocs = ", ".join(f"{k}x{v}" for k, v in sorted(c["outcomes"].items()))
        print(f"{name:<{w}}  {c['fired']:>5}  {c['violations']:>4}  {ocs}")
    if report["silent_classes"]:
        print(f"\nSILENT (never fired): "
              f"{', '.join(report['silent_classes'])}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="differential fuzzing over the untrusted-bytes "
                    "surface (checkpoint/delta/wire/ingest), native "
                    "probes under ASan+UBSan")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed; the whole run replays from it")
    ap.add_argument("--iters", type=int, default=None,
                    help="iterations (default: one per declared class; "
                         "classes fire round-robin, so >= the class "
                         "count guarantees full coverage)")
    ap.add_argument("--lanes", default="ckpt,wire,ingest",
                    help="comma-separated lane subset (ckpt,wire,ingest)")
    ap.add_argument("--deadline", type=float, default=30.0,
                    help="per-probe hang deadline in seconds")
    ap.add_argument("--regress", action="store_true",
                    help="replay the pinned regression corpus "
                         "(tests/fixtures/fuzz_corpus.py) instead of "
                         "fuzzing: every entry must produce exactly its "
                         "pinned per-reader disposition")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="write the full deterministic report as JSON "
                         "(the CI artifact)")
    ap.add_argument("--emit-corpus", default="", metavar="DIR",
                    help="also materialize every pinned corpus entry "
                         "as a mutated checkpoint dir under DIR (the "
                         "weekly CI corpus artifact)")
    args = ap.parse_args(argv)

    lanes = tuple(x for x in args.lanes.split(",") if x)
    bad_lanes = [x for x in lanes if x not in LANES]
    if bad_lanes or not lanes:
        print(f"graftfuzz: unknown lanes {bad_lanes} (have: {LANES})",
              file=sys.stderr)
        return 2

    from openembedding_tpu.analysis import fuzz

    if args.emit_corpus:
        import tempfile
        os.makedirs(args.emit_corpus, exist_ok=True)
        with tempfile.TemporaryDirectory(prefix="graftfuzz-seed-") as tmp:
            ctx = fuzz.SeedContext(os.path.join(tmp, "ctx"))
            for name in sorted(fuzz.CORPUS_BUILDERS):
                fuzz.build_corpus_dir(name, ctx, args.emit_corpus)
        print(f"graftfuzz: {len(fuzz.CORPUS_BUILDERS)} corpus dirs -> "
              f"{args.emit_corpus}")

    failed = 0
    if args.regress:
        import shutil
        import tempfile
        tmp = tempfile.mkdtemp(prefix="graftfuzz-regress-")
        try:
            ctx = fuzz.SeedContext(os.path.join(tmp, "ctx"))
            libs = fuzz.sanitizer_libs()
            report = fuzz.run_regress(ctx, libs, os.path.join(tmp, "w"),
                                      deadline=args.deadline, log=print)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        for f in report["failures"]:
            print(f"[{f['entry']}] {f['detail']}", file=sys.stderr)
        failed = len(report["failures"])
        print(f"graftfuzz --regress: {report['entries']} corpus entries, "
              f"{failed} disposition failure(s)")
    else:
        report = fuzz.run_fuzz(seed=args.seed, iters=args.iters,
                               lanes=lanes, deadline=args.deadline,
                               log=print)
        _print_coverage(report)
        for v in report["violations"]:
            print(f"[iter {v['iter']} {v['class']}] {v['detail']}",
                  file=sys.stderr)
        failed = len(report["violations"]) + len(report["silent_classes"])
        n_cls = len(report["classes"])
        print(f"\ngraftfuzz: seed {report['seed']}, "
              f"{report['iters']} iteration(s) over {n_cls} class(es) "
              f"[{','.join(report['lanes'])}], sanitizers "
              f"{report['sanitizers'] or ['-']}: "
              f"{len(report['violations'])} violation(s), "
              f"{len(report['silent_classes'])} silent class(es)")

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
        print(f"graftfuzz: gate report -> {args.json}")

    if failed:
        print(f"graftfuzz: {failed} failing check(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
