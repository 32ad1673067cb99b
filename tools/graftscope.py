"""graftscope CLI: traced capture + expected-vs-measured byte ledger.

    python -m tools.graftscope [--mesh 2x4] [--steps 10]
                               [--plane a2a+grouped] [--out trace.json]

Builds a virtual CPU mesh and makes the device bench round honest in
three moves (``openembedding_tpu/analysis/scope.py``):

1. **Expected bytes** — lower every registered plane's pull/push
   program exactly as the training path runs it and cost-account its
   collectives from the compiled HLO (the same numbers
   ``analysis/contracts.py`` bounds; each program is audited against
   its contract here too, so the printed bytes provably sit inside the
   enforced bounds).
2. **Measured spans** — run ``--steps`` eager pull/push dispatches per
   plane (compile warmed up outside the measured window) so every
   exchange lands in the graftscope latency histograms, then print the
   per-plane/per-stage table: calls, p50/p95 latency, expected
   collective bytes, achieved GB/s at the p50, and the program's
   expected per-device HBM peak (graftwatch memory ledger) — latency,
   bytes, and memory in one artifact.
3. **Traced train run** — ``--steps`` real ``Trainer.train_step`` calls
   on ``--plane`` (step spans, lookahead spans) captured into the span
   rings and written as Chrome-trace/Perfetto JSON (``--out``; open at
   https://ui.perfetto.dev).

Exit 0 when every contract holds, the trace round-trips as JSON, and
every plane recorded nonzero pull AND push spans — the CI smoke
invocation relies on that.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="span capture + per-plane byte ledger")
    ap.add_argument("--mesh", default="2x4",
                    help="DATAxMODEL virtual mesh shape (default 2x4)")
    ap.add_argument("--steps", type=int, default=10,
                    help="measured dispatches per plane/stage AND train "
                         "steps in the traced run")
    ap.add_argument("--plane", default="a2a",
                    help="plane for the traced train-step run; the "
                         "ledger always covers every registered plane")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--out", default="graftscope_trace.json",
                    help="Chrome-trace/Perfetto JSON output path")
    ap.add_argument("--skip-train", action="store_true",
                    help="skip the traced Trainer run (ledger only)")
    ap.add_argument("--export-stats", default="",
                    help="also dump the capture's observed-stats window "
                         "(per-table pull uniqueness/skew, serving "
                         "lookup sizes, cache + ingest counters) as "
                         "JSON in the tools/graftplan input schema")
    args = ap.parse_args(argv)
    data, model = (int(x) for x in args.mesh.split("x"))

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", data * model)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from openembedding_tpu.analysis import contracts, scope
    from openembedding_tpu.analysis import programs
    from openembedding_tpu.parallel.mesh import create_mesh, DATA_AXIS
    from openembedding_tpu.utils import observability

    mesh = create_mesh(data, model)
    scope.set_tracing(True)
    failures = 0

    planes = sorted({p for (p, prog) in contracts.REGISTRY
                     if prog in ("pull", "push")})

    # --- 1. expected bytes from compiled HLO (contract-audited) ------------
    expected = []
    for plane in planes:
        for program in ("pull", "push"):
            try:
                expected.append(scope.plane_expected_bytes(
                    mesh, plane, program, batch=args.batch, dim=args.dim))
            except Exception as e:  # noqa: BLE001 — report every program
                failures += 1
                print(f"FAIL expected-bytes {plane}/{program}: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
    print(f"expected bytes computed for {len(expected)} programs "
          f"(contract-audited against analysis/contracts.py bounds)")

    # --- 2. measured pull/push rounds per plane ----------------------------
    # build + warm every plane first (the dispatch program cache keys on
    # the evaluate_performance flag, so warmup must run with the SAME
    # flag as measurement), then drop the warmup samples and measure
    rng = np.random.RandomState(0)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    observability.set_evaluate_performance(True)

    def _vocab(plane: str) -> int:
        return (1 << 14) if plane == "a2a+grouped" else (1 << 16)

    def _batches(coll, vocab):
        names = tuple(coll.specs)
        idxs = {n: jax.device_put(
            jnp.asarray(rng.randint(0, vocab, size=args.batch)
                        .astype(np.int32)), sh) for n in names}
        grads = {n: jax.device_put(
            jnp.zeros((args.batch, args.dim), jnp.float32), sh)
            for n in names}
        return idxs, grads

    worlds = {}
    for plane in planes:
        vocab = _vocab(plane)
        if plane == "a2a+grouped":
            coll = programs._grouped_collection(
                mesh, tables=3, vocab=vocab, dim=args.dim, use_hash=False)
        else:
            coll = programs._collection(mesh, plane, vocab=vocab,
                                        dim=args.dim, use_hash=False)
        states = coll.init(jax.random.PRNGKey(0))
        idxs, grads = _batches(coll, vocab)
        jax.block_until_ready(coll.pull(states, idxs))       # compile pull
        states = coll.apply_gradients(states, idxs, grads)   # compile push
        jax.block_until_ready(jax.tree.leaves(states))
        worlds[plane] = (coll, states)
    scope.HISTOGRAMS.reset()     # drop compile-inclusive warmup samples
    scope.reset()
    window_t0 = time.perf_counter()   # stats window starts post-warmup

    for plane in planes:
        coll, states = worlds[plane]
        vocab = _vocab(plane)
        for _ in range(args.steps):
            idxs, grads = _batches(coll, vocab)
            coll.pull(states, idxs)      # plane_timed blocks + records
            states = coll.apply_gradients(states, idxs, grads)
        worlds[plane] = (coll, states)
    # evaluate_performance stays ON through the traced Trainer run so
    # record_batch_stats feeds the per-table distributions printed
    # below (the host-side stats run outside the jitted step)

    rows = scope.ledger_rows(expected)
    print()
    print(scope.format_ledger(rows))
    print()
    for r in rows:
        ops = ", ".join(f"{op}: {c}x/{b}B"
                        for op, (c, b) in sorted(r["per_op"].items()))
        print(f"  {r['plane']}/{r['stage']}: {ops or 'no collectives'}")

    for r in rows:
        if r["calls"] < args.steps:
            failures += 1
            print(f"FAIL {r['plane']}/{r['stage']}: {r['calls']} span(s) "
                  f"recorded < {args.steps} dispatched", file=sys.stderr)


    # --- 3. traced train-step run on --plane -------------------------------
    table_dims = {}
    if not args.skip_train:
        import optax
        from openembedding_tpu.embedding import EmbeddingCollection
        from openembedding_tpu.models import deepctr
        from openembedding_tpu.training import Trainer
        features = ("c0", "c1")
        vocab, dim, batch = 4096, 8, 256
        specs = deepctr.make_feature_specs(features, vocab, dim,
                                           plane=args.plane)
        table_dims = {s.name: s.output_dim for s in specs}
        coll = EmbeddingCollection(
            specs, mesh,
            default_optimizer={"category": "adagrad",
                               "learning_rate": 0.1})
        trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                          optax.adam(1e-2))
        brng = np.random.RandomState(1)
        batch_data = {
            "label": brng.randint(0, 2, size=batch).astype(np.float32),
            "dense": brng.randn(batch, 4).astype(np.float32),
            "sparse": {f: brng.randint(0, vocab, size=batch)
                       .astype(np.int32) for f in features},
        }
        for f in features:
            batch_data["sparse"][f + deepctr.LINEAR_SUFFIX] = \
                batch_data["sparse"][f]
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batch_data))
        for _ in range(args.steps):
            state, _metrics = trainer.train_step(state, batch_data)
        n = scope.HISTOGRAMS.count("span_step_seconds")
        p50 = scope.HISTOGRAMS.quantile("span_step_seconds", 0.5)
        p95 = scope.HISTOGRAMS.quantile("span_step_seconds", 0.95)
        print(f"\ntraced run ({args.plane}, deepfm, {args.steps} steps): "
              f"{n} step spans, p50 {p50 * 1e3:.1f} ms, "
              f"p95 {p95 * 1e3:.1f} ms (first step includes compile — "
              "deliberately kept: the trace should show it)")
        if n < args.steps:
            failures += 1
            print(f"FAIL traced run: {n} step spans < {args.steps}",
                  file=sys.stderr)
    observability.set_evaluate_performance(False)

    # batch-shape distribution series recorded this capture: the
    # per-table pull stats (traced run, evaluate_performance on) and —
    # when a serving path ran in-process — the per-variable serving
    # lookup-size histogram (ISSUE 11: the input the micro-batching
    # scheduler will be sized from)
    dist_names = ("pull_rows", "pull_unique_ratio", "pull_key_skew",
                  "serving_lookup_rows")
    dist = [(n, lb) for (n, lb) in scope.HISTOGRAMS.series()
            if n in dist_names]
    if dist:
        print("\ndistributions (count / p50 / p95):")
        for name, labels in dist:
            lab = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            print(f"  {name}{{{lab}}}: "
                  f"{scope.HISTOGRAMS.count(name, **labels)} / "
                  f"{scope.HISTOGRAMS.quantile(name, 0.5, **labels):.4g}"
                  f" / "
                  f"{scope.HISTOGRAMS.quantile(name, 0.95, **labels):.4g}")

    # --- observed-stats window export (tools/graftplan input) --------------
    if args.export_stats:
        from tools.graftwatch import device_fingerprint
        from openembedding_tpu.analysis import plan as plan_lib
        fp, device = device_fingerprint()
        window = plan_lib.collect_window(
            window_s=time.perf_counter() - window_t0,
            fingerprint=fp, device=device, table_dims=table_dims)
        problems = plan_lib.validate_window(window)
        if problems:
            failures += 1
            print("FAIL stats window does not validate against its own "
                  "schema: " + "; ".join(problems), file=sys.stderr)
        else:
            with open(args.export_stats, "w", encoding="utf-8") as f:
                json.dump(window, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote {args.export_stats}: stats window "
                  f"({len(window['tables'])} tables, "
                  f"{window['serving']['lookup_rows']['count']} serving "
                  f"lookups, fingerprint {fp})")

    # --- trace export + validation -----------------------------------------
    scope.export_chrome_trace(args.out)
    try:
        with open(args.out, "r", encoding="utf-8") as f:
            trace = json.load(f)
        n_events = sum(1 for e in trace["traceEvents"]
                       if e.get("ph") == "X")
        if n_events == 0:
            raise ValueError("trace has no span events")
        print(f"wrote {args.out}: {n_events} span events "
              f"(open in https://ui.perfetto.dev)")
    except Exception as e:  # noqa: BLE001 — a broken trace must fail CI
        failures += 1
        print(f"FAIL trace export: {type(e).__name__}: {e}",
              file=sys.stderr)

    if failures:
        print(f"graftscope: {failures} failure(s)", file=sys.stderr)
        return 1
    print("graftscope: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
