"""graftcheck CLI: per-plane compiled-program contract gate for CI.

    python -m tools.graftcheck [--mesh 2x4] [--batch 1024] [--dim 16]

Builds a virtual CPU mesh, lowers every registered plane's pull/push
program (array AND hash tables) plus the whole jitted train step, and
audits them against ``openembedding_tpu/analysis/contracts.py``:
collective inventory + byte bounds, no f64, no host transfers, step
donation honored — plus the graftwatch MEMORY ledger
(``analysis/memwatch.py``): every plane's compiled temp allocation
audited against the peak-temp-bytes contract at sizes where one table
shard dwarfs batch scratch. Exit 0 when every contract holds, 1 with
the first violation per program otherwise.

This is the compile-audit-time version of the scaling guarantee: a
sharding/plane regression fails HERE, on a laptop, instead of as a
silent 10x ICI blowup on a real mesh. ``tests/test_analysis_contracts.py``
runs the same registry inside the tier-1 lane.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="compiled-program contract gate")
    ap.add_argument("--mesh", default="2x4",
                    help="DATAxMODEL virtual mesh shape (default 2x4)")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--skip-step", action="store_true",
                    help="skip the (slower) whole-train-step audit")
    ap.add_argument("--skip-mem", action="store_true",
                    help="skip the graftwatch memory-ledger audit")
    args = ap.parse_args(argv)
    data, model = (int(x) for x in args.mesh.split("x"))

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", data * model)

    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.analysis import contracts, programs

    mesh = create_mesh(data, model)
    failures = 0

    def audit(label, fn):
        nonlocal failures
        try:
            summary = fn()
            print(f"ok   {label}: {summary}")
        except contracts.ContractViolation as e:
            failures += 1
            print(f"FAIL {label}: {e}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — a gate must report,
            # not die on the first broken lowering: the remaining
            # programs still get audited and the summary still prints
            failures += 1
            print(f"FAIL {label}: {type(e).__name__}: {e}",
                  file=sys.stderr)

    for plane in ("psum", "a2a", "a2a+cache"):
        for use_hash in (False, True):
            kind = "hash" if use_hash else "array"
            for prog, lower in (("pull", programs.lower_pull),
                                ("push", programs.lower_push)):
                def run(plane=plane, prog=prog, lower=lower,
                        use_hash=use_hash):
                    txt, params = lower(mesh, plane, batch=args.batch,
                                        dim=args.dim, use_hash=use_hash)
                    return contracts.check_program(txt, plane, prog,
                                                   **params)
                audit(f"{plane}/{prog} ({kind})", run)

    # compressed-exchange planes (parallel/precision.py): inventory
    # bounds at the WIRE itemsize plus the byte-halving ratio vs the
    # f32 a2a plane's compiled program — exchange collective bytes must
    # be <= 0.55x, measured on BOTH compiled HLOs, pull and push
    # separately. Audited at dim 64 where the ratio binds (keys/counts
    # stay int32, so the ratio asymptotes to 0.5 from above as dim
    # grows; at the default dim 16 the int32 legs alone push bf16 past
    # 0.55 — the contract pins the audit shape, see contracts.py).
    COMPRESSED_DIM = 64
    for use_hash in (False, True):
        kind = "hash" if use_hash else "array"
        baselines = {}
        for prog, lower in (("pull", programs.lower_pull),
                            ("push", programs.lower_push)):
            try:
                baselines[prog], _ = lower(mesh, "a2a", batch=args.batch,
                                           dim=COMPRESSED_DIM,
                                           use_hash=use_hash)
            except Exception as e:  # noqa: BLE001 — keep auditing
                failures += 1
                print(f"FAIL a2a baseline {prog} ({kind}, dim "
                      f"{COMPRESSED_DIM}): {type(e).__name__}: {e}",
                      file=sys.stderr)
        for plane in ("a2a+bf16", "a2a+int8"):
            for prog, lower in (("pull", programs.lower_pull),
                                ("push", programs.lower_push)):
                if prog not in baselines:
                    continue

                def run(plane=plane, prog=prog, lower=lower,
                        use_hash=use_hash):
                    txt, params = lower(mesh, plane, batch=args.batch,
                                        dim=COMPRESSED_DIM,
                                        use_hash=use_hash)
                    res = contracts.check_compressed_program(
                        txt, baselines[prog], plane, prog, **params)
                    return (f"exchange {res['exchange_bytes']}B = "
                            f"{res['ratio']:.3f}x f32 "
                            f"(<= {res['max_ratio']:.2f})")
                audit(f"{plane}/{prog} ({kind}, byte-halving vs a2a)",
                      run)

    # grouped plane: collection-level lowering over 3 heterogeneous
    # same-dim tables (one exchange group) — the contract caps the
    # all-to-all launch count at num_groups * per-exchange ops, which a
    # per-table-loop regression (3x the ops) fails
    for use_hash in (False, True):
        kind = "hash" if use_hash else "array"
        for prog, lower in (("pull", programs.lower_grouped_pull),
                            ("push", programs.lower_grouped_push)):
            def run(prog=prog, lower=lower, use_hash=use_hash):
                txt, params = lower(mesh, tables=3, batch=args.batch,
                                    dim=args.dim, use_hash=use_hash)
                return contracts.check_program(txt, "a2a+grouped", prog,
                                               **params)
            audit(f"a2a+grouped/{prog} ({kind}, 3 tables)", run)

    # graftplan cost audit: every registered PlaneSpec's DECLARED
    # exchange bytes (analysis/contracts.py cost registry) against the
    # compiled HLO's actual collective bytes, within
    # COST_MODEL_TOLERANCE. Audited at batch >= 512 — the regime the
    # closed forms are calibrated in (below it XLA elides the
    # residue/overflow legs and the additive terms drift, see the
    # registry comment) on the 1 x N layout where the exchange spans
    # every device — mixed data-parallel layouts split the per-device
    # bytes differently, which is a property of the LAYOUT, not the
    # plane, and the planner only consumes the plane ranking. A stale
    # or wrong declaration fails HERE, so the offline planner can
    # never rank planes off fiction.
    cost_batch = max(args.batch, 512)
    cost_mesh = create_mesh(1, data * model)
    for plane in sorted(contracts.PLANE_SPECS):
        if plane == "a2a+grouped":
            lowers = (("pull", programs.lower_grouped_pull),
                      ("push", programs.lower_grouped_push))
        else:
            lowers = (("pull", programs.lower_pull),
                      ("push", programs.lower_push))
        for prog, lower in lowers:
            def run(plane=plane, prog=prog, lower=lower):
                if plane == "a2a+grouped":
                    txt, params = lower(cost_mesh, tables=3,
                                        batch=cost_batch,
                                        dim=args.dim, use_hash=False)
                else:
                    txt, params = lower(cost_mesh, plane,
                                        batch=cost_batch,
                                        dim=args.dim, use_hash=False)
                res = contracts.check_cost_model(txt, plane, prog,
                                                 params)
                return (f"declared {res['declared']}B vs HLO "
                        f"{res['actual']}B (err "
                        f"{res['rel_err'] * 100:.1f}% <= "
                        f"{res['tolerance'] * 100:.0f}%)")
            audit(f"{plane}/{prog} (graftplan cost model)", run)

    # graftwatch memory ledger: peak-temp contract per plane at the
    # calibrated audit sizes (memwatch.AUDIT_*, deliberately independent
    # of --batch: detection power needs the table shard to dwarf batch
    # scratch, exactly like the step audit's copy bound below)
    if not args.skip_mem:
        from openembedding_tpu.analysis import memwatch

        def run_mem():
            rows = memwatch.memory_ledger(mesh)
            print(memwatch.format_memory_table(rows))
            missing = [f"{r.plane}/{r.program}" for r in rows
                       if r.mem is None]
            if missing:
                raise RuntimeError(
                    f"no compiled memory analysis for {missing} — the "
                    "backend stopped exposing memory_analysis(); the "
                    "ledger (and every HBM claim downstream) is blind")
            return f"{len(rows)} programs, peak-temp bounds hold"
        audit("memory ledger (all planes, peak-temp contract)", run_mem)

    if not args.skip_step:
        def run_step():
            # vocab/dim sized so each table shard dwarfs every dense
            # buffer: a copy at/above shard size can only be a table
            # that lost its donation (see contracts.max_copy_bytes)
            vocab, dim = 1 << 16, 16
            txt, params = programs.lower_train_step(mesh, "a2a",
                                                    vocab=vocab, dim=dim,
                                                    batch=args.batch // 4)
            summary = contracts.check_program(txt, "any", "step",
                                              **params)
            shard_bytes = vocab * dim * 4 // mesh.size
            worst = contracts.max_copy_bytes(txt)
            if worst >= shard_bytes:
                raise contracts.ContractViolation(
                    f"step program copies a {worst}-byte buffer >= table "
                    f"shard size {shard_bytes} — donation silently "
                    "declined for a table")
            return summary
        audit("any/step (deepfm, a2a)", run_step)

    if failures:
        print(f"graftcheck: {failures} contract violation(s)",
              file=sys.stderr)
        return 1
    print("graftcheck: all contracts hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
