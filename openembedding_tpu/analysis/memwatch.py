"""graftwatch memory ledger: per-plane compiled-program memory audits.

graftscope (``scope.py``) made latency and collective bytes observable;
this module covers the third cost axis — memory. Every registered
plane's pull/push program is lowered exactly as the training path runs
it (:mod:`.programs` ``compile_*``) and its XLA memory analysis is
extracted through ``utils.jaxcompat.compiled_memory_stats`` (None on
a backend that reports no analysis): per-device argument / output / temp / alias
bytes, plus the derived peak estimate. Two consumers:

* **The peak-temp contract** (:func:`..analysis.contracts.
  check_peak_temp_bytes`): compiled temp must stay batch-scale scratch
  (pull) plus at most one declined-donation state materialization
  (push). This catches what the HLO-text ``copy`` audit cannot —
  XLA materializations that never appear as an explicit ``copy`` op
  (fusion outputs, gather results) still land in the temp allocation.
  Enforced by ``python -m tools.graftcheck`` per plane.
* **The bench trajectory** (``tools/graftwatch.py``): every recorded
  run carries its planes' memory-ledger numbers, so an HBM regression
  (a new buffer the size of a table shard) is diffable across PRs like
  a latency regression.

Audit sizing: like ``max_copy_bytes``, detection power needs the table
shard to dwarf batch scratch — the default audit sizes below put one
weights shard at 8 MiB against ~1 MiB of scratch, so a single stray
shard-sized materialization busts the bound instead of hiding in slack.

Import discipline: jax only inside functions (this module is lazy in
``analysis.__init__`` next to ``programs``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping, Optional, Tuple

# audit sizes: weights shard = vocab*dim*4/8 = 8 MiB (array planes),
# 4 MiB per grouped member table — both >> the ~1 MiB batch scratch at
# batch 512, so the peak-temp bound detects one extra shard
AUDIT_VOCAB = 1 << 20
AUDIT_GROUPED_VOCAB = 1 << 19
AUDIT_BATCH = 512
AUDIT_DIM = 16


@dataclasses.dataclass(frozen=True)
class MemoryRow:
    """One plane program's per-device compiled-memory ledger entry."""

    plane: str
    program: str                       # "pull" | "push"
    kind: str                          # "array" | "hash"
    mem: Optional[Mapping[str, int]]   # compiled_memory_stats dict or None
    params: Mapping[str, int]
    temp_bound: Optional[int] = None   # the enforced peak-temp cap

    def as_dict(self) -> Dict[str, Any]:
        out = {"plane": self.plane, "program": self.program,
               "kind": self.kind, "temp_bound": self.temp_bound}
        out.update(self.mem or {})
        return out


def plane_memory(mesh, plane: str, program: str, *,
                 batch: int = AUDIT_BATCH, dim: int = AUDIT_DIM,
                 vocab: Optional[int] = None, use_hash: bool = False,
                 tables: int = 3, check: bool = True) -> MemoryRow:
    """Memory-ledger row for one plane program on ``mesh``.

    ``check=True`` enforces the peak-temp contract
    (:class:`..analysis.contracts.ContractViolation` on breach); rows
    whose backend exposes no memory analysis carry ``mem=None`` and are
    never audited (absence of data is reported, not punished).
    """
    from . import contracts, programs
    from ..utils import jaxcompat
    if plane == "a2a+grouped":
        build = (programs.compile_grouped_pull if program == "pull"
                 else programs.compile_grouped_push)
        compiled, params = build(
            mesh, tables=tables, vocab=vocab or AUDIT_GROUPED_VOCAB,
            batch=batch, dim=dim, use_hash=use_hash)
    else:
        build = (programs.compile_pull if program == "pull"
                 else programs.compile_push)
        compiled, params = build(
            mesh, plane, vocab=vocab or AUDIT_VOCAB, batch=batch,
            dim=dim, use_hash=use_hash)
    mem = jaxcompat.compiled_memory_stats(compiled)
    bound = None
    if mem is not None:
        if check:
            bound = contracts.check_peak_temp_bytes(
                mem, params, program=program,
                label=f"{plane}/{program} ({'hash' if use_hash else 'array'})")
        else:
            bound = contracts.peak_temp_bound(
                params, program, int(mem.get("alias_bytes", 0)))
    return MemoryRow(plane=plane, program=program,
                     kind="hash" if use_hash else "array", mem=mem,
                     params=params, temp_bound=bound)


def registered_planes() -> List[str]:
    """Planes with a pull/push contract in the registry — the coverage
    set the graftcheck/graftwatch memory audits iterate."""
    from . import contracts
    return sorted({p for (p, prog) in contracts.REGISTRY
                   if prog in ("pull", "push")})


def memory_ledger(mesh, *, batch: int = AUDIT_BATCH, dim: int = AUDIT_DIM,
                  planes: Optional[Tuple[str, ...]] = None,
                  check: bool = True) -> List[MemoryRow]:
    """Memory rows for every registered plane's pull AND push (array
    tables; the a2a plane additionally in its hash form — hash scratch
    shapes differ enough to audit separately). Raises on the first
    contract breach when ``check``; lowering errors propagate (a plane
    whose ledger cannot be produced must fail the gate, same contract
    as the span coverage check in graftscope)."""
    rows = []
    for plane in (planes or registered_planes()):
        for program in ("pull", "push"):
            rows.append(plane_memory(mesh, plane, program, batch=batch,
                                     dim=dim, check=check))
            if plane == "a2a":
                rows.append(plane_memory(mesh, plane, program,
                                         batch=batch, dim=dim,
                                         use_hash=True, check=check))
    return rows


def format_memory_table(rows: List[MemoryRow]) -> str:
    """Fixed-width ledger table (MiB) for terminals and CI logs."""
    head = (f"{'plane':<14}{'stage':<7}{'kind':<7}{'arg_MiB':>9}"
            f"{'out_MiB':>9}{'temp_MiB':>9}{'alias_MiB':>10}"
            f"{'peak_MiB':>9}{'temp_cap':>9}")
    out = [head, "-" * len(head)]

    def mib(v) -> str:
        return f"{v / (1 << 20):.2f}" if v is not None else "n/a"

    for r in rows:
        m = r.mem or {}
        out.append(
            f"{r.plane:<14}{r.program:<7}{r.kind:<7}"
            f"{mib(m.get('argument_bytes')):>9}"
            f"{mib(m.get('output_bytes')):>9}"
            f"{mib(m.get('temp_bytes')):>9}"
            f"{mib(m.get('alias_bytes')):>10}"
            f"{mib(m.get('peak_bytes')):>9}"
            f"{mib(r.temp_bound):>9}")
    return "\n".join(out)
