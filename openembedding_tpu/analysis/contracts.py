"""Compiled-program contract registry: per-plane HLO audits.

The framework's core guarantee is structural, not numerical: per-device
ICI bytes on the a2a planes scale as O(slack * batch_slice * dim), never
O(global_batch * dim) or O(table) (SURVEY §1; the reference's
exchange-not-broadcast design, EmbeddingPullOperator.cpp:60-112). That
property lives in the COMPILED program — a sharding-annotation regression
shows up as an oversized ``all-gather`` in the pull HLO long before it
shows up as a 10x ICI blowup on a real mesh. This module generalizes the
original ``utils/hlocheck.py`` (still re-exported there) into a
declarative registry: each (plane, program) pair declares its expected
collective inventory and byte bounds, checked against compiled HLO text.

Cross-cutting audits (any program):

* :func:`check_no_f64` — no ``f64`` op anywhere (an x64 leak doubles
  every table byte and halves MXU throughput);
* :func:`check_donation` — the step program's ``input_output_alias``
  header actually aliases the donated table buffers;
* :func:`max_copy_bytes` — no full-table ``copy`` op (donation that XLA
  silently declined);
* :func:`check_no_host_transfers` — no infeed/outfeed/host-callback
  custom-calls inside the jitted step (the hot-cache admission sketch
  and the observability accumulators must stay host-side; a stray
  callback stalls TPU pipelining every step).

Byte semantics follow hlocheck: bounds apply to the largest SINGLE
buffer of a collective (async ``-start`` tuples carry operand AND result
buffers — summing would double-count), ops inside a ``while`` body count
once (static program size), and ``-done`` ops are skipped (their result
aliases the ``-start`` tuple).

This module imports only the stdlib so every other module (including
``parallel/*``) can import it without cycles.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}

_COLLECTIVES = ("all-to-all", "all-gather", "all-reduce",
                "collective-permute", "reduce-scatter")

# post-optimization TPU HLO splits collectives into async -start/-done
# pairs (`%x = (...) all-gather-start(...)`); match either form under the
# base name, and skip -done ops (their result aliases the -start tuple —
# counting both would double every byte)
_OP_RE = re.compile(
    r"= (?P<type>.*?) (?P<op>" + "|".join(_COLLECTIVES)
    + r")(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")

# the one legitimate all-gather in a pull program re-assembles each data
# slice's pulled rows on its model-axis peers; the partitioner may pad
# the gathered dim, so bounds carry this slack factor
ROW_ASSEMBLY_SLACK = 1.0625


class ContractViolation(AssertionError):
    """A compiled program broke its plane's declared contract."""


# --- HLO text parsing (absorbed from utils/hlocheck.py) ----------------------

def _type_bytes(type_str: str) -> Tuple[int, int]:
    """(total bytes, largest single buffer bytes) of one HLO type string."""
    total = largest = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES[dtype]
        total += b
        largest = max(largest, b)
    return total, largest


def collect_collectives(hlo_text: str) -> List[Tuple[str, int, int]]:
    """Collective ops in a compiled HLO dump as (op, bytes, max_buffer).

    ``bytes`` sums the result type's buffers (all-to-all emits one per
    peer); ``max_buffer`` is the largest SINGLE buffer — the size-bound
    checks use it because async -start tuples carry operand AND result
    buffers (summing would double-count). Ops inside a ``while`` body are
    counted once (static program size): per-invocation shapes, not
    dynamic step totals — exactly what the scaling contract is about.
    """
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m and m.group("suffix") != "-done":
            total, largest = _type_bytes(m.group("type"))
            out.append((m.group("op"), total, largest))
    return out


EXCHANGE_BYTE_OPS = ("all-to-all", "all-gather")
# the compressed-exchange promise (ROADMAP item 6): a bf16/int8 plane's
# exchange collectives move at most this fraction of the f32 plane's
# bytes — asserted against BOTH compiled programs, not computed from a
# formula, so partitioner padding/decomposition drift cannot fake it
COMPRESSED_BYTE_RATIO = 0.55


def exchange_collective_bytes(hlo_text: str,
                              ops: Tuple[str, ...] = EXCHANGE_BYTE_OPS
                              ) -> int:
    """Total exchange bytes of one compiled program: the sum over every
    ``ops`` collective instance of its largest single buffer (the
    async-safe accounting summarize/largest uses — ``-start`` tuples
    carry operand AND result). This is the quantity the byte-halving
    contract compares between a compressed plane and its f32 baseline;
    scalar all-reduces (residue-loop counts) are excluded by default."""
    return sum(big for op, _total, big in collect_collectives(hlo_text)
               if op in ops)


def check_byte_halving(compressed_hlo: str, baseline_hlo: str, *,
                       ratio: float = COMPRESSED_BYTE_RATIO,
                       label: str = "") -> Tuple[int, int]:
    """Enforce compressed exchange bytes <= ratio * f32 exchange bytes.

    Both arguments are compiled HLO text of the SAME program shape
    (same mesh/batch/dim — the callers lower them side by side).
    Returns (compressed_bytes, baseline_bytes); raises
    :class:`ContractViolation` when the claimed halving is not in the
    compiled program — including when the "compressed" program is
    secretly the f32 one (ratio 1.0), the negative the tests pin.
    """
    where = f"{label}: " if label else ""
    got = exchange_collective_bytes(compressed_hlo)
    base = exchange_collective_bytes(baseline_hlo)
    if base <= 0:
        raise ContractViolation(
            f"{where}baseline f32 program has no exchange collectives — "
            "nothing to compare the compressed plane against")
    if got > ratio * base:
        raise ContractViolation(
            f"{where}compressed exchange moves {got} bytes > "
            f"{ratio:.2f} x f32 baseline {base} bytes "
            f"(ratio {got / base:.3f}) — the wire is NOT compressed "
            "(rows crossing the exchange at full precision?)")
    return got, base


def summarize(hlo_text: str, *,
              largest: bool = False) -> Dict[str, Tuple[int, int]]:
    """op -> (count, bytes). Default bytes sum every result buffer;
    ``largest=True`` sums each instance's LARGEST single buffer instead —
    the async-safe accounting (``-start`` tuples carry operand AND
    result) shared by the contract byte bounds and the graftscope
    ledger. One fold so the accounting rule lives in one place."""
    out: Dict[str, Tuple[int, int]] = {}
    for op, b, big in collect_collectives(hlo_text):
        c, t = out.get(op, (0, 0))
        out[op] = (c + 1, t + (big if largest else b))
    return out


# --- cross-cutting audits ----------------------------------------------------

def find_f64(hlo_text: str) -> List[str]:
    """Lines carrying an f64 buffer — an x64 leak into the compiled plane."""
    return [ln.strip() for ln in hlo_text.splitlines() if "f64[" in ln]


def check_no_f64(hlo_text: str) -> None:
    bad = find_f64(hlo_text)
    if bad:
        raise ContractViolation(
            f"{len(bad)} f64 op(s) in the compiled program (x64 leak) — "
            f"first: {bad[0][:200]}")


_ALIAS_RE = re.compile(r"\((\d+),\s*\{[^}]*\},\s*(?:may|must)-alias\)")


def donated_params(hlo_text: str) -> Tuple[int, ...]:
    """Parameter numbers the ``input_output_alias`` header aliases.

    Donation declared at the jit boundary is a *request*; the header in
    the post-optimization module is what XLA actually honored.
    """
    header = hlo_text.splitlines()[0] if hlo_text else ""
    m = re.search(r"input_output_alias=\{(.*?)\},\s*\w+=", header)
    blob = m.group(1) if m else header
    return tuple(sorted({int(p) for p in _ALIAS_RE.findall(blob)}))


def check_donation(hlo_text: str, min_aliased: int = 1) -> Tuple[int, ...]:
    """The compiled module aliases at least ``min_aliased`` inputs to
    outputs (table buffers updated in place, not copied per step)."""
    aliased = donated_params(hlo_text)
    if len(aliased) < min_aliased:
        raise ContractViolation(
            f"input_output_alias covers {len(aliased)} parameter(s) "
            f"({aliased}) < required {min_aliased} — donation of the "
            "table/state buffers was declined or never declared")
    return aliased


# the type is captured lazily like _OP_RE: async copy-start (and TPU
# send/recv/infeed below) carry TUPLE result types with spaces — a \S+
# capture would silently skip exactly the ops these audits exist for
_COPY_RE = re.compile(r"= (?P<type>.*?) copy(?:-start)?\(")


def max_copy_bytes(hlo_text: str) -> int:
    """Largest single ``copy`` result buffer (0 if the program has none).

    A copy the size of a table shard means XLA materialized a second
    table per step — donation silently declined. The backend may insert
    legitimate large copies of REPLICATED buffers (dense params), so
    callers enforce ``max_copy_bytes(txt) < table_shard_bytes`` with a
    model sized so table shards dominate every dense buffer
    (``tests/test_analysis_contracts.py::test_train_step_contract`` and
    the ``tools/graftcheck.py`` step audit both do).
    """
    worst = 0
    for line in hlo_text.splitlines():
        m = _COPY_RE.search(line)
        if m:
            _total, largest = _type_bytes(m.group("type"))
            worst = max(worst, largest)
    return worst


_HOST_TRANSFER_RE = re.compile(
    r"= .*? (infeed|outfeed|send|send-done|recv|recv-done)\(")


def host_transfer_ops(hlo_text: str) -> List[str]:
    """Host<->device transfer ops inside the program: infeed/outfeed,
    HOST-side send/recv, and host-callback custom-calls
    (jax.debug.callback / io_callback lower to
    ``custom_call_target="xla_python_cpu_callback"`` and friends).

    send/recv are also device-to-device channel ops (SPMD partitioners
    decompose collective-permute into them), so those two only count
    when the op carries ``is_host_transfer=true``.
    """
    out = []
    for line in hlo_text.splitlines():
        m = _HOST_TRANSFER_RE.search(line)
        if m:
            op = m.group(1)
            if op.startswith(("send", "recv")) \
                    and "is_host_transfer=true" not in line:
                continue
            out.append(op)
            continue
        if "custom-call" in line and re.search(
                r'custom_call_target="[^"]*(callback|host)[^"]*"', line):
            out.append("host-callback")
    return out


def check_no_host_transfers(hlo_text: str) -> None:
    ops = host_transfer_ops(hlo_text)
    if ops:
        raise ContractViolation(
            f"compiled program contains host transfer op(s) {ops[:4]} — "
            "host state (admission sketches, counters) must stay outside "
            "the jitted step; a per-step callback stalls device "
            "pipelining")


# --- HLO def-use parsing -----------------------------------------------------

# attributes whose %refs name CALLED COMPUTATIONS, not data operands
_CALL_ATTRS = ("calls", "to_apply", "body", "condition",
               "branch_computations", "called_computations")
_CALL_ATTR_RE = re.compile(
    r"(?:" + "|".join(_CALL_ATTRS) + r")=(\{[^}]*\}|%[\w.\-]+)")
_REF_RE = re.compile(r"%([\w.\-]+)")
_CTRL_RE = re.compile(r"control-predecessors=\{([^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_COMP_HDR_RE = re.compile(r"^\s*(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)"
                          r"\s*\(.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
                       r"(?P<rest>.+)$")


@dataclasses.dataclass(frozen=True)
class HloInstr:
    """One parsed instruction: data operands, called computations, its
    opcode and trace scope — enough for class-level reachability."""

    name: str
    opcode: str
    operands: Tuple[str, ...]
    calls: Tuple[str, ...]
    line_no: int
    op_name: str = ""                # metadata trace path (may be "")


def _split_instr(rest: str) -> Tuple[str, str, str]:
    """(opcode, operand_blob, attr_blob) of an instruction's RHS.

    The RHS is ``<type> <opcode>(<operands>), <attrs>`` where the type
    may be a parenthesized tuple — skip it by balance, then take the
    first identifier followed by ``(``.
    """
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    i += 1
                    break
    m = re.search(r"([a-z][\w\-]*)\(", rest[i:])
    if not m:
        return "", "", rest
    opcode = m.group(1)
    start = i + m.end()          # first char after the opening paren
    depth = 1
    j = start
    while j < len(rest) and depth:
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
        j += 1
    return opcode, rest[start:j - 1], rest[j:]


def parse_hlo_computations(hlo_text: str
                           ) -> Tuple[str, Dict[str, List[HloInstr]]]:
    """(entry_name, computation -> instructions) of one HLO module."""
    comps: Dict[str, List[HloInstr]] = {}
    entry = ""
    current: Optional[List[HloInstr]] = None
    for ln, line in enumerate(hlo_text.splitlines()):
        hdr = _COMP_HDR_RE.match(line)
        if hdr and "=" not in line.split("(")[0]:
            comps[hdr.group("name")] = current = []
            if hdr.group("entry"):
                entry = hdr.group("name")
            continue
        if current is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        opcode, operand_blob, attr_blob = _split_instr(m.group("rest"))
        if not opcode:
            continue
        calls = []
        for blob in _CALL_ATTR_RE.findall(m.group("rest")):
            calls.extend(_REF_RE.findall(blob))
        operands = [r for r in _REF_RE.findall(operand_blob)
                    if r not in calls]
        ctrl = _CTRL_RE.search(attr_blob)
        if ctrl:
            operands.extend(_REF_RE.findall(ctrl.group(1)))
        meta = _OP_NAME_RE.search(attr_blob)
        current.append(HloInstr(name=m.group("name"), opcode=opcode,
                                operands=tuple(operands),
                                calls=tuple(calls), line_no=ln,
                                op_name=meta.group(1) if meta else ""))
    return entry, comps


# --- peak-temp-bytes audit (the memory-level copy check) ---------------------

# calibrated against the shipped planes on the cpu8 mesh (graftwatch
# memory ledger, vocab sized so a table shard dwarfs batch scratch):
# batch scratch covers index widening / sort perms / routed buckets
# (scales with the stream AND the shard count on the owner-dispatch
# paths), the state term covers the one legitimate state materialization
# a DECLINED donation forces (CPU never aliases; on TPU alias_bytes
# covers the state and the term collapses)
TEMP_FLOOR_BYTES = 1 << 18
TEMP_BATCH_FACTOR = 2
TEMP_STATE_SLACK = 1.1


def peak_temp_bound(params: Mapping[str, int], program: str,
                    alias_bytes: int = 0) -> int:
    """Allowed compiled temp bytes for one plane program.

    Pull programs are read-only: temp must stay batch-scale scratch. A
    push program whose donation the backend declined legitimately
    materializes the updated state once in temp — that is the
    ``state_shard_bytes - alias_bytes`` term. Anything beyond is an
    accidental extra materialization (a table-shard-sized gather or a
    second state copy) — the memory-level twin of :func:`max_copy_bytes`.
    Like that audit, detection power depends on the harness sizing the
    table so one shard dwarfs batch scratch (``memwatch.AUDIT_VOCAB``).
    """
    unit = int(params["global_batch"]) * (int(params["dim"]) + 2) \
        * int(params.get("itemsize", 4)) \
        * int(params.get("num_shards", 1))
    bound = TEMP_FLOOR_BYTES + TEMP_BATCH_FACTOR * unit
    if program != "pull":
        unaliased = max(0, int(params.get("state_shard_bytes", 0))
                        - int(alias_bytes))
        bound += int(TEMP_STATE_SLACK * unaliased)
    return bound


def check_peak_temp_bytes(mem: Mapping[str, int], params: Mapping[str, int],
                          *, program: str, label: str = "") -> int:
    """Audit one compiled program's ``memory_analysis`` temp bytes
    against :func:`peak_temp_bound`; returns the bound. ``mem`` is the
    normalized dict from ``utils.jaxcompat.compiled_memory_stats``.
    Complements :func:`max_copy_bytes`: a materialization XLA performs
    without an explicit ``copy`` op (fusion output buffers, gather
    results) never shows in the HLO-text audit but always lands in
    temp."""
    temp = int(mem.get("temp_bytes", 0))
    bound = peak_temp_bound(params, program,
                            int(mem.get("alias_bytes", 0)))
    if temp > bound:
        raise ContractViolation(
            f"{label or program}: compiled temp allocation of {temp} "
            f"bytes > peak-temp bound {bound} (params {dict(params)}, "
            f"alias_bytes={mem.get('alias_bytes', 0)}) — an accidental "
            "table-shard-sized materialization (or a second state copy) "
            "is live inside the program")
    return bound


# --- the per-plane registry --------------------------------------------------

# A bound is a function of the program's static parameters. Every bound
# receives the same params dict; the keys each plane consumes:
#   batch_slice  entries per data-axis slice (global_batch / data axis)
#   global_batch entries in the whole batch
#   dim          embedding dim
#   itemsize     row element bytes (4 for f32)
#   cache_k      hot-row replica slots ("a2a+cache" only)
#   num_shards   table shards (= mesh size on the a2a planes)
Bound = Callable[[Mapping[str, int]], int]


def _row_assembly(p: Mapping[str, int]) -> int:
    # each data slice's pulled rows returned to its model-axis peers
    return int(p["batch_slice"] * p["dim"] * p["itemsize"]
               * ROW_ASSEMBLY_SLACK)


def _wire(p: Mapping[str, int]) -> int:
    # per-element bytes of ROW/GRAD payload on the wire: the compressed
    # planes' params carry wire_itemsize (2 = bf16, 1 = int8); absent
    # (uncompressed planes) it equals the storage itemsize
    return int(p.get("wire_itemsize", p["itemsize"]))


def _row_assembly_wire(p: Mapping[str, int]) -> int:
    # compressed pull: the row-assembly gather moves WIRE-dtype rows
    return int(p["batch_slice"] * p["dim"] * _wire(p)
               * ROW_ASSEMBLY_SLACK)


def _global_prereduce_wire(p: Mapping[str, int]) -> int:
    # compressed push overflow fallback: grads gather at wire width,
    # keys/scales/counts gather as separate int32/pair buffers — the
    # +8 covers the widest of those per entry
    return int(p["global_batch"] * (p["dim"] * _wire(p) + 8)
               * ROW_ASSEMBLY_SLACK)


def _global_prereduce(p: Mapping[str, int]) -> int:
    # the push overflow fallback all_gathers every peer's pre-reduced
    # slice: O(global_batch * dim) — paid only when structured key skew
    # overflows the routed buckets, but the branch is compiled in
    return int(p["global_batch"] * (p["dim"] + 2) * p["itemsize"]
               * ROW_ASSEMBLY_SLACK)


def _cache_psum(p: Mapping[str, int]) -> int:
    # the K-row (grad sum, count) merge — O(cache_k * dim), batch-free
    return int((p["cache_k"] + 1) * (p["dim"] + 1) * p["itemsize"]
               * ROW_ASSEMBLY_SLACK)


def _scalar(p: Mapping[str, int]) -> int:
    # residue-loop pending counts / overflow flags: a few scalars
    return 256


def _batch_rows(p: Mapping[str, int]) -> int:
    # psum-plane pull: rows for this device's batch slice, psum'd over
    # the model axis — the plane's O(batch_slice * dim) broadcast cost
    return int(p["batch_slice"] * (p["dim"] + 1) * p["itemsize"]
               * ROW_ASSEMBLY_SLACK)


def _global_batch_rows(p: Mapping[str, int]) -> int:
    # psum-plane push: the full global batch gathered to every shard —
    # the O(global_batch * dim) signature the a2a plane exists to kill
    return int(p["global_batch"] * (p["dim"] + 2) * p["itemsize"]
               * ROW_ASSEMBLY_SLACK)


def _grouped_a2a_ops(p: Mapping[str, int]) -> int:
    # THE grouped-plane claim: the collective launch count is
    # O(#groups), not O(#tables). ``a2a_ops_per_exchange`` is counted
    # empirically from a single-table a2a program on the same mesh
    # (programs.count_exchange_a2a) — a per-table loop would compile
    # num_tables * that many all-to-alls and fail this cap.
    return int(p["num_groups"] * p["a2a_ops_per_exchange"])


def _grouped_row_assembly(p: Mapping[str, int]) -> int:
    # grouped pull re-assembly: the concatenated stream carries every
    # member table's entries at the group's padded bucket dim
    return int(p["num_tables"] * p["batch_slice"] * p["dim_bucket"]
               * p["itemsize"] * ROW_ASSEMBLY_SLACK)


def _grouped_prereduce(p: Mapping[str, int]) -> int:
    # grouped push overflow fallback: every peer's pre-reduced
    # concatenated slice — entries gain up to 3 key words (lo, hi, tag)
    # next to the padded-dim grad row
    return int(p["num_tables"] * p["global_batch"] * (p["dim_bucket"] + 4)
               * p["itemsize"] * ROW_ASSEMBLY_SLACK)


@dataclasses.dataclass(frozen=True)
class OpBudget:
    """Inventory entry for one collective op within one program."""

    min_count: int = 0
    # static cap, or a Bound of the program params (the grouped plane's
    # cap is num_groups * per-exchange ops — param-dependent)
    max_count: Optional[Any] = None
    max_buffer: Optional[Bound] = None   # bound on the largest single buffer
    # bound on the SUMMED bytes across all ops of this type: catches a
    # regression that splits O(global) traffic into many small buffers
    # (e.g. one per-table gather each below the single-buffer bound)
    max_total: Optional[Bound] = None


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    """Declarative contract for one (plane, program) compiled HLO."""

    plane: str
    program: str                      # "pull" | "push" | "step"
    ops: Mapping[str, OpBudget] = dataclasses.field(default_factory=dict)
    forbid: Tuple[str, ...] = ()
    no_f64: bool = True
    no_host_transfers: bool = True
    min_aliased: int = 0              # donation floor (step programs)
    # compressed planes: exchange bytes <= byte_ratio x the baseline
    # plane's compiled program (enforced by check_compressed_program,
    # which needs BOTH HLO texts; check() alone cannot see the baseline)
    baseline_plane: Optional[str] = None
    byte_ratio: Optional[float] = None

    def check(self, hlo_text: str,
              params: Mapping[str, int]) -> Dict[str, Tuple[int, int]]:
        """Audit ``hlo_text`` against this contract; returns the
        collective summary. Raises :class:`ContractViolation`."""
        # one parse: summary and per-op largest buffer both derive from it
        collected = collect_collectives(hlo_text)
        summary: Dict[str, Tuple[int, int]] = {}
        largest: Dict[str, int] = {}
        # per-op sum of each instance's LARGEST buffer: async -start
        # tuples carry operand AND result, so summing all buffers
        # (summary's total) would double-count on async backends; the
        # largest single buffer equals the result for both sync and
        # async forms, and its sum still exposes O(table) traffic split
        # across many individually-small buffers
        big_sum: Dict[str, int] = {}
        for op, b, big in collected:
            c, t = summary.get(op, (0, 0))
            summary[op] = (c + 1, t + b)
            largest[op] = max(largest.get(op, 0), big)
            big_sum[op] = big_sum.get(op, 0) + big
        label = f"{self.plane}/{self.program}"
        for op in self.forbid:
            if op in summary:
                raise ContractViolation(
                    f"{label}: forbidden collective {op!r} present "
                    f"(inventory: {summary})")
        for op, budget in self.ops.items():
            count = summary.get(op, (0, 0))[0]
            if count < budget.min_count:
                raise ContractViolation(
                    f"{label}: expected >= {budget.min_count} {op!r} "
                    f"op(s), found {count} (inventory: {summary}) — the "
                    "plane's exchange structure is gone")
            if budget.max_count is not None:
                cap = budget.max_count(params) if callable(budget.max_count) \
                    else budget.max_count
                if count > cap:
                    raise ContractViolation(
                        f"{label}: {count} {op!r} op(s) > allowed {cap} "
                        f"(inventory: {summary}, params {dict(params)})")
            if budget.max_buffer is not None and op in largest:
                bound = budget.max_buffer(params)
                if largest[op] > bound:
                    raise ContractViolation(
                        f"{label}: {op!r} buffer of {largest[op]} bytes "
                        f"> bound {bound} (params "
                        f"{dict(params)}) — O(global_batch)/O(table) "
                        "traffic has reappeared")
            if budget.max_total is not None and op in big_sum:
                bound = budget.max_total(params)
                total = big_sum[op]
                if total > bound:
                    raise ContractViolation(
                        f"{label}: {op!r} ops total {total} bytes "
                        f"> bound {bound} (params {dict(params)}) — "
                        "O(global_batch)/O(table) traffic has reappeared "
                        "split across buffers")
        if self.no_f64:
            check_no_f64(hlo_text)
        if self.no_host_transfers:
            check_no_host_transfers(hlo_text)
        if self.min_aliased:
            check_donation(hlo_text, self.min_aliased)
        return summary


REGISTRY: Dict[Tuple[str, str], ProgramContract] = {}


def _register(c: ProgramContract) -> ProgramContract:
    REGISTRY[(c.plane, c.program)] = c
    return c


# The a2a planes: owner exchange present, all-gather bounded by the row
# re-assembly, all-reduce bounded by residue-loop scalars (pull) or the
# K-row cache merge (cached push). The psum plane: NO all-to-all (that's
# the point of the ablation), all-reduce/all-gather carry the
# broadcast-style O(batch) signatures — inventoried so the baseline's
# own shape is pinned too.
_register(ProgramContract(
    plane="a2a", program="pull",
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_row_assembly),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a", program="push",
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_global_prereduce),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a+cache", program="pull",
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_row_assembly),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a+cache", program="push",
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_global_prereduce),
         "all-reduce": OpBudget(max_buffer=_cache_psum)}))
# The grouped plane: its EXTRA promise over plain a2a is the collective
# LAUNCH COUNT — one exchange set per GROUP of same-shape tables, never
# one per table (params carry num_groups and the empirically-counted
# per-exchange op count; a per-table-loop regression multiplies the
# all-to-all inventory by num_tables and fails the cap).
_register(ProgramContract(
    plane="a2a+grouped", program="pull",
    ops={"all-to-all": OpBudget(min_count=1, max_count=_grouped_a2a_ops),
         # max_total (not just max_buffer): a broken output annotation
         # re-gathers each table's rows in a SEPARATE buffer, each below
         # the concatenated-stream bound — the sum is what gives it away
         "all-gather": OpBudget(max_buffer=_grouped_row_assembly,
                                max_total=_grouped_row_assembly),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a+grouped", program="push",
    ops={"all-to-all": OpBudget(min_count=1, max_count=_grouped_a2a_ops),
         "all-gather": OpBudget(max_buffer=_grouped_prereduce,
                                max_total=_grouped_prereduce),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
# The compressed-exchange planes (parallel/precision.py): same owner
# exchange as a2a, but the row/grad payloads cross the wire narrowed —
# bf16 rows both directions ("a2a+bf16"), or bf16 pull + per-row-scale
# int8 error-feedback push ("a2a+int8"). Two teeth per program: (1) the
# inventory bounds below, with the all-gather legs bounded at the WIRE
# itemsize (an f32 row-assembly gather under a compressed contract
# busts _row_assembly_wire — the "f32 plane registered as compressed"
# negative); (2) the byte-halving ratio vs the f32 baseline's compiled
# program, enforced by check_compressed_program/graftcheck. The ratio
# binds at the audit shape (dim >= 32): keys/counts stay int32, so
# total-bytes/f32 asymptotes to 0.5 as dim grows and crosses 0.55 from
# above near dim 16 — the audit pins dim 64, where pull ≈ 0.51 and
# int8 push ≈ 0.30.
_register(ProgramContract(
    plane="a2a+bf16", program="pull",
    baseline_plane="a2a", byte_ratio=COMPRESSED_BYTE_RATIO,
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_row_assembly_wire),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a+bf16", program="push",
    baseline_plane="a2a", byte_ratio=COMPRESSED_BYTE_RATIO,
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_global_prereduce_wire),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
# "a2a+int8" pulls ride the bf16 wire (the token selects exchange bf16
# + push int8_ef); its push payload is int8 with the f32 scales bitcast
# into the integer key/count exchange buffer
_register(ProgramContract(
    plane="a2a+int8", program="pull",
    baseline_plane="a2a", byte_ratio=COMPRESSED_BYTE_RATIO,
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_row_assembly_wire),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="a2a+int8", program="push",
    baseline_plane="a2a", byte_ratio=COMPRESSED_BYTE_RATIO,
    ops={"all-to-all": OpBudget(min_count=1),
         "all-gather": OpBudget(max_buffer=_global_prereduce_wire),
         "all-reduce": OpBudget(max_buffer=_scalar)}))
_register(ProgramContract(
    plane="psum", program="pull",
    forbid=("all-to-all",),
    ops={"all-reduce": OpBudget(min_count=1, max_buffer=_batch_rows)}))
_register(ProgramContract(
    plane="psum", program="push",
    forbid=("all-to-all",),
    ops={"all-gather": OpBudget(min_count=1,
                                max_buffer=_global_batch_rows)}))
# the whole train step: cross-cutting only (its collective inventory is
# the union of its planes' + the dense-grad all-reduce); what the step
# must prove is donation (tables updated in place) and host purity
_register(ProgramContract(plane="any", program="step", min_aliased=1))


# --- the per-plane COST registry (graftplan) ---------------------------------

# Every plane above also declares its cost terms here, next to its HLO
# contract, so a new plane is automatically *plannable* the day it is
# registered (ROADMAP item 5) instead of becoming hand-tuning folklore.
# Two different kinds of number live in one PlaneSpec:
#
# * ``exchange_bytes`` — the per-device wire bytes of the COMPILED
#   pull/push program as a closed form over the lowering params
#   (global_batch, dim, itemsize, wire_itemsize, num_tables,
#   dim_bucket). These are audited: ``tools.graftcheck``'s cost-audit
#   section lowers every plane and fails if a declaration disagrees
#   with ``exchange_collective_bytes`` of the real HLO by more than
#   :data:`COST_MODEL_TOLERANCE`. The forms are calibrated in the
#   contract-audit regime (batch >= 512; at smaller shapes XLA elides
#   the residue/overflow legs and the small additive terms drift).
# * planner-only terms — ``workload_factor`` (how observed
#   unique_ratio / key_skew / cache hit-ratio scale the EFFECTIVE
#   cost; the compiled program is static, the workload is not),
#   ``launches`` (collective launch count per program — the per-launch
#   overhead proxy), ``hbm_overhead_bytes`` (resident bytes the plane
#   costs beyond the table shards). These feed ``analysis/plan.py``
#   and are NOT HLO-auditable; they are documented estimates.
#
# ``wire_ops`` names which collective ops carry the plane's exchange:
# the a2a family moves payload on all-to-all/all-gather (scalar
# all-reduces excluded, as in the byte-halving audit); the psum
# baseline's pull cost IS its all-reduce broadcast, so its spec widens
# the op set — the audit then compares against the same accounting.

COST_MODEL_TOLERANCE = 0.10
PSUM_WIRE_OPS = ("all-to-all", "all-gather", "all-reduce")


def _a2a_pull_bytes(p: Mapping[str, Any]) -> int:
    # row re-assembly gather (batch * dim * itemsize) + two int32
    # index/offset exchanges + residue-round scalars
    return int(p["global_batch"] * (p["dim"] * p["itemsize"] + 8) + 256)


def _a2a_push_bytes(p: Mapping[str, Any]) -> int:
    # grad+count prereduce gather ((dim+1) words) + one int32 key
    # exchange + residue scalars
    return int(p["global_batch"]
               * ((p["dim"] + 1) * p["itemsize"] + 4) + 256)


def _compressed_pull_bytes(p: Mapping[str, Any]) -> int:
    # rows cross at the wire width; ONE int32 index exchange (the key
    # leg rides the compressed payload)
    return int(p["global_batch"] * (p["dim"] * _wire(p) + 4) + 256)


def _bf16_push_bytes(p: Mapping[str, Any]) -> int:
    # bf16 grads + int32 keys on the gather, narrow a2a legs
    return int(p["global_batch"] * (p["dim"] * _wire(p) + 6) + 256)


def _int8_push_bytes(p: Mapping[str, Any]) -> int:
    # int8 grads + per-row f32 scale + int32 keys (+8), plus the
    # int8-width a2a leg (+wire)
    return int(p["global_batch"]
               * (p["dim"] * _wire(p) + 8 + _wire(p)) + 384)


def _psum_pull_bytes(p: Mapping[str, Any]) -> int:
    # the broadcast-style baseline: one O(batch * dim) all-reduce
    return int(p["global_batch"] * p["dim"] * p["itemsize"])


def _psum_push_bytes(p: Mapping[str, Any]) -> int:
    # full global batch gathered to every shard — the O(global) cost
    # the a2a plane exists to kill
    return int(p["global_batch"] * (p["dim"] + 1) * p["itemsize"])


def _grouped_pull_bytes(p: Mapping[str, Any]) -> int:
    # concatenated stream: every member table at the padded bucket dim
    return int(p["num_tables"] * p["global_batch"]
               * (p["dim_bucket"] * p["itemsize"] + 4) + 384)


def _grouped_push_bytes(p: Mapping[str, Any]) -> int:
    return int(p["num_tables"] * p["global_batch"]
               * ((p["dim_bucket"] + 1) * p["itemsize"] + 4) + 384)


def _unit_factor(stats: Mapping[str, Any]) -> float:
    # the compiled exchange moves the FULL index stream — dedup happens
    # host-side on the serving path, not in the device program
    return 1.0


def _cache_factor(stats: Mapping[str, Any]) -> float:
    # hot rows served from the replicated K-row cache skip the owner
    # exchange payload; the index legs still cross. Floor keeps the
    # model honest when the scraped hit ratio is noisy.
    hit = float(stats.get("cache_hit_ratio", 0.0))
    return max(0.15, 1.0 - hit)


def _no_overhead(p: Mapping[str, Any]) -> int:
    return 0


def _cache_hbm(p: Mapping[str, Any]) -> int:
    # K replicated hot rows + their grad-merge slot, per device
    return int(p.get("cache_k", 128) * (p["dim"] + 1) * p["itemsize"])


def _grouped_hbm(p: Mapping[str, Any]) -> int:
    # bucket-padding waste across the concatenated stream
    return int(p["num_tables"] * p["global_batch"]
               * max(0, p["dim_bucket"] - p["dim"]) * p["itemsize"])


@dataclasses.dataclass(frozen=True)
class PlaneSpec:
    """Declared cost model for one exchange plane (graftplan).

    ``exchange_bytes`` maps program -> declared per-device wire bytes
    (audited against compiled HLO by the graftcheck cost-audit);
    ``launches`` maps program -> collective launch count at the audit
    shape; ``workload_factor`` scales the effective exchange cost by
    observed workload stats; ``hbm_overhead_bytes`` is the plane's
    resident-memory overhead beyond the table shards;
    ``host_step_units`` is a relative host-side CPU dispatch cost per
    step (per-table program dispatches the host must issue).
    """

    plane: str
    exchange_bytes: Mapping[str, Bound]
    launches: Mapping[str, int]
    wire_ops: Tuple[str, ...] = EXCHANGE_BYTE_OPS
    workload_factor: Callable[[Mapping[str, Any]], float] = _unit_factor
    hbm_overhead_bytes: Bound = _no_overhead
    host_step_units: float = 1.0


PLANE_SPECS: Dict[str, PlaneSpec] = {}


def _register_spec(s: PlaneSpec) -> PlaneSpec:
    PLANE_SPECS[s.plane] = s
    return s


_register_spec(PlaneSpec(
    plane="a2a",
    exchange_bytes={"pull": _a2a_pull_bytes, "push": _a2a_push_bytes},
    launches={"pull": 7, "push": 5}))
_register_spec(PlaneSpec(
    plane="a2a+cache",
    exchange_bytes={"pull": _a2a_pull_bytes, "push": _a2a_push_bytes},
    launches={"pull": 7, "push": 7},
    workload_factor=_cache_factor, hbm_overhead_bytes=_cache_hbm))
_register_spec(PlaneSpec(
    plane="a2a+grouped",
    exchange_bytes={"pull": _grouped_pull_bytes,
                    "push": _grouped_push_bytes},
    # THE grouped claim priced in: launch count is per GROUP, so the
    # per-step host dispatch cost stays ~one table's worth
    launches={"pull": 7, "push": 5},
    hbm_overhead_bytes=_grouped_hbm, host_step_units=0.5))
_register_spec(PlaneSpec(
    plane="a2a+bf16",
    exchange_bytes={"pull": _compressed_pull_bytes,
                    "push": _bf16_push_bytes},
    launches={"pull": 7, "push": 5}))
_register_spec(PlaneSpec(
    plane="a2a+int8",
    exchange_bytes={"pull": _compressed_pull_bytes,
                    "push": _int8_push_bytes},
    launches={"pull": 7, "push": 6}))
_register_spec(PlaneSpec(
    plane="psum",
    exchange_bytes={"pull": _psum_pull_bytes, "push": _psum_push_bytes},
    launches={"pull": 1, "push": 2},
    wire_ops=PSUM_WIRE_OPS))

# completeness: every plane with a registered pull/push contract MUST
# carry a cost declaration — a new plane that forgets one fails at
# import, not at planning time
for _plane, _prog in REGISTRY:
    if _prog in ("pull", "push") and _plane not in PLANE_SPECS:
        raise AssertionError(
            f"plane {_plane!r} has a ProgramContract but no PlaneSpec "
            "cost declaration — register one next to its contract so "
            "graftplan can price it")


def declared_exchange_bytes(plane: str, program: str,
                            params: Mapping[str, Any]) -> int:
    """The PlaneSpec-declared wire bytes of one (plane, program) at
    ``params`` — the number the graftcheck cost-audit holds against
    the compiled HLO."""
    spec = PLANE_SPECS.get(plane)
    if spec is None or program not in spec.exchange_bytes:
        raise KeyError(f"no PlaneSpec cost declaration for "
                       f"({plane!r}, {program!r}); known: "
                       f"{sorted(PLANE_SPECS)}")
    return int(spec.exchange_bytes[program](params))


def check_cost_model(hlo_text: str, plane: str, program: str,
                     params: Mapping[str, Any], *,
                     tolerance: float = COST_MODEL_TOLERANCE,
                     spec: Optional[PlaneSpec] = None
                     ) -> Dict[str, Any]:
    """Audit one plane's declared exchange bytes against its compiled
    HLO: |declared - actual| must stay within ``tolerance`` of the
    actual ``exchange_collective_bytes`` over the spec's wire ops.
    ``spec`` overrides the registered one (the negative tests inject a
    deliberately-wrong declaration). Returns the comparison; raises
    :class:`ContractViolation` on disagreement."""
    spec = spec if spec is not None else PLANE_SPECS.get(plane)
    if spec is None or program not in spec.exchange_bytes:
        raise KeyError(f"no PlaneSpec cost declaration for "
                       f"({plane!r}, {program!r})")
    declared = int(spec.exchange_bytes[program](params))
    actual = exchange_collective_bytes(hlo_text, ops=spec.wire_ops)
    scale = max(actual, 1)
    err = abs(declared - actual) / scale
    if err > tolerance:
        raise ContractViolation(
            f"{plane}/{program}: declared exchange cost {declared} B "
            f"disagrees with compiled HLO {actual} B by "
            f"{err * 100:.1f}% > {tolerance * 100:.0f}% "
            f"(params {dict(params)}) — the PlaneSpec cost model is "
            "stale; recalibrate the declaration next to the plane's "
            "contract")
    return {"plane": plane, "program": program, "declared": declared,
            "actual": actual, "rel_err": err, "tolerance": tolerance}


def check_program(hlo_text: str, plane: str, program: str,
                  **params) -> Dict[str, Tuple[int, int]]:
    """Audit one compiled program against its registered contract.

    ``params``: batch_slice, global_batch, dim, itemsize (default 4),
    cache_k (cached plane), num_shards — whatever the plane's bounds
    consume. Returns the collective summary; raises
    :class:`ContractViolation` on any breach.
    """
    key = (plane, program)
    if key not in REGISTRY:
        raise KeyError(f"no contract registered for {key}; known: "
                       f"{sorted(REGISTRY)}")
    params.setdefault("itemsize", 4)
    if program == "push" and "global_batch" not in params:
        # never guess it from batch_slice: on a data>1 mesh that
        # understates the overflow-fallback bound and raises spurious
        # violations (programs.contract_params supplies both)
        raise KeyError(
            "push contracts need global_batch (the overflow-fallback "
            "all-gather is O(global_batch * dim)); pass it explicitly "
            "or use analysis.programs.contract_params")
    return REGISTRY[key].check(hlo_text, params)


def check_compressed_program(hlo_text: str, baseline_hlo: str, plane: str,
                             program: str, **params) -> Dict[str, Any]:
    """Full audit of one COMPRESSED plane program: its registered
    inventory contract (wire-width byte bounds) PLUS the byte-halving
    ratio against the f32 baseline's compiled HLO. ``baseline_hlo``
    must be the registered ``baseline_plane``'s program lowered at the
    same mesh/batch/dim. Returns a summary dict; raises
    :class:`ContractViolation` on any breach."""
    summary = check_program(hlo_text, plane, program, **params)
    contract = REGISTRY[(plane, program)]
    if contract.byte_ratio is None or contract.baseline_plane is None:
        raise KeyError(
            f"({plane}, {program}) is not a compressed contract — no "
            "byte_ratio/baseline_plane registered")
    got, base = check_byte_halving(
        hlo_text, baseline_hlo, ratio=contract.byte_ratio,
        label=f"{plane}/{program} vs {contract.baseline_plane}")
    return {"collectives": summary, "exchange_bytes": got,
            "baseline_bytes": base, "ratio": got / base,
            "max_ratio": contract.byte_ratio}


# --- the original hlocheck entry point (kept verbatim for callers) -----------

def check_a2a_pull_hlo(hlo_text: str, *, batch_slice: int, dim: int,
                       itemsize: int = 4) -> Dict[str, Tuple[int, int]]:
    """Enforce the a2a pull program's ICI contract; returns the summary.

    * >= 1 ``all-to-all`` (the owner exchange actually compiled in — if
      XLA or a plane regression replaced it with broadcast-style
      collectives, the plane's whole point is gone);
    * every ``all-gather`` result is bounded by the ROW-ASSEMBLY size
      ``batch_slice * dim * itemsize`` (+6.25% partitioner padding slack):
      the one legitimate gather returns each data-slice's pulled rows to
      its model-axis peers. A table-sized or global-batch-sized gather
      (the psum plane's O(global_batch * dim) signature) fails here.
    """
    summary = summarize(hlo_text)
    if "all-to-all" not in summary:
        raise AssertionError(
            "a2a pull program compiled WITHOUT an all-to-all — the owner "
            f"exchange is gone (collectives: {summary})")
    bound = int(batch_slice * dim * itemsize * ROW_ASSEMBLY_SLACK)
    for op, _total, largest in collect_collectives(hlo_text):
        if op == "all-gather" and largest > bound:
            raise AssertionError(
                f"a2a pull program contains an all-gather buffer of "
                f"{largest} bytes > row-assembly bound {bound} "
                f"(batch_slice={batch_slice}, dim={dim}) — "
                "O(global_batch)/O(table) traffic has reappeared on the "
                "pull path")
    return summary
