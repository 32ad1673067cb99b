"""Owner-routed all-to-all exchange: the scale-grade sparse data plane.

The reference's pull/push pipeline is an *owner exchange*: dedup client-side,
partition keys by owning shard, send each shard only its own requests, scatter
the per-shard responses back
(/root/reference/openembedding/server/EmbeddingPullOperator.cpp:60-112,207-252,
EmbeddingPushOperator.cpp:29-104). The first TPU data plane here (the "psum"
plane in ``sharded_table``/``sharded_hash``) replaced that with gather + psum
(pull) and all_gather + masked local update (push) — simple and correct, but
its ICI traffic scales with *mesh size*, not with owned rows: the push
all_gathers the full global batch to every device.

This module is the owner exchange done TPU-natively, inside one shard_map
program ("a2a" plane):

* tables are sharded over the **whole mesh** (data x model axes = N shards),
  so HBM capacity scales with every chip and there are no table replicas to
  keep in sync;
* each device handles a distinct slice of the batch (the model-axis peers of
  a data slice split their common copy), dedups it, buckets the unique keys
  by owner shard into fixed-capacity blocks, and a grid all-to-all routes
  each block to its owner — indices out, rows (pull) or pre-reduced
  (grad, count) pairs (push) back;
* the owner resolves rows locally (array index math or hash probe) and, on
  push, merges the per-peer pre-reduces exactly like the reference's
  server-side MpscGradientReducer (counts are summed, not recounted).

Per-device ICI bytes per step are O(slack * batch_slice * dim) instead of
O(global_batch * dim) — the gap to the reference's per-owner exchange closed.

Static shapes: the per-destination bucket capacity must be fixed at trace
time. Keys are uniform across owners by construction ("mod" layout spreads
sequential ids; hash keys are avalanche-mixed), so the default capacity
``max(32, 2 * mean_bucket)`` fits everything in the first round with
overwhelming probability. The exchange is nevertheless EXACT for any key
distribution — like the reference's variable-size RPC exchange
(EmbeddingPullOperator.cpp:60-112): entries past a bucket's capacity stay
pending and a residue loop (``lax.while_loop``) re-routes them in further
fixed-capacity rounds until a globally psum'd pending count reaches zero.
Adversarial skew (e.g. every id congruent modulo the shard count) costs
extra rounds, never correctness. :func:`routing_overflow` remains as a
sizing diagnostic — it now predicts *extra rounds*, not data loss — and the
gated ``a2a_extra_entries_*`` accumulators count residue-routed entries
(the reference ships the same measurement methodology,
laboratory/benchmark/analyze.py). Raise ``a2a_capacity``/``a2a_slack`` if
your key distribution routinely needs more than one round.

One plan a distinct id column a step. Pull and push of one train step route
the same keys the same way, and so do tables fed one column; what that
takes — the slice, its dedup and counts, the owners, round 1's buckets,
the key all-to-all, the owner's dedup of what it received — needs no table
and no gradient. :func:`plan_exchange` makes it once (a
:class:`RoutedPlan`), and :func:`exchange_pull` / :func:`exchange_push`
that are handed it do the rest: rows back, gradients out. Handed none
they run as they always did.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from ..analysis import scope
from ..analysis.lint import host_fn
from ..ops import dedup
from ..utils import observability


def pin_wire(x: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret a 16-bit float wire payload as uint16 bits.

    The compressed planes' promise is a BYTE property of the compiled
    collectives. A plain ``astype`` pair around the exchange is
    value-correct but not byte-stable: XLA's algebraic simplifier
    commutes converts across data-movement ops (and drops
    optimization_barrier on some backends), happily shipping f32 with a
    fused bf16 round-trip in front — same numbers, double the bytes,
    and the byte-halving contract fails. A bitcast is not a convert:
    the simplifier cannot move it across the collective, so the wire
    buffer is uint16 in the compiled program on every backend.
    """
    return lax.bitcast_convert_type(x, jnp.uint16)


def unpin_wire(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`pin_wire` (exact bit reinterpretation)."""
    return lax.bitcast_convert_type(x, dtype)


def record_stat(counter: str, local_value: jnp.ndarray,
                record: bool) -> None:
    """Gated host accumulation of routed-exchange statistics.

    ``record`` is the trace-time gate (callers thread
    ``observability.evaluate_performance()`` through their program-cache key
    so toggling it compiles the right program) — the same gate the reference
    puts on its pull_indices/pull_unique counters
    (EmbeddingPullOperator.cpp:208-209,244-248). Off by default: a host
    callback per step would stall TPU pipelining. The callback re-checks the
    gate at run time so a program traced with recording on goes quiet when
    the gate is turned off.
    """
    if record:
        # _cb runs on HOST via jax.debug.callback — the one sanctioned
        # escape hatch for counters (graftlint exempts callback
        # functions; the compiled-program audit sees the resulting
        # custom-call, which is why contracts are checked against the
        # default record-off programs)
        def _cb(d):
            if observability.evaluate_performance():
                observability.GLOBAL.add(counter, int(d))
        jax.debug.callback(_cb, local_value)


def record_float_stat(counter: str, local_value: jnp.ndarray,
                      record: bool) -> None:
    """:func:`record_stat` for float-valued quantization telemetry.

    Used by the int8_ef push path: ``quant_error_max`` (this device's
    largest absolute residual this step) and ``quant_residual_norm``
    (this device's residual L2 norm). The callback fires once per
    device shard, so the host accumulator SUMS locals across devices
    and steps — a cumulative drift series; the per-sample distribution
    additionally lands in the graftscope histogram registry, rendered
    on /metrics as an ``oe_quant_*`` series next to the counters.
    """
    if record:
        def _cb(d):
            if observability.evaluate_performance():
                v = float(d)
                observability.GLOBAL.add(counter, v)
                from ..analysis import scope
                scope.HISTOGRAMS.observe(counter, v)
        jax.debug.callback(_cb, local_value)


def linear_shard_id(axes: Sequence[str], sizes: Sequence[int]) -> jnp.ndarray:
    """This device's shard ordinal, row-major over ``axes`` (static sizes).

    Matches the block order of ``PartitionSpec((*axes,))`` on dim 0: the
    device at mesh position (i0, i1, ...) owns block i0*s1*... + i1*... .
    """
    idx = jnp.zeros((), jnp.int32)
    for ax, size in zip(axes, sizes):
        idx = idx * size + lax.axis_index(ax)
    return idx


def bucket_capacity(slice_size: int, num_shards: int,
                    capacity: int = 0, slack: float = 2.0) -> int:
    """Per-destination bucket size: explicit, or mean*slack with a floor.

    Slices of <= 32 entries (tests, serving probes) get ``capacity ==
    slice_size`` and finish in one round regardless of key skew. Larger
    slices rely on owner uniformity: binomial concentration makes ``2 *
    mean`` single-round for uniform owners (hashed keys, or sequential ids
    under the "mod" layout). *Structured* skew — e.g. ids all congruent
    modulo the shard count — overflows the first round, which only costs
    extra residue rounds (the exchange is exact either way). Monitor with
    :func:`routing_overflow` or the gated ``a2a_extra_entries_*``
    accumulators, and raise ``a2a_capacity``/``a2a_slack`` (up to
    ``slice_size`` = always one round) if your keys defeat the layout.
    """
    if capacity:
        return min(capacity, slice_size)
    mean = math.ceil(slice_size / num_shards)
    c = max(32, math.ceil(mean * slack))
    c = min(slice_size, -(-c // 8) * 8)
    return max(c, 1)


def bucketize(owner: jnp.ndarray, num_shards: int, capacity: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Assign each entry a flat send-buffer slot ``owner * capacity + pos``.

    ``owner`` is [m] with values in [0, num_shards) or >= num_shards for
    entries that must not be sent. Returns ``(dest [m], ok [m])``: ``dest``
    is the flat slot (== num_shards * capacity, i.e. out of range, when not
    sent this round), ``ok`` marks entries that made it into a bucket.
    Equivalent of the reference's per-shard request assembly
    (EmbeddingPullOperator.cpp:73-112) under XLA's static shapes: stable
    sort by owner, rank within group; past-capacity ranks stay pending for
    the caller's residue loop.
    """
    m = owner.shape[0]
    owner = owner.astype(jnp.int32)
    clamped = jnp.minimum(owner, num_shards)
    order = jnp.argsort(clamped, stable=True)
    sorted_owner = clamped[order]
    counts = jnp.zeros((num_shards + 1,), jnp.int32).at[clamped].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(m, dtype=jnp.int32) - starts[sorted_owner]
    pos = jnp.zeros((m,), jnp.int32).at[order].set(pos_sorted)
    ok = (owner < num_shards) & (pos < capacity)
    dest = jnp.where(ok, owner * capacity + pos, num_shards * capacity)
    return dest, ok


def fill_buckets(values: jnp.ndarray, dest: jnp.ndarray, num_shards: int,
                 capacity: int, fill) -> jnp.ndarray:
    """Scatter [m, ...] values into a [num_shards, capacity, ...] send buffer."""
    out = jnp.full((num_shards * capacity,) + values.shape[1:], fill,
                   dtype=values.dtype)
    out = out.at[dest].set(values, mode="drop")
    return out.reshape((num_shards, capacity) + values.shape[1:])


def grid_all_to_all(x: jnp.ndarray, axes: Sequence[str],
                    sizes: Sequence[int]) -> jnp.ndarray:
    """All-to-all over the product of mesh ``axes``.

    ``x`` is [N, ...] of per-destination blocks in row-major linear-shard
    order (N = prod(sizes)); the result is [N, ...] where row j is the block
    peer j destined for this device. Decomposed into one ``lax.all_to_all``
    per axis (a grid transpose): after routing over axis k, block (j0..jk..)
    holds data from the peer matching on later axes — the composition routes
    every block to exactly its (j0, ..., jn) owner.
    """
    @scope.stage("exchange")
    def route_blocks(x):
        y = x.reshape(tuple(sizes) + x.shape[1:])
        for k, (ax, size) in enumerate(zip(axes, sizes)):
            if size > 1:
                y = lax.all_to_all(y, ax, split_axis=k, concat_axis=k)
        return y.reshape(x.shape)

    return route_blocks(x)


def grid_info(mesh, shard_axes: Sequence[str], model_axis: str,
              batch_sharded: bool):
    """(grid_axes, grid_sizes, split_axes, split_sizes) for one exchange.

    The batch is divided among the mesh axes it is *replicated* over (the
    model axis when batch-sharded over data; the whole shard grid when fully
    replicated), and routed to owners over all table shard axes.
    """
    grid_axes = tuple(shard_axes)
    grid_sizes = tuple(mesh.shape[a] for a in grid_axes)
    split_axes = (model_axis,) if batch_sharded else grid_axes
    split_sizes = tuple(mesh.shape[a] for a in split_axes)
    return grid_axes, grid_sizes, split_axes, split_sizes


def split_slice(flat: jnp.ndarray, num_parts: int, my_part: jnp.ndarray,
                fill) -> Tuple[jnp.ndarray, int]:
    """Pad ``flat`` [n] (or [n, kc] wide keys) to a multiple of
    ``num_parts`` and take slice ``my_part`` of size m = ceil(n /
    num_parts). Returns (slice, m)."""
    n = flat.shape[0]
    m = -(-n // num_parts)
    padded = jnp.full((m * num_parts,) + flat.shape[1:], fill,
                      dtype=flat.dtype)
    padded = padded.at[:n].set(flat)
    start = (my_part * m).astype(jnp.int32)
    starts = (start,) + (jnp.zeros((), jnp.int32),) * (flat.ndim - 1)
    return lax.dynamic_slice(padded, starts, (m,) + flat.shape[1:]), m


def split_slice_rows(rows: jnp.ndarray, num_parts: int, my_part: jnp.ndarray
                     ) -> jnp.ndarray:
    """Row variant of :func:`split_slice` (zero padding)."""
    n = rows.shape[0]
    m = -(-n // num_parts)
    padded = jnp.zeros((m * num_parts,) + rows.shape[1:], rows.dtype)
    padded = padded.at[:n].set(rows)
    start = (my_part * m).astype(jnp.int32)
    starts = (start,) + (jnp.zeros((), jnp.int32),) * (rows.ndim - 1)
    return lax.dynamic_slice(padded, starts, (m,) + rows.shape[1:])


def segment_offsets(sizes: Sequence[int]) -> Tuple[int, ...]:
    """Static exclusive prefix sums over per-segment entry counts.

    The grouped exchange concatenates several tables' key streams into one
    routed stream; these offsets carve each table's slice back out of the
    concatenated result (all sizes are trace-time constants, so the carves
    are static slices, not dynamic ops).
    """
    out = [0]
    for s in sizes:
        out.append(out[-1] + int(s))
    return tuple(out)


def carve_segments(rows: jnp.ndarray, sizes: Sequence[int]) -> list:
    """Split ``rows`` [sum(sizes), ...] back into per-segment blocks."""
    offs = segment_offsets(sizes)
    return [rows[offs[i]:offs[i + 1]] for i in range(len(sizes))]


@struct.dataclass
class RoutedPlan:
    """One id column of one train step on the routed plane: everything the
    exchange does with its keys that needs no table and no gradient, made
    once (:func:`plan_exchange`) for every :func:`exchange_pull` and
    :func:`exchange_push` of every table that reads the column. Each
    device's own, laid over the exchange grid.

    The sender's half: its slice of the batch deduplicated and counted,
    each distinct key's owner, and round 1's buckets. The owner's half:
    the keys round 1 brought it with their counts (they cross the wire
    here, once a step), deduplicated over the ``[num_shards * capacity]``
    bucket slots, sentinel padding no key, the counts of a key's senders
    summed: the owner reads one row and merges once a distinct key, as the
    masked-local body does with its step's ``dedup.Plan``, and lays rows
    back into bucket order by ``owner.inverse``. Both plans bring their
    ``counts``, which are the column's: a table's push sums and sends its
    gradients alone. Round 1 is the plan's; what it did not hold
    (``spilled`` > 0) goes the way it goes without a plan: the pull's
    residue rounds, the push's gathered branch."""

    sender: dedup.Plan      # of the slice: [m] uniq, inverse, valid, counts
    owners: jnp.ndarray     # [m] int32: a distinct key's owner shard
    dest: jnp.ndarray       # [m] int32: its bucket slot in round 1
    ok: jnp.ndarray         # [m] bool: round 1 holds it
    owner: dedup.Plan       # of the [num_shards * capacity] keys received
    spilled: jnp.ndarray    # [] int32: keys round 1 left, over all devices


def plan_exchange(flat_idx: jnp.ndarray,
                  owner_fn: Callable[[jnp.ndarray], jnp.ndarray],
                  *,
                  sentinel,
                  num_shards: int,
                  grid_axes: Sequence[str],
                  grid_sizes: Sequence[int],
                  split_axes: Sequence[str],
                  split_sizes: Sequence[int],
                  capacity: int = 0,
                  slack: float = 2.0,
                  record_stats: bool = False) -> RoutedPlan:
    """The :class:`RoutedPlan` of ``flat_idx`` ([n], or [n, 2] wide pairs;
    identical on all ``split_axes`` peers): what :func:`exchange_pull` and
    :func:`exchange_push` each compute in front of round 1 when they are
    handed none, the key all-to-all itself (the counts ride with the keys,
    as they do in a push that is handed no plan), and the owner's dedup of
    the keys it received, with their counts summed.

    ``record_stats`` counts ``routed_plan_keys_sent`` (the distinct keys
    this device put into round 1's buckets), ``routed_plan_owner_keys_live``
    and ``routed_plan_owner_slots`` (the distinct keys among the bucket
    slots it received: the rest is padding and duplicates no owner reads
    any more)."""
    wide = flat_idx.ndim == 2
    parts = math.prod(split_sizes)
    m = -(-flat_idx.shape[0] // parts)
    cap = bucket_capacity(m, num_shards, capacity, slack)

    @scope.stage("route")
    def my_slice(flat_idx):
        my_part = linear_shard_id(split_axes, split_sizes)
        return split_slice(flat_idx, parts, my_part, sentinel)[0]

    sender = dedup.plan_keys(my_slice(flat_idx), sentinel)
    sender = sender.replace(counts=dedup.count_keys(sender.inverse, m))
    kw = flat_idx.shape[1] if wide else 1  # key words per entry

    @scope.stage("route")
    def to_buckets(uniq, counts):
        owners = owner_fn(uniq)
        dest, ok = bucketize(owners, num_shards, cap)
        # keys and counts share one integer buffer, as exchange_push's do
        ku = uniq if wide else uniq[:, None]
        kc = jnp.concatenate([ku, counts.astype(ku.dtype)[:, None]], axis=1)
        return owners, dest, ok, fill_buckets(kc, dest, num_shards, cap,
                                              sentinel)

    @scope.stage("route")
    def from_buckets(rkc):
        flat_kc = rkc.reshape((-1, kw + 1))
        return (flat_kc[:, :kw] if wide else flat_kc[:, 0],
                flat_kc[:, kw].astype(jnp.int32))

    owners, dest, ok, send = to_buckets(sender.uniq, sender.counts)
    keys, counts = from_buckets(grid_all_to_all(send, grid_axes, grid_sizes))
    owner = dedup.plan_keys(keys, sentinel)
    # a padding slot's count is the fill: it lands in the slot of the
    # fill's own key, which holds no key
    owner = owner.replace(counts=dedup.count_keys(
        owner.inverse, num_shards * cap, counts))
    spilled = jnp.zeros((), jnp.int32)
    if cap < m:     # else the buckets hold the whole slice
        spilled = scope.stage("exchange")(
            lambda x: lax.psum(x, tuple(grid_axes)))(
                jnp.sum((owners < num_shards) & ~ok).astype(jnp.int32))
    record_stat("routed_plan_keys_sent", jnp.sum(ok, dtype=jnp.int32),
                record_stats)
    record_stat("routed_plan_owner_keys_live",
                jnp.sum(owner.valid, dtype=jnp.int32), record_stats)
    record_stat("routed_plan_owner_slots", jnp.int32(num_shards * cap),
                record_stats)
    return RoutedPlan(sender=sender, owners=owners, dest=dest, ok=ok,
                      owner=owner, spilled=spilled)


def exchange_pull(flat_idx: jnp.ndarray,
                  resolve_fn: Callable[[jnp.ndarray], jnp.ndarray],
                  owner_fn: Callable[[jnp.ndarray], jnp.ndarray],
                  *,
                  sentinel,
                  dim: int,
                  num_shards: int,
                  grid_axes: Sequence[str],
                  grid_sizes: Sequence[int],
                  split_axes: Sequence[str],
                  split_sizes: Sequence[int],
                  capacity: int = 0,
                  slack: float = 2.0,
                  record_stats: bool = False,
                  wire_dtype=None,
                  plan: Optional[RoutedPlan] = None,
                  read_plan: Optional[Callable] = None):
    """Owner-routed lookup of ``flat_idx`` [n] -> rows [n, dim]. EXACT.

    ``flat_idx`` must be identical on all ``split_axes`` peers (they divide
    the work); ``resolve_fn(keys [K]) -> [K, dim]`` runs on the owner and
    must return zero rows for keys it does not own (sentinel included).
    ``owner_fn(keys)`` maps keys to shard ordinals (>= num_shards = do not
    send). The result is replicated over ``split_axes`` again (all_gather).
    WIDE keys ride as [n, 2] int32 (lo, hi) pairs (x64-off 64-bit space);
    a pair is padding iff its hi word equals ``sentinel``. Composite keys
    generalize this to [n, K] rows (the grouped plane's table-tagged
    streams, ``parallel/grouped.py``): padding rows carry ``sentinel`` in
    every column and ``resolve_fn``/``owner_fn`` see the full K columns.

    Round 1 routes everything that fits the fixed-capacity buckets; any
    residue (structured key skew) loops through further rounds until the
    globally psum'd pending count is zero, so no key distribution can drop
    entries — parity with the reference's variable-size exchange
    (EmbeddingPullOperator.cpp:60-112).

    ``wire_dtype`` (``parallel/precision.py``): rows cross the response
    all-to-all AND the row-assembly all-gather in this dtype (bf16 =
    half the exchange bytes) and are upcast to the resolver's dtype
    after the last collective. Exactness caveat: each pulled row then
    carries ONE round-to-nearest cast (the residue accumulator fills
    every entry exactly once, so rounds never compound the error).
    ``None`` leaves the program byte-identical to the uncompressed one.

    ``plan`` is :func:`plan_exchange`'s of the same ``flat_idx``: the
    slice, its dedup, the owners, round 1's buckets and the key all-to-all
    are taken from it, and the owner answers round 1 through
    ``read_plan(plan.owner) -> dedup.Resolution``: one row a distinct key
    it received, laid back into bucket order by ``plan.owner.inverse``.
    The same rows come back, and with them what the owner resolved:
    ``(rows, resolution)``, for :func:`exchange_push` of the same plan
    while nothing has written the table. Keys round 1 did not hold go
    through the residue rounds as they do without a plan (``resolve_fn``).
    """
    n = flat_idx.shape[0]
    wide = flat_idx.ndim == 2
    kw = flat_idx.shape[1] if wide else 1  # key words per entry
    parts = math.prod(split_sizes)
    m = -(-n // parts)

    cap = bucket_capacity(m, num_shards, capacity, slack)
    if plan is not None:
        uniq, inverse, owners = (plan.sender.uniq, plan.sender.inverse,
                                 plan.owners)
    else:
        @scope.stage("route")
        def my_slice(flat_idx):
            my_part = linear_shard_id(split_axes, split_sizes)
            return split_slice(flat_idx, parts, my_part, sentinel)[0]

        sl = my_slice(flat_idx)
        if wide:
            uniq, inverse, _valid = dedup.unique_rows(sl, m,
                                                      fill_value=sentinel)
        else:
            uniq, inverse, _valid = dedup.unique_indices(
                sl, m, fill_value=sentinel)
        owners = scope.stage("route")(owner_fn)(uniq)
    out_dtype = jax.eval_shape(resolve_fn, uniq).dtype
    acc_dtype = out_dtype if wire_dtype is None else jnp.dtype(wire_dtype)

    @scope.stage("route")
    def to_buckets(pending, uniq):
        dest, ok = bucketize(pending, num_shards, cap)
        return fill_buckets(uniq, dest, num_shards, cap, sentinel), dest, ok

    @scope.stage("route")
    def from_buckets(resp, dest, ok, pending, acc):
        flat_resp = resp.reshape((num_shards * cap, dim))
        got = jnp.take(flat_resp, jnp.where(ok, dest, 0), axis=0)
        acc = acc + jnp.where(ok[:, None], got, jnp.zeros_like(got))
        return jnp.where(ok, jnp.int32(num_shards), pending), acc

    @scope.stage("exchange")
    def count_left(pending):
        return lax.psum(jnp.sum(pending < num_shards).astype(jnp.int32),
                        tuple(grid_axes))

    def respond(rows, dest, ok, pending, acc):
        if wire_dtype is not None:
            # the ONE lossy point of a compressed pull: owner-resolved
            # rows narrow to the wire dtype before the response leg,
            # bit-pinned to uint16 so the compiled collective really
            # carries 2-byte buffers (see pin_wire)
            rows = pin_wire(rows.astype(acc_dtype))
        resp = grid_all_to_all(rows.reshape((num_shards, cap, dim)),
                               grid_axes, grid_sizes)
        if wire_dtype is not None:
            resp = unpin_wire(resp, acc_dtype)
        return from_buckets(resp, dest, ok, pending, acc)

    def one_round(pending, acc):
        send, dest, ok = to_buckets(pending, uniq)
        req = grid_all_to_all(send, grid_axes, grid_sizes)
        rows = scope.stage("resolve")(resolve_fn)(
            req.reshape((-1, kw)) if wide else req.ravel())
        pending, acc = respond(rows, dest, ok, pending, acc)
        return pending, acc, count_left(pending)

    pending0 = owners.astype(jnp.int32)
    acc0 = jnp.zeros((m, dim), dtype=acc_dtype)
    if plan is None:
        pending, uniq_rows, left = one_round(pending0, acc0)
    else:
        # round 1 is the plan's: the keys are at their owner, which reads
        # a row a distinct key and hands every bucket slot its key's
        resolved = read_plan(plan.owner)
        rows = scope.stage("expand")(
            lambda rows, inverse: jnp.take(rows, inverse, axis=0,
                                           mode="clip"))(
                resolved.rows, plan.owner.inverse)
        pending, uniq_rows = respond(rows, plan.dest, plan.ok, pending0,
                                     acc0)
        left = plan.spilled
    # record the per-device residue: the callback fires on every device
    # shard, so the host accumulator sums locals into the global total
    record_stat("a2a_extra_entries_pull",
                 jnp.sum(pending < num_shards).astype(jnp.int32),
                 record_stats)
    if cap < m:
        # residue loop: only reachable when round 1 could overflow
        pending, uniq_rows, _ = lax.while_loop(
            lambda c: c[2] > 0,
            lambda c: one_round(c[0], c[1]),
            (pending, uniq_rows, left))
    slice_rows = scope.stage("expand")(
        lambda rows, inverse: jnp.take(rows, inverse, axis=0))(
            uniq_rows, inverse)
    assemble = scope.stage("exchange")(
        lambda rows: lax.all_gather(rows, tuple(split_axes), tiled=True))
    if wire_dtype is not None:
        # the row-assembly gather ships the pinned 16-bit wire form too;
        # the upcast after it is exact (bf16 -> f32 loses nothing)
        out = assemble(pin_wire(slice_rows))
        out = unpin_wire(out[:n], acc_dtype).astype(out_dtype)
    else:
        out = assemble(slice_rows)[:n]
    return out if plan is None else (out, resolved)


def exchange_push(flat_idx: jnp.ndarray,
                  grads: jnp.ndarray,
                  merge_fn: Callable,
                  owner_fn: Callable[[jnp.ndarray], jnp.ndarray],
                  *,
                  sentinel,
                  num_shards: int,
                  grid_axes: Sequence[str],
                  grid_sizes: Sequence[int],
                  split_axes: Sequence[str],
                  split_sizes: Sequence[int],
                  capacity: int = 0,
                  slack: float = 2.0,
                  record_stats: bool = False,
                  wire_dtype=None,
                  ef_state=None,
                  plan: Optional[RoutedPlan] = None,
                  merge_plan: Optional[Callable] = None):
    """Owner-routed push: pre-reduce, route (key, grad sum, count) to owners.
    EXACT for any key distribution.

    ``merge_fn(keys [K], grads [K, dim], counts [K]) -> merged`` runs on
    the owner with the per-peer pre-reduces and merges them: ``merged`` is
    a pytree of arrays whose leading axis is the slots of the owner's
    deduplicated buffer (``table.merge_gradients``,
    ``hash_table.combine_keys``). Entries with a sentinel key are padding
    and must be ignored by ``merge_fn`` (both built-in mergers drop them
    via the invalid-key contract; their count values are garbage by
    design). Returns ``merged``, for the caller to write to its table
    after the exchange (a hash table's find-or-insert, then
    ``table.apply_rows``): a merger writes nothing, because those loops
    carry the table, and the v5e compiler copies a table that a loop
    inside a branch of a conditional carries (each key array of 2^26 wide
    slots is 512 MiB). Each branch merges at its own size, and ``merged``
    is padded at the tail with zeros (dead slots) to the longer of the
    two; the find and the apply walk the occupied prefix, so the padding
    costs nothing.

    Unlike the pull (idempotent reads, residue rounds compose), a push must
    apply each key's optimizer update EXACTLY ONCE per step with all peer
    contributions merged — splitting a key across two apply calls is wrong
    for nonlinear optimizers (adam's moments would see two half-batches).
    So overflow is detected globally *before* anything is applied, and the
    program conditions on it:

    * no overflow (the overwhelmingly common case — capacity heuristics are
      sized for it): one routed fixed-capacity exchange, owner merges the
      per-peer (sum, count) pre-reduces via ``in_counts``;
    * overflow (structured key skew): fall back to an all_gather of every
      peer's pre-reduced slice over the grid — the psum-plane push, paid
      only when the routed plane can't hold the batch — so the owner still
      sees each key exactly once with all contributions.

    Both branches are exact; the reference gets the same guarantee from
    variable-size RPCs + server-side MpscGradientReducer
    (EmbeddingPushOperator.cpp:29-104). Note for mergers that dedup with a
    bounded capacity: the OWNED-UNIQUE count a merger sees is identical
    in both branches (each peer slice contributes a key at most once either
    way — the gathered batch is longer but not more unique), so capacity
    sizing is branch-independent. Keys and counts share one integer
    exchange buffer ([.., 2] channels) so a routed push costs two
    collectives per mesh axis, not three.

    Compressed wires (``parallel/precision.py``):

    * ``wire_dtype`` (bf16): the pre-reduced gradient rows cross the
      exchange (or the overflow all_gather) narrowed, upcast before the
      owner's f32 optimizer math — keys/counts stay int32.
    * ``ef_state = (prev_keys, prev_resid)``: int8 error-feedback push.
      Each sender adds the residual it stored for keys it also
      pre-reduced LAST step, quantizes the total per row (max-abs/127
      scale, int8 payload; the f32 scale rides the integer key/count
      buffer bitcast into one extra channel), and keeps the new
      quantization error for next step. Returns ``(result, (keys,
      resid))`` instead of ``result`` — both computed before the
      overflow branch, so feedback is branch-independent. Padding rows'
      scales are garbage on the routed wire (single-fill buffer);
      owners zero them by key validity so no NaN can reach a merger.

    ``plan`` is :func:`plan_exchange`'s of the same ``flat_idx``, the one
    the step's :func:`exchange_pull` ran on (no ``ef_state`` goes with
    it): the slice's gradients are combined by its ``inverse`` into its
    distinct keys' slots and ride round 1's buckets by its ``dest``. The
    keys and their counts are at their owner already, so the gradients
    cross alone, and the owner merges through ``merge_plan(grads [K,
    dim]) -> merged``, which sums them by ``plan.owner.inverse`` and
    deduplicates and counts nothing. A step
    round 1 did not hold (``plan.spilled`` > 0) takes the gathered branch
    as it is without a plan; ``merge_fn`` and ``merge_plan`` return one
    structure.
    """
    dim = grads.shape[-1]
    parts = math.prod(split_sizes)
    wide = flat_idx.ndim == 2
    m = -(-flat_idx.shape[0] // parts)
    cap = bucket_capacity(m, num_shards, capacity, slack)

    if plan is not None:
        g2 = scope.stage("route")(
            lambda grads: split_slice_rows(
                grads.reshape((-1, dim)), parts,
                linear_shard_id(split_axes, split_sizes)))(grads)
        uniq, inverse = plan.sender.uniq, plan.sender.inverse
        summed, counts = dedup.combine_gradients(
            g2, inverse, m, counts=plan.sender.counts)
        owners, dest, ok = plan.owners, plan.dest, plan.ok
    else:
        @scope.stage("route")
        def my_slice(flat_idx, grads):
            my_part = linear_shard_id(split_axes, split_sizes)
            return (split_slice(flat_idx, parts, my_part, sentinel)[0],
                    split_slice_rows(grads.reshape((-1, dim)), parts,
                                     my_part))

        @scope.stage("route")
        def to_owners(uniq):
            owners = owner_fn(uniq)
            return (owners,) + bucketize(owners, num_shards, cap)

        sl, g2 = my_slice(flat_idx, grads)
        if wide:
            uniq, inverse, _valid = dedup.unique_rows(sl, m,
                                                      fill_value=sentinel)
        else:
            uniq, inverse, _valid = dedup.unique_indices(
                sl, m, fill_value=sentinel)
        summed, counts = dedup.combine_gradients(g2, inverse, m)
        owners, dest, ok = to_owners(uniq)
    kw = flat_idx.shape[1] if wide else 1  # key words per exchange entry

    quant = ef_state is not None
    new_ef = q8 = scale = None
    if quant:
        valid = (uniq[:, -1] != sentinel) if wide else (uniq != sentinel)
        summed, q8, scale, new_ef = _quantize_ef(
            uniq, summed, valid, ef_state, record_stats)

    def _key_valid(k):
        return (k[:, -1] != sentinel) if wide else (k != sentinel)

    @scope.stage("route")
    def to_buckets(uniq, counts, payload, scale, dest):
        ku = uniq if wide else uniq[:, None]
        cols = [ku, counts.astype(ku.dtype)[:, None]]
        if quant:
            # f32 scale bits ride the integer buffer as one extra channel
            cols.append(lax.bitcast_convert_type(
                scale, jnp.int32).astype(ku.dtype)[:, None])
        kc = jnp.concatenate(cols, axis=1)       # [m, kw+1(+1)]
        return (fill_buckets(kc, dest, num_shards, cap, sentinel),
                fill_buckets(payload, dest, num_shards, cap, 0))

    @scope.stage("route")
    def from_buckets(rkc, rg):
        flat_kc = rkc.reshape((-1, rkc.shape[-1]))
        k = flat_kc[:, :kw] if wide else flat_kc[:, 0]
        rc = flat_kc[:, kw].astype(jnp.int32)
        g = rg.reshape((flat_kc.shape[0], dim))
        if quant:
            # padding slots carry the single fill value in the scale
            # channel — zero them by key validity (a garbage bitcast
            # could be NaN, and 0 * NaN contaminates)
            rscale = lax.bitcast_convert_type(
                flat_kc[:, kw + 1].astype(jnp.int32), jnp.float32)
            rscale = jnp.where(_key_valid(k), rscale, 0.0)
            g = g.astype(summed.dtype) * rscale[:, None]
        elif wire_dtype is not None:
            g = unpin_wire(g, wire_dtype).astype(summed.dtype)
        return k, g, rc

    @scope.stage("route")
    def rows_from_buckets(rg):
        g = rg.reshape((num_shards * cap, dim))
        if wire_dtype is not None:
            g = unpin_wire(g, wire_dtype).astype(summed.dtype)
        return g

    @scope.stage("push_routed")
    def routed():
        payload = q8 if quant else (
            summed if wire_dtype is None
            else pin_wire(summed.astype(wire_dtype)))
        if plan is not None:
            send_g = scope.stage("route")(
                lambda payload, dest: fill_buckets(
                    payload, dest, num_shards, cap, 0))(payload, dest)
            return merge_plan(rows_from_buckets(
                grid_all_to_all(send_g, grid_axes, grid_sizes)))
        send_kc, send_g = to_buckets(uniq, counts, payload, scale, dest)
        rkc = grid_all_to_all(send_kc, grid_axes, grid_sizes)
        rg = grid_all_to_all(send_g, grid_axes, grid_sizes)
        return merge_fn(*from_buckets(rkc, rg))

    gather_all = scope.stage("exchange")(
        lambda x: lax.all_gather(x, tuple(grid_axes), tiled=True))

    @scope.stage("push_spilled")
    def gathered():
        k = gather_all(uniq)            # [P*m] or [P*m, 2]
        c = gather_all(counts)
        if quant:
            g = gather_all(q8).astype(summed.dtype) \
                * gather_all(scale)[:, None]
        elif wire_dtype is not None:
            narrowed = pin_wire(summed.astype(wire_dtype))
            g = unpin_wire(gather_all(narrowed),
                           wire_dtype).astype(summed.dtype)
        else:
            g = gather_all(summed)
        return merge_fn(k, g, c)

    if cap >= m:
        # buckets can hold the whole slice: bucketize cannot overflow
        out = routed()
        return (out, new_ef) if quant else out
    local_spill = jnp.sum((owners < num_shards) & ~ok).astype(jnp.int32)
    spilled = plan.spilled if plan is not None else scope.stage("exchange")(
        lambda x: lax.psum(x, tuple(grid_axes)))(local_spill)
    # per-device residue: the callback fires on every device shard, so the
    # host accumulator sums locals into the global total
    record_stat("a2a_extra_entries_push", local_spill, record_stats)
    # each merged leaf as long as the longer branch leaves it
    lengths = jax.tree.map(
        lambda a, b: max(a.shape[0], b.shape[0]),
        *(jax.eval_shape(branch) for branch in (routed, gathered)))

    def padded(branch):
        return lambda: jax.tree.map(
            lambda x, n: jnp.pad(
                x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)),
            branch(), lengths)

    out = lax.cond(spilled == 0, padded(routed), padded(gathered))
    return (out, new_ef) if quant else out


def _match_prev_keys(uniq, pk):
    """(candidate index into pk, exact-equality flag) per current key.

    Narrow keys: sort the previous step's keys once, binary-search each
    current key, verify exactly. Wide ``[m, 2]`` pair keys: sort by a
    32-bit multiplicative mix of (lo, hi) and verify BOTH words exactly
    — a mix collision between two previous keys can hide (never corrupt)
    one residual. O(m log m) compute, O(m) memory.
    """
    wide = uniq.ndim == 2

    def _mix(k):
        lo = k[:, 0].astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
        hi = k[:, 1].astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
        return (lo ^ hi).astype(jnp.int32)

    cur = _mix(uniq) if wide else uniq
    prev = _mix(pk) if wide else pk
    order = jnp.argsort(prev)
    pos = jnp.searchsorted(prev[order], cur)
    cand = order[jnp.clip(pos, 0, pk.shape[0] - 1)]
    hit_rows = jnp.take(pk, cand, axis=0)
    if wide:
        eq = jnp.all(hit_rows == uniq, axis=-1)
    else:
        eq = hit_rows == uniq
    return cand, eq


def _quantize_ef(uniq, summed, valid, ef_state, record_stats: bool):
    """int8 error-feedback quantization of one sender's pre-reduced rows.

    ``ef_state = (prev_keys, prev_resid)``: the PREVIOUS step's unique
    keys and quantization errors of THIS sender (positional — see
    ``precision.EFState``). Returns ``(summed_ef, q8, scale, (keys,
    resid))``: the residual-carried totals, their int8 payload, the
    per-row f32 scales, and the new residual to thread forward. Both
    wire branches dequantize ``q8 * scale``, so the stored residual is
    exactly the error the owner will see — recirculated next step.
    """
    pk, pr = ef_state
    total = summed
    if pk.shape[0]:
        # sort-based matching, O(m log m): a broadcast m x m0 equality
        # would cost O(m^2) compare/memory — 1.8e8 bools at the fused
        # deepfm stream size. Wide (pair) keys match on a 32-bit mix
        # with exact verification; a prev-side mix collision can at
        # worst hide one residual for one step (forfeited, not
        # corrupted — the verify is exact)
        cand, eq = _match_prev_keys(uniq, pk)
        # sentinel rows may "match" sentinel padding in pk — harmless
        # (padding residual is stored as exact zero), but gate on the
        # current row's validity anyway so padding stays all-zero
        hit = eq & valid
        carry = jnp.where(hit[:, None], jnp.take(pr, cand, axis=0), 0.0)
        total = summed + carry.astype(summed.dtype)
    absmax = jnp.max(jnp.abs(total.astype(jnp.float32)), axis=1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)
    q8 = jnp.clip(jnp.round(total.astype(jnp.float32) / scale[:, None]),
                  -127, 127).astype(jnp.int8)
    deq = q8.astype(jnp.float32) * scale[:, None]
    resid = jnp.where(valid[:, None],
                      total.astype(jnp.float32) - deq, 0.0)
    record_float_stat("quant_error_max", jnp.max(jnp.abs(resid)),
                      record_stats)
    record_float_stat("quant_residual_norm",
                      jnp.sqrt(jnp.sum(resid * resid)), record_stats)
    return total, q8, scale, (uniq, resid)


@host_fn
def routing_overflow(indices, num_shards: int, slice_parts: int,
                     owner_of, capacity: int = 0, slack: float = 2.0) -> int:
    """Host-side diagnostic: how many uniques spill past round 1's buckets?

    Sizes the bucket capacity for a sample batch the way the exchange does
    (dedup per slice, bucket by owner) and counts past-capacity uniques —
    the reference measures batch key-overlap the same way before sizing its
    dedup structures (laboratory/benchmark/analyze.py). 0 means the exchange
    finishes in one round for this batch shape + key distribution; a nonzero
    count is re-routed by the residue loop (extra rounds, never data loss).
    """
    import numpy as np
    flat = np.asarray(indices).ravel()
    n = flat.shape[0]
    m = -(-n // slice_parts)
    cap = bucket_capacity(m, num_shards, capacity, slack)
    dropped = 0
    for p in range(slice_parts):
        sl = flat[p * m:(p + 1) * m]
        uniq = np.unique(sl)
        owners = np.asarray(owner_of(uniq))
        keep = owners < num_shards
        counts = np.bincount(owners[keep], minlength=num_shards)
        dropped += int(np.maximum(counts - cap, 0).sum())
    return dropped
