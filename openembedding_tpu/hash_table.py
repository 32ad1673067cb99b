"""Hash-table embedding variant for unbounded int64 key spaces.

TPU-native redesign of the reference's hash-table embedding
(/root/reference/openembedding/variable/EmbeddingTable.h:55-118 —
``EasyHashMap<key, T*>`` + block pool, selected when
``vocabulary_size >= 2^63``, Meta.h:44-46): a **static-capacity
open-addressing table in HBM** so every lookup/insert is a fixed-shape XLA
program (no host round trips, no dynamic allocation):

* ``keys``: ``[capacity]`` array, ``EMPTY`` sentinel for free slots; weights
  and optimizer slots are parallel ``[capacity, ...]`` arrays as in the array
  table.
* The slot space is organized in **buckets of 128 slots** (one int32 lane
  row, so a bucket is a single aligned DMA for the Pallas probe kernel and a
  single contiguous row gather for XLA). A key hashes to a start bucket and
  may overflow into the next bucket(s) of its chain — ``max_probes`` is the
  total probed slots (chain length = ``max_probes / 128`` buckets; tables
  smaller than a bucket degenerate to one whole-table bucket).
* **Lookup** gathers the chain's ``[n, W]`` candidate keys in one pass, then
  a masked argmax. A key is only ever placed in bucket ``b+j`` if buckets
  ``b..b+j-1`` were full at insert time, and slots are never freed — so the
  chain scan is exact up to chain overflow.
* **Insert** is the reference's deferred materialization
  (EmbeddingOptimizerVariable.h:242-266: pull lazily creates rows in
  ``_new_weights``, merged on the next update) made functional: a *pull* of a
  missing key returns its **deterministic per-key initializer row** (PRNG
  folded with the key) without mutating anything; the *update* inserts the
  row (claim-based parallel probing, ``lax.fori_loop`` over probe rounds) and
  applies the gradient on top of that same deterministic init. Pull-then-push
  therefore behaves exactly as if the row had materialized on pull.
* Window overflow (table nearly full / pathological clustering) drops the
  update and bumps ``insert_failures`` — observable, like the reference's
  table growth being observable via item pool stats. Size the capacity for a
  load factor <= ~0.7 and the default 32-probe window is effectively exact.

Key dtype follows the incoming indices (int32 by default). The reference's
full 2^62 hashed key space is available two ways: ``key_width=64`` stores
keys as [capacity, 2] int32 (lo, hi) pairs and takes [n, 2] pair queries —
NO global flag needed (cf. ``split64``/``join64``); or
``key_dtype=jnp.int64`` under ``jax_enable_x64``. The ``EMPTY`` sentinel is
``iinfo(dtype).min`` — the same value dedup uses as its padding fill, so
padding slots are naturally invalid keys here (wide slots are free iff the
HI word is EMPTY).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from flax import struct

from .analysis import scope
from .meta import EmbeddingVariableMeta
from .ops import dedup
from .optim.initializers import Initializer, make_initializer
from .optim.optimizers import SparseOptimizer, make_optimizer
from .parallel.alltoall import record_stat
from . import table as table_lib

BUCKET = 128            # slots per bucket = one int32 lane row
DEFAULT_MAX_PROBES = 256  # probed slots per lookup (2-bucket chain)


def empty_key(dtype) -> int:
    return int(jnp.iinfo(dtype).min)


# --- wide (64-bit) keys without jax_enable_x64 -------------------------------
#
# A process without the global x64 flag cannot hold jnp int64 arrays, but the
# reference's key space is 2^62 (hashed ids, criteo_deepctr.py
# to_hash_bucket_fast(2**62)). Wide keys are therefore carried as [n, 2]
# int32 (lo, hi) pairs end-to-end on device; a slot is free iff its hi word
# equals the EMPTY sentinel (keys with hi == INT32_MIN are excluded — the
# top 2^32 of a 2^64 space, matching the reference's own 2^62 bound).

def is_wide(keys: jnp.ndarray) -> bool:
    """[n, 2] (lo, hi) pair keys vs plain [n] keys."""
    return keys.ndim == 2


def split64(keys64: np.ndarray) -> np.ndarray:
    """Host helper: int64 numpy keys -> [n, 2] int32 (lo, hi) pairs."""
    k = np.asarray(keys64, np.int64)
    return np.stack([(k & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
                     (k >> 32).astype(np.int32)], axis=-1)


def join64(pairs: np.ndarray) -> np.ndarray:
    """Host helper: [n, 2] int32 pairs -> int64 numpy keys."""
    p = np.asarray(pairs)
    lo = p[..., 0].view(np.uint32).astype(np.uint64)
    hi = p[..., 1].astype(np.int64)
    return (hi << np.int64(32)) | lo.astype(np.int64)


def widen_ids(ids: jnp.ndarray) -> jnp.ndarray:
    """Narrow integer ids (any shape) -> ``[..., 2]`` int32 (lo, hi) pairs.

    The device-side bridge that lets WIDE tables (the default hash key
    space) accept plain int32/int64 id columns: each id becomes the pair
    encoding of its sign-extended 64-bit value, so a pipeline feeding
    int32 ids and one feeding ``split64`` pairs address the same rows.
    The narrow dtype's own invalid sentinel (its minimum value — the
    framework-wide EMPTY/padding id) maps to the EMPTY pair, preserving
    the invalid-id contract across the widening.
    """
    ids = jnp.asarray(ids)
    empty = jnp.int32(empty_key(jnp.int32))
    if ids.dtype.itemsize == 8:
        lo = (ids & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(
            jnp.int32)
        hi = (ids >> jnp.int64(32)).astype(jnp.int32)
        invalid = ids == jnp.iinfo(jnp.int64).min
    else:
        ids = ids.astype(jnp.int32)
        lo = ids
        hi = ids >> jnp.int32(31)      # arithmetic: 0 or -1 (sign extend)
        invalid = ids == empty
    pair = jnp.stack([lo, hi], axis=-1)
    return jnp.where(invalid[..., None], empty, pair)


def pair_mod(pairs: jnp.ndarray, g: int) -> jnp.ndarray:
    """``join64(pairs) mod g`` computed in 32-bit words (x64-off safe).

    The serving shard-group owner rule for wide keys — identical to the
    narrow rule ``id % g`` on the joined 64-bit value, so a model keeps
    its placement across key-width migrations (int32 dump -> wide table,
    wide dump -> int64 table). Python-modulo semantics (result in
    [0, g)): ``(hi*2^32 + lo_unsigned) mod g`` decomposes as
    ``((hi mod g) * (2^32 mod g) + lo mod g) mod g``; every intermediate
    fits int32 for any realistic shard count (g < 2^15).
    """
    if not 0 < g < (1 << 15):
        raise ValueError(f"shard count {g} out of range [1, 2^15)")
    hi_m = jnp.mod(pairs[..., 1], jnp.int32(g))           # in [0, g)
    lo_m = (pairs[..., 0].astype(jnp.uint32)
            % jnp.uint32(g)).astype(jnp.int32)
    return jnp.mod(hi_m * jnp.int32((1 << 32) % g) + lo_m, jnp.int32(g))


def _mix_pair(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """32-bit-only avalanche over a key pair (x64-off safe)."""
    a = lo.astype(jnp.uint32)
    b = hi.astype(jnp.uint32)
    h = a ^ (b * jnp.uint32(0x9E3779B9))
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = h ^ b
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def table_layout(capacity: int, max_probes: int) -> Tuple[int, int, int]:
    """(bucket_size, num_buckets, chain_buckets) for a table's slot space.

    ``capacity`` must be a multiple of the bucket size (``round_capacity``
    does the rounding at creation). Tables smaller than ``BUCKET`` collapse
    to a single whole-table bucket.
    """
    b = min(BUCKET, capacity)
    if capacity % b:
        raise ValueError(
            f"hash-table capacity {capacity} is not a multiple of the "
            f"bucket size {b}; use round_capacity() when allocating")
    nb = capacity // b
    chain = max(1, min(max_probes // b, nb))
    return b, nb, chain


def round_capacity(capacity: int) -> int:
    """Round a requested capacity up to the bucket granularity."""
    if capacity >= BUCKET:
        return -(-capacity // BUCKET) * BUCKET
    return capacity


def probe_window(capacity: int, max_probes: int) -> int:
    """Total probed slots per lookup (chain_buckets * bucket_size)."""
    b, _nb, chain = table_layout(capacity, max_probes)
    return b * chain


def probe_starts(keys: jnp.ndarray, capacity: int,
                 max_probes: int) -> jnp.ndarray:
    """First probe SLOT per key — always bucket-aligned.

    ``mix(key) % (num_buckets - chain + 1) * bucket_size``: the whole chain
    fits without wrapping, so a lookup's candidate slots are one CONTIGUOUS
    aligned run — a single ``[chain, 128]`` DMA for the Pallas probe kernel,
    plain ``start + i`` adds everywhere else. The last ``chain - 1`` buckets
    are only reachable as chain tails; the occupancy skew is
    O(chain/num_buckets), negligible at real sizes.
    """
    b, nb, chain = table_layout(capacity, max_probes)
    if is_wide(keys):
        mixed = _mix_pair(keys[:, 0], keys[:, 1])
    else:
        mixed = _mix(keys)
    span = jnp.asarray(nb - chain + 1, mixed.dtype)
    return ((mixed % span).astype(jnp.int32)) * b


def _mix(keys: jnp.ndarray) -> jnp.ndarray:
    """Avalanche-mix keys to probe start positions (unsigned arithmetic).

    murmur3/splitmix-style finalizer so sequential or strided ids spread
    uniformly — the reference gets this from EasyHashMap's hash policy.
    """
    if keys.dtype.itemsize == 8:
        u = keys.astype(jnp.uint64)
        u = (u ^ (u >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
        u = (u ^ (u >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
        u = u ^ (u >> 33)
    else:
        u = keys.astype(jnp.uint32)
        u = (u ^ (u >> 16)) * jnp.uint32(0x85EBCA6B)
        u = (u ^ (u >> 13)) * jnp.uint32(0xC2B2AE35)
        u = u ^ (u >> 16)
    return u


@struct.dataclass
class HashTableState:
    """Pytree for one hash-table shard."""

    keys: jnp.ndarray                    # [capacity], EMPTY = free
    weights: jnp.ndarray                 # [capacity, dim]
    slots: Dict[str, jnp.ndarray]        # each [capacity, ...]
    init_rng: jax.Array                  # base PRNG for per-key row init
    insert_failures: jnp.ndarray         # int32 scalar, probe-window overflows

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def dim(self) -> int:
        return self.weights.shape[1]

    @property
    def wide(self) -> bool:
        return self.keys.ndim == 2

    def num_used(self) -> jnp.ndarray:
        empty = empty_key(self.keys.dtype)
        live = (self.keys[:, 1] != empty) if self.wide \
            else (self.keys != empty)
        return jnp.sum(live).astype(jnp.int32)


def create_hash_table(meta: EmbeddingVariableMeta,
                      optimizer: Any,
                      *,
                      capacity: int,
                      rng: Optional[jax.Array] = None,
                      key_dtype=jnp.int32,
                      key_width: int = 32) -> HashTableState:
    """Allocate an empty hash table shard.

    ``capacity`` plays the reference's ``reserve_items`` role
    (EmbeddingInitOperator.cpp:138-168) — hash vocabularies are unbounded so
    the caller must budget rows. Rounded up to the bucket granularity.
    ``key_width=64`` stores keys as [capacity, 2] int32 (lo, hi) pairs —
    the reference's 2^62 key space WITHOUT the global jax_enable_x64 flag
    (queries then come as [n, 2] pairs, cf. :func:`split64`).
    """
    optimizer = make_optimizer(optimizer)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    capacity = round_capacity(capacity)
    dtype = table_lib.resolve_dtype(meta)
    dim = meta.embedding_dim
    if key_width == 64:
        keys = jnp.full((capacity, 2), empty_key(jnp.int32),
                        dtype=jnp.int32)
    else:
        keys = jnp.full((capacity,), empty_key(key_dtype), dtype=key_dtype)
    # weights hold placeholder zeros; live rows are written on insert with the
    # deterministic per-key init, so this buffer's initial content never leaks.
    weights = jnp.zeros((capacity, dim), dtype=dtype)
    slots = optimizer.init_slots(capacity, dim, dtype)
    return HashTableState(keys=keys, weights=weights, slots=slots,
                          init_rng=rng,
                          insert_failures=jnp.zeros((), jnp.int32))


def _wide_query(keys: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Validate + flatten a wide-table query to [n, 2] pairs."""
    if indices.ndim < 2 or indices.shape[-1] != 2:
        raise ValueError(
            f"key-shape mismatch: wide (64-bit pair) tables take [..., 2] "
            f"int32 queries (hash_table.split64), got {indices.shape}")
    return check_key_dtype(keys, indices.reshape(-1, 2))


def init_rows(initializer: Initializer, base_rng: jax.Array,
              keys: jnp.ndarray, dim: int, dtype) -> jnp.ndarray:
    """Deterministic initializer row per key: fold key into the base PRNG.
    Wide keys fold both words, so rows depend on the full 64-bit key."""
    if is_wide(keys):
        def one(base_rng, k):
            r = jax.random.fold_in(base_rng, k[0])
            return initializer.init(jax.random.fold_in(r, k[1]),
                                    (dim,), dtype)
    else:
        def one(base_rng, k):
            return initializer.init(jax.random.fold_in(base_rng, k),
                                    (dim,), dtype)
    return scope.stage("init_rows")(jax.vmap(one, in_axes=(None, 0)))(
        base_rng, keys)


def check_key_dtype(table_keys: jnp.ndarray, query: jnp.ndarray) -> jnp.ndarray:
    """Cast query keys to the table's key dtype, refusing silent truncation.

    A table created with int32 keys cannot address an int64 id space — that
    would alias ids modulo 2^32. Use ``key_width=64`` (pair keys, works
    with x64 off) or ``key_dtype=jnp.int64`` (requires jax_enable_x64) for
    the reference's full 2^62 hashed key space.
    """
    if is_wide(table_keys) != is_wide(query):
        raise ValueError(
            f"key-shape mismatch: table keys {table_keys.shape} vs query "
            f"{query.shape} — wide (64-bit pair) tables take [n, 2] int32 "
            "queries (hash_table.split64)")
    if query.dtype.itemsize > table_keys.dtype.itemsize:
        raise ValueError(
            f"query keys are {query.dtype} but the table stores "
            f"{table_keys.dtype} keys; create the table with "
            f"key_dtype={query.dtype} (int64 needs jax_enable_x64) or "
            "key_width=64 (pair keys, x64-off)")
    return query.astype(table_keys.dtype)


def find_rows(table_keys: jnp.ndarray, query: jnp.ndarray,
              max_probes: int = DEFAULT_MAX_PROBES) -> jnp.ndarray:
    """Slot index for each query key, or -1 when absent / invalid.

    Probes by gathering whole bucket ROWS (``[n, chain, 128]`` via a row
    gather of the ``[num_buckets, 128]`` key view), then a masked
    first-match. Row gathers are the operation XLA's TPU gather is built
    for; the element-wise ``[n, W]`` scalar gather an earlier layout needed
    measured ~30x slower on v5e (2.1 ms vs 61 ms for 32k lookups in a
    2^22-slot table) — the bucket-aligned layout is what makes the probe a
    row gather.
    """
    return scope.stage("probe")(
        lambda table_keys, query: _find_rows(table_keys, query, max_probes))(
            table_keys, query)


def _find_rows(table_keys, query, max_probes):
    query = check_key_dtype(table_keys, query)
    capacity = table_keys.shape[0]
    n = query.shape[0]
    bsz, nb, chain = table_layout(capacity, max_probes)
    h = probe_starts(query, capacity, max_probes)
    b0 = h // bsz
    bkts = b0[:, None] + jnp.arange(chain, dtype=jnp.int32)[None, :]
    empty = empty_key(table_keys.dtype)
    if is_wide(table_keys):
        probed = jnp.take(table_keys.reshape(nb, bsz, 2), bkts, axis=0)
        probed = probed.reshape(n, chain * bsz, 2)
        match = ((probed[..., 0] == query[:, None, 0])
                 & (probed[..., 1] == query[:, None, 1]))
        valid = query[:, 1] != empty
    else:
        probed = jnp.take(table_keys.reshape(nb, bsz), bkts, axis=0)
        match = probed.reshape(n, chain * bsz) == query[:, None]
        valid = query != empty
    hit = jnp.any(match, axis=1)
    first = jnp.argmax(match, axis=1).astype(jnp.int32)
    slot = h + first
    return jnp.where(hit & valid, slot, -1)


def insert_width(n: int) -> int:
    """Keys the compact buffer of a :func:`find_or_insert` call of ``n``
    keys holds, and the most that may miss for its loop to place them: an
    eighth of the call, rounded up to 1024. It decides WHICH loop places
    the misses, not what that costs: the loop over the buffer walks the
    misses alone, ``table.INSERT_CHUNK`` a trip. A training push misses
    ~5% of its keys; a bulk load misses all of them and takes the
    full-width loop."""
    return -(-n // (8 * 1024)) * 1024


def find_or_insert(table_keys: jnp.ndarray, new_keys: jnp.ndarray,
                   valid: jnp.ndarray,
                   max_probes: int = DEFAULT_MAX_PROBES,
                   record_stats: bool = False,
                   found: Optional[jnp.ndarray] = None,
                   known: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Find each (unique) key's slot, inserting missing keys.

    Two phases. *Find*: every key probes its chain for a match, one row
    gather a level and no insert machinery, as the pull's
    :func:`find_rows` does; a key found there is done. A call wider than
    ``table.FIND_CHUNK`` walks its keys a chunk a trip and stops after the
    last valid one, so the find costs what the keys of a step cost, not
    what the padded unique buffer would. A caller that holds the find's
    answer hands it over as ``found`` ([n] int32, -1 for a key that is not
    in the table or not ``valid``) and the phase is skipped: the push of a
    train step, whose pull found these keys under this mask
    (:func:`pull_distinct`) with no write to the table between the two.
    ``known`` ([n] bool) says for which keys ``found`` is that answer: the
    find then walks the others alone (the routed push behind a step its
    buckets did not hold, whose owner met keys no pull resolved), and
    none where every valid key is known.
    *Insert*: the keys
    that missed, in their original order, are compacted into the front of
    a buffer of :func:`insert_width` keys and only they run the insert
    loop below, ``table.INSERT_CHUNK`` keys a trip, every trip of a level
    before the next level (:func:`_insert_trips`); their slots are written
    back to the keys' positions. When more keys miss than the buffer holds
    (a bulk load, a cold table's first steps) the loop runs over all ``n``
    keys instead, a level a pass: both loops are in the program, the
    observed count leaves one of them without a key to place, and a loop
    stops at the first level that finds none. Where the buffer would be
    no narrower than the call (small calls) the full-width loop is all
    there is, over every key, or over the misses where ``found`` says
    which they are. A key already in the table can never take a slot (its
    earlier chain buckets are full), and compaction and the trips keep
    every other contender's rank within its bucket, so slots,
    ``inserted``, ``failed`` and the key array are the same whichever
    loop placed the keys.

    The insert loop makes one pass per chain level: every unplaced key
    probes its level-j bucket — a contiguous 128-slot row — matches
    existing entries, then unmatched keys are assigned free slots by RANK:
    contenders for the same bucket are grouped (stable sort by bucket id),
    ranked within the group, and rank r takes the bucket's (r+1)-th free
    slot. Keys are unique, ranks within a bucket are unique, so assignments
    never collide; keys ranked past the free count overflow to the next
    chain level — which is exactly the "only overflow when the bucket
    filled up" invariant lookup relies on.

    A level costs O(width * 128) gathers + O(width log width) sort work
    and a scatter of ``width`` keys into the key array, where ``width`` is
    the call's keys in the full-width loop and the misses, rounded up to
    whole trips, in the compact one (a push that places 1,000 keys of
    106,496 walks 1,024, not the buffer's 13,312; one that places none
    runs no level) — *independent of table capacity* (an earlier design
    materialized a [capacity] claim buffer per probe round:
    O(max_probes * capacity) HBM traffic per insert call, benign at 2^23
    rows, fatal at the reference's 10^9-row scale, documents/en/pmem.md
    north star).

    ``record_stats`` (the trace-time gate of ``alltoall.record_stat``)
    counts ``hash_insert_compact`` / ``hash_insert_full``, the calls that
    ran the loop over the buffer / over every key,
    ``hash_insert_missed``, the keys that were not in the table,
    ``hash_insert_keys_walked``, the keys the compact loop's trips walked,
    summed over the levels it ran (0 where the full-width loop ran: over
    ``hash_insert_missed``, the padding a call still pays), and
    ``hash_find_slots_live`` / ``hash_find_slots_walked``, the valid keys
    of a call and the keys its find walked (none where ``found`` came with
    the call).

    Returns ``(table_keys, slot [n] (-1 = failed), inserted [n],
    failed [n])``.
    """
    return scope.stage("probe")(
        lambda table_keys, new_keys, valid, found, known: _find_or_insert(
            table_keys, new_keys, valid, max_probes, record_stats, found,
            known))(table_keys, new_keys, valid, found, known)


def _find_or_insert(table_keys, new_keys, valid, max_probes, record_stats,
                    found=None, known=None):
    n = new_keys.shape[0]
    m = insert_width(n)
    if known is not None:
        # a key nothing is known of is found by the loop that places it
        # (small calls) or by a find of its own
        found = jnp.where(known, found, -1)
    if m >= n:
        out = _insert_levels(
            table_keys, new_keys,
            valid if found is None else valid & (found < 0), max_probes,
            slot0=found)
        _, _, inserted, failed = out
        record_stat("hash_insert_full", jnp.int32(1), record_stats)
        record_stat("hash_insert_missed",
                    jnp.sum(inserted | failed, dtype=jnp.int32), record_stats)
        return out

    if found is None:
        found, walked = _find_levels(table_keys, new_keys, valid, max_probes)
    elif known is not None:
        sought, walked = _find_levels(table_keys, new_keys, valid & ~known,
                                      max_probes)
        found = jnp.where(known, found, sought)
    else:
        walked = jnp.int32(0)
    miss = valid & (found < 0)
    missed = jnp.sum(miss, dtype=jnp.int32)
    fits = missed <= m
    # The misses' positions in their order, then n: ascending, so every
    # contender keeps its rank within its bucket.
    at = jnp.sort(jnp.where(miss, jnp.arange(n, dtype=jnp.int32), n))[:m]
    # One of the two loops has keys to place and the other runs no level.
    # Under a lax.cond the v5e compiler copies the key array on its way
    # into a branch's loop (512 MiB a table at 2^26 wide slots); through
    # two whiles it stays in place, as through the one. The misses are the
    # first ``missed`` keys of the buffer, and its loop walks those alone.
    table_keys, slot_m, walked_m = _insert_trips(
        table_keys, jnp.take(new_keys, at, axis=0, mode="clip"),
        jnp.where(fits, missed, 0), max_probes)
    table_keys, slot, _, _ = _insert_levels(
        table_keys, new_keys, valid & ~fits, max_probes,
        slot0=found.at[at].set(slot_m, mode="drop"))
    record_stat("hash_insert_compact", fits.astype(jnp.int32), record_stats)
    record_stat("hash_insert_full", (~fits).astype(jnp.int32), record_stats)
    record_stat("hash_insert_missed", missed, record_stats)
    record_stat("hash_insert_keys_walked", walked_m, record_stats)
    record_stat("hash_find_slots_live", jnp.sum(valid, dtype=jnp.int32),
                record_stats)
    record_stat("hash_find_slots_walked", walked, record_stats)
    # a key that missed is in no bucket the loop reads for it: it is placed
    # or it fails, it never hits
    return table_keys, slot, miss & (slot >= 0), miss & (slot < 0)


def _bucket_masks(keys_arr, query, bkt, max_probes):
    """One row gather: bucket ``bkt[i]`` of the table against ``query[i]``.
    ``([n, bucket] slot holds the key, [n, bucket] slot is free)``."""
    bsz, nb, _chain = table_layout(keys_arr.shape[0], max_probes)
    empty = empty_key(keys_arr.dtype)
    if is_wide(keys_arr):
        rows = jnp.take(keys_arr.reshape(nb, bsz, 2), bkt, axis=0)
        return ((rows[..., 0] == query[:, None, 0])
                & (rows[..., 1] == query[:, None, 1]),
                rows[..., 1] == empty)
    rows = jnp.take(keys_arr.reshape(nb, bsz), bkt, axis=0)
    return rows == query[:, None], rows == empty


def _find_levels(table_keys, query, valid, max_probes):
    """What :func:`find_rows` finds for the valid keys, one chain level at
    a time (a bulk load's whole-chain gather is a 4 GiB temporary, and as
    much again for its relayout) and, in a call wider than
    ``table.FIND_CHUNK``, one chunk of keys a trip up to the last valid
    one: a push hands over the whole unique buffer, whose keys fill a
    prefix of it (a third, for Criteo-shaped ids), and a bucket row
    gathered for padding is thrown away. The loop only reads the key array
    and sits under no conditional, so the array stays where it is.
    ``(slot [n], keys walked)``."""
    capacity = table_keys.shape[0]
    n = query.shape[0]
    chunk = table_lib.FIND_CHUNK
    bsz, _nb, chain = table_layout(capacity, max_probes)

    def levels(query, valid):
        b0 = probe_starts(query, capacity, max_probes) // bsz
        slot = jnp.full(query.shape[:1], -1, jnp.int32)
        for j in range(chain):
            match, _ = _bucket_masks(table_keys, query, b0 + j, max_probes)
            hit = valid & (slot < 0) & jnp.any(match, axis=1)
            first = jnp.argmax(match, axis=1).astype(jnp.int32)
            slot = jnp.where(hit, (b0 + j) * bsz + first, slot)
        return slot

    if n <= chunk:
        return levels(query, valid), jnp.int32(n)
    trips = (table_lib.occupied_prefix(valid) + (chunk - 1)) // chunk
    # whole chunks only, as table.apply_rows pads: the padding is invalid
    short = -n % chunk
    query, valid = (jnp.pad(x, [(0, short)] + [(0, 0)] * (x.ndim - 1))
                    for x in (query, valid))

    def trip(i, slot):
        part = levels(*(lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                        for x in (query, valid)))
        return lax.dynamic_update_slice_in_dim(slot, part, i * chunk, 0)

    slot = lax.fori_loop(0, trips, trip,
                         jnp.full((n + short,), -1, jnp.int32))
    return slot[:n], trips * chunk


def _level_placer(capacity, n, max_probes):
    """One chain level of the insert loop for ``n`` keys at a time, as a
    function ``(key array, keys [n], their level's buckets [n], valid [n],
    slot [n], done [n]) -> (key array, slot, done, placed [n])``: every
    valid key not done probes its bucket (a contiguous 128-slot row); a
    key found there is done, and the others are placed by rank among the
    contenders for their bucket, in the keys' order. A key ranked past the
    bucket's free slots stays not done and overflows to the next level."""
    bsz, nb, _chain = table_layout(capacity, max_probes)
    oob = jnp.asarray(capacity, jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)

    def place_level(keys_arr, new_keys, bj, valid, slot, done):
        start = bj * bsz
        match, emptym = _bucket_masks(keys_arr, new_keys, bj, max_probes)
        active = valid & ~done
        # already present (keys are unique; at most one slot matches)
        hitm = active & jnp.any(match, axis=1)
        moff = jnp.argmax(match, axis=1).astype(jnp.int32)
        slot = jnp.where(hitm, start + moff, slot)
        done = done | hitm
        active = active & ~hitm
        # rank contenders within each bucket: stable sort by bucket id,
        # rank = distance from the group's first sorted position
        bid = jnp.where(active, bj, nb)
        order = jnp.argsort(bid, stable=True)
        sorted_bid = bid[order]
        seg = jnp.concatenate([
            jnp.ones((1,), bool), sorted_bid[1:] != sorted_bid[:-1]])
        group_start = lax.cummax(jnp.where(seg, ids, 0))
        rank = jnp.zeros((n,), jnp.int32).at[order].set(ids - group_start)
        # rank r takes the (r+1)-th free slot of the bucket
        cum = jnp.cumsum(emptym, axis=1).astype(jnp.int32)
        nfree = cum[:, -1]
        place = active & (rank < nfree)
        tgt = jnp.argmax((cum == rank[:, None] + 1) & emptym,
                         axis=1).astype(jnp.int32)
        pslot = start + tgt
        keys_arr = keys_arr.at[jnp.where(place, pslot, oob)].set(
            new_keys, mode="drop")
        return keys_arr, jnp.where(place, pslot, slot), done | place, place

    return place_level


def _insert_levels(table_keys, new_keys, valid, max_probes, slot0=None):
    """The insert loop of :func:`find_or_insert` over every key given, one
    chain level at a time while a valid key is neither found nor placed: a
    level costs the call's width, however few of its keys are left.
    ``slot0`` is what ``slot`` reads for a key the loop does neither to."""
    capacity = table_keys.shape[0]
    n = new_keys.shape[0]
    bsz, _nb, chain = table_layout(capacity, max_probes)
    h = probe_starts(new_keys, capacity, max_probes)
    b0 = h // bsz
    place_level = _level_placer(capacity, n, max_probes)

    def level(carry):
        j, keys_arr, slot, done, inserted = carry
        keys_arr, slot, done, place = place_level(
            keys_arr, new_keys, b0 + j, valid, slot, done)
        inserted = inserted | place
        return j + 1, keys_arr, slot, done, inserted

    if slot0 is None:
        slot0 = jnp.full((n,), -1, jnp.int32)
    done0 = ~valid
    ins0 = jnp.zeros((n,), bool)
    def unplaced(carry):
        j, _keys_arr, _slot, done, _inserted = carry
        return (j < chain) & ~jnp.all(done)

    _, table_keys, slot, done, inserted = lax.while_loop(
        unplaced, level, (jnp.int32(0), table_keys, slot0, done0, ins0))
    failed = valid & ~done
    return table_keys, slot, inserted, failed


def _insert_trips(table_keys, new_keys, count, max_probes):
    """The insert loop of :func:`find_or_insert` over the first ``count``
    of the keys given (the compacted misses, none of them in the table),
    ``table.INSERT_CHUNK`` keys a trip: a level costs the trips that hold a
    key, not the buffer. Levels outside, trips inside: within a level the
    trips run in the keys' order and each sees the key array as the trips
    before it left it, whose keys took their buckets' first free slots, so
    a key's rank among its bucket's contenders, its slot and the level at
    which it overflows are :func:`_insert_levels`' over the whole buffer.
    The key array is carried through both loops and written where it is.
    ``(key array, slot [m] (-1: not placed), keys walked)``."""
    capacity = table_keys.shape[0]
    m = new_keys.shape[0]
    chunk = min(table_lib.INSERT_CHUNK, m)
    bsz, _nb, chain = table_layout(capacity, max_probes)
    # whole chunks only, as the find pads
    short = -m % chunk
    new_keys = jnp.pad(new_keys,
                       [(0, short)] + [(0, 0)] * (new_keys.ndim - 1))
    b0 = probe_starts(new_keys, capacity, max_probes) // bsz
    valid = jnp.arange(m + short, dtype=jnp.int32) < count
    place_level = _level_placer(capacity, chunk, max_probes)
    trips = (count + (chunk - 1)) // chunk

    def level(carry):
        j, keys_arr, slot, done = carry

        def trip(i, walked):
            keys_arr, slot, done = walked
            keys_i, b0_i, valid_i, slot_i, done_i = (
                lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                for x in (new_keys, b0, valid, slot, done))
            keys_arr, slot_i, done_i, _ = place_level(
                keys_arr, keys_i, b0_i + j, valid_i, slot_i, done_i)
            return (keys_arr,) + tuple(
                lax.dynamic_update_slice_in_dim(x, part, i * chunk, 0)
                for x, part in ((slot, slot_i), (done, done_i)))

        return (j + 1,) + lax.fori_loop(0, trips, trip,
                                        (keys_arr, slot, done))

    def unplaced(carry):
        j, _keys_arr, _slot, done = carry
        return (j < chain) & ~jnp.all(done)

    levels, table_keys, slot, _ = lax.while_loop(
        unplaced, level,
        (jnp.int32(0), table_keys, jnp.full((m + short,), -1, jnp.int32),
         ~valid))
    return table_keys, slot[:m], levels * trips * chunk


def insert_rows(state: HashTableState,
                keys: jnp.ndarray,
                weights: jnp.ndarray,
                slot_rows: Optional[Dict[str, jnp.ndarray]] = None,
                max_probes: int = DEFAULT_MAX_PROBES,
                record_stats: bool = False) -> HashTableState:
    """Directly set rows (and optionally optimizer-state rows) for keys.

    The load-path primitive (reference EmbeddingInitItems delivery,
    EmbeddingLoadOperator.cpp:58-111): inserts missing keys and overwrites
    weights/states verbatim — no optimizer math. ``keys`` must be unique;
    EMPTY-sentinel keys are skipped.
    """
    empty = empty_key(state.keys.dtype)
    if state.wide:
        keys = _wide_query(state.keys, keys)
        valid = keys[:, 1] != empty
    else:
        keys = check_key_dtype(state.keys, keys.ravel())
        valid = keys != empty
    keys_arr, slot, _inserted, failed = find_or_insert(
        state.keys, keys, valid, max_probes, record_stats)
    ok = valid & (slot >= 0)
    oob = jnp.asarray(state.capacity, jnp.int32)
    scatter_idx = jnp.where(ok, slot, oob)
    new_weights = state.weights.at[scatter_idx].set(
        weights.astype(state.weights.dtype), mode="drop")
    slots = dict(state.slots)
    if slot_rows:
        for name, rows in slot_rows.items():
            slots[name] = state.slots[name].at[scatter_idx].set(
                rows.astype(state.slots[name].dtype), mode="drop")
    return HashTableState(
        keys=keys_arr, weights=new_weights, slots=slots,
        init_rng=state.init_rng,
        insert_failures=state.insert_failures + jnp.sum(failed).astype(jnp.int32))


def pull(state: HashTableState, indices: jnp.ndarray,
         initializer: Any,
         max_probes: int = DEFAULT_MAX_PROBES) -> jnp.ndarray:
    """Lookup rows; missing keys return their deterministic init row.

    Mirrors the reference's pull contract (present -> stored row, absent ->
    freshly initialized row, EmbeddingOptimizerVariable.h:242-266) without
    mutation: the same init row materializes again at insert time. Keys equal
    to the EMPTY sentinel return zeros.

    ``initializer=None`` selects the **read-only** (serving) contract:
    missing keys return zero rows with no init math — the reference's
    read_only get_weights path (EmbeddingPullOperator.cpp:179-181).
    """
    if state.wide:
        flat = _wide_query(state.keys, indices)
        invalid = flat[:, 1] == empty_key(state.keys.dtype)
        out_shape = indices.shape[:-1] + (state.dim,)
    else:
        flat = check_key_dtype(state.keys, indices.ravel())
        invalid = flat == empty_key(state.keys.dtype)
        out_shape = indices.shape + (state.dim,)
    if initializer is not None:
        initializer = make_initializer(initializer)

    @scope.stage("resolve")
    def read(keys, weights, init_rng, flat, invalid):
        slot = find_rows(keys, flat, max_probes)
        hit = slot >= 0
        rows = jnp.take(weights, jnp.where(hit, slot, 0), axis=0,
                        mode="clip")
        return _or_fresh(initializer, init_rng, flat, rows, hit, invalid)

    return read(state.keys, state.weights, state.init_rng, flat,
                invalid).reshape(out_shape)


def _or_fresh(initializer, init_rng, keys, rows, hit, invalid):
    """``rows`` where ``hit``, a key's deterministic init row (zeros under
    the read-only contract) where not, zeros for an ``invalid`` key."""
    if initializer is None:
        fresh = jnp.zeros_like(rows)
    else:
        fresh = init_rows(initializer, init_rng, keys, rows.shape[1],
                          rows.dtype)
    rows = jnp.where(hit[:, None], rows, fresh)
    return jnp.where(invalid[:, None], jnp.zeros_like(rows), rows)


def pull_distinct(state: HashTableState, keys: jnp.ndarray,
                  valid: jnp.ndarray, initializer: Any,
                  max_probes: int = DEFAULT_MAX_PROBES, *,
                  positions: int, record_stats: bool = False
                  ) -> dedup.Resolution:
    """:func:`pull` for the distinct keys of a step's plan
    (``dedup.Plan.uniq`` with its ``valid``, a prefix of the buffer): one
    row a slot, each key resolved once. The find and the row read walk
    the valid prefix in chunks (:func:`_find_levels` and
    ``table.read_distinct``), so a pull costs what the distinct keys of
    the batch cost; the caller expands by the plan's ``inverse``, over
    ``positions`` keys (``table.record_pull``'s count). Returned with the
    rows are the slots the find found, -1 for a key the table does not
    hold: what the step's push would find again, and takes from here
    (:func:`merge_gradients`)."""
    if initializer is not None:
        initializer = make_initializer(initializer)
    keys = check_key_dtype(state.keys, keys)
    find = scope.stage("probe")(
        lambda tkeys, keys, valid: _find_levels(tkeys, keys, valid,
                                                max_probes))

    @scope.stage("resolve")
    def read(tkeys, weights, init_rng, keys, valid):
        slot, walked = find(tkeys, keys, valid)
        hit = slot >= 0
        rows, _ = table_lib.read_distinct(weights, slot, hit)
        table_lib.record_pull(valid, walked, positions, record_stats)
        return dedup.Resolution(
            rows=_or_fresh(initializer, init_rng, keys, rows, hit, ~valid),
            slot=slot)

    return read(state.keys, state.weights, state.init_rng, keys, valid)


def snapshot_keys(table_keys: jnp.ndarray, arrays, query: jnp.ndarray,
                  count: jnp.ndarray,
                  max_probes: int = DEFAULT_MAX_PROBES):
    """What a delta checkpoint takes of a hash table in the step's stream:
    the rows of the keys ``query[:count]`` in every array of ``arrays``
    (the weights, the optimizer's slots), in buffers of ``query``'s
    length. The find is the push's (:func:`_find_levels`: a chunk of keys
    a trip up to the last valid one, the key array read where it is)
    under a stage of its own, ``ckpt_find``; the read by slot is
    ``table.snapshot_rows``, stage ``ckpt_gather``. A key the table does
    not hold reads ``found`` false and a zero row; nothing is inserted.
    ``(found [n], rows of each array)``."""
    query = check_key_dtype(table_keys, query)
    empty = empty_key(table_keys.dtype)
    valid = ((query[:, 1] if is_wide(query) else query) != empty) \
        & (jnp.arange(query.shape[0], dtype=jnp.int32) < count)
    find = scope.stage("ckpt_find")(
        lambda tkeys, query, valid: _find_levels(tkeys, query, valid,
                                                 max_probes)[0])
    slot = find(table_keys, query, valid)
    found = slot >= 0
    oob = jnp.asarray(table_keys.shape[0], jnp.int32)
    return found, table_lib.snapshot_rows(
        arrays, jnp.where(found, slot, oob), count)


def combine_keys(state: HashTableState,
                 indices: jnp.ndarray,
                 grads: jnp.ndarray,
                 *,
                 dedup_capacity: Optional[int] = None,
                 in_counts: Optional[jnp.ndarray] = None,
                 plan: Optional[dedup.Plan] = None):
    """The half of :func:`merge_gradients` that reads nothing of the
    table (``state`` says the key form and the row width): deduplicate the
    keys and combine their gradients into a buffer of ``dedup_capacity``
    (default ``n``) slots. ``(uniq, valid, summed, counts)``. ``plan`` is
    the dedup of ``indices`` where the step has made it already, in front
    of its pull: its slots are the buffer and nothing is deduplicated
    again."""
    dim = state.dim
    empty = empty_key(state.keys.dtype)
    if plan is not None:
        uniq = check_key_dtype(state.keys, plan.uniq)
        inverse, valid = plan.inverse, plan.valid
        capacity = uniq.shape[0]
    elif state.wide:
        flat_idx = _wide_query(state.keys, indices)
        capacity = dedup_capacity or flat_idx.shape[0]
        uniq, inverse, valid = dedup.unique_pairs(
            flat_idx, capacity, fill_value=empty)
    else:
        flat_idx = check_key_dtype(state.keys, indices.ravel())
        capacity = dedup_capacity or flat_idx.shape[0]
        uniq, inverse, valid = dedup.unique_indices(
            flat_idx, capacity, fill_value=empty)
    valid = valid & ((uniq[:, 1] if state.wide else uniq) != empty)
    summed, counts = dedup.combine_gradients(
        grads.reshape(-1, dim), inverse, capacity, in_counts,
        counts=None if plan is None else plan.counts)
    return uniq, valid, summed, counts


def place_keys(state: HashTableState, initializer: Any, uniq: jnp.ndarray,
               valid: jnp.ndarray, *,
               max_probes: int = DEFAULT_MAX_PROBES,
               record_stats: bool = False,
               resolved: Optional[dedup.Resolution] = None,
               known: Optional[jnp.ndarray] = None):
    """The half of :func:`merge_gradients` that touches the key array, and
    it alone: find or insert each of :func:`combine_keys`' distinct keys.
    ``(keys, failed, slot, inserted, fresh)``: the new key array, the
    number of keys no window held, each key's slot (-1: it failed), the
    keys the call inserted, and their init rows, None where ``resolved``
    came with the call (its ``rows`` hold them). ``resolved`` is what a
    pull found for these keys, the table unwritten since: its ``slot`` is
    :func:`find_or_insert`'s ``found``, for the keys ``known`` says (every
    key, without it). ``record_stats`` then counts ``push_slots_carried``,
    the valid keys whose find the call took."""
    keys_arr, slot, inserted, failed = find_or_insert(
        state.keys, uniq, valid, max_probes, record_stats,
        found=None if resolved is None else resolved.slot, known=known)
    if resolved is None:
        fresh = init_rows(make_initializer(initializer), state.init_rng,
                          uniq, state.dim, state.weights.dtype)
    else:
        fresh = None
        record_stat("push_slots_carried", jnp.sum(
            valid if known is None else valid & known, dtype=jnp.int32),
            record_stats)
    return keys_arr, jnp.sum(failed).astype(jnp.int32), slot, inserted, fresh


def merge_gradients(state: HashTableState,
                    initializer: Any,
                    indices: jnp.ndarray,
                    grads: jnp.ndarray,
                    *,
                    dedup_capacity: Optional[int] = None,
                    max_probes: int = DEFAULT_MAX_PROBES,
                    in_counts: Optional[jnp.ndarray] = None,
                    record_stats: bool = False,
                    plan: Optional[dedup.Plan] = None,
                    resolved: Optional[dedup.Resolution] = None):
    """The first half of :func:`apply_gradients`, which touches the key
    array alone: :func:`combine_keys`, then :func:`place_keys`. Returns
    ``(keys, failed, merged)``: the new key array, the number of keys no
    window held, and ``table.apply_rows``'s ``(rows, live, summed, counts,
    fresh, inserted)``. ``plan`` is :func:`combine_keys`'s. ``resolved`` is
    what the step's pull found for the plan's slots
    (:func:`pull_distinct` of the same keys under the same mask, the
    table unwritten since): no key is looked for again, and its ``rows``
    hold a missing key's init row where the apply wants it
    (``table.apply_rows``'s ``pulled``), so ``fresh`` and ``inserted`` are
    None and no init row is made here."""
    uniq, valid, summed, counts = combine_keys(
        state, indices, grads, dedup_capacity=dedup_capacity,
        in_counts=in_counts, plan=plan)
    keys_arr, failed, slot, inserted, fresh = place_keys(
        state, initializer, uniq, valid, max_probes=max_probes,
        record_stats=record_stats, resolved=resolved)
    return (keys_arr, failed,
            (slot, valid & (slot >= 0), summed, counts, fresh,
             None if resolved is not None else inserted))


def apply_gradients(state: HashTableState,
                    optimizer: SparseOptimizer,
                    initializer: Any,
                    indices: jnp.ndarray,
                    grads: jnp.ndarray,
                    *,
                    dedup_capacity: Optional[int] = None,
                    max_probes: int = DEFAULT_MAX_PROBES,
                    in_counts: Optional[jnp.ndarray] = None,
                    record_stats: bool = False,
                    plan: Optional[dedup.Plan] = None,
                    resolved: Optional[dedup.Resolution] = None
                    ) -> HashTableState:
    """Combine duplicate grads, insert missing keys, update touched rows.

    The hash-table analogue of ``table.apply_gradients``: dedup -> claim/probe
    insert -> gather (with deterministic init for fresh rows) -> vectorized
    optimizer -> scatter. Window-overflow keys are dropped and counted.
    ``in_counts`` ([n]) marks grads that are already pre-reduced sums of that
    many originals (owner side of the all-to-all exchange).

    The dedup, the combine and the find (:func:`merge_gradients`) run over
    ``dedup_capacity`` (default ``n``) slots; the gather, the optimizer and
    the scatter are ``table.apply_rows``, whose cost follows the distinct
    keys of the batch and not ``dedup_capacity``. ``plan`` and
    ``resolved`` are :func:`merge_gradients`'s; with ``resolved`` the apply
    takes the weight rows from it and gathers none.
    """
    keys_arr, failed, merged = merge_gradients(
        state, initializer, indices, grads, dedup_capacity=dedup_capacity,
        max_probes=max_probes, in_counts=in_counts,
        record_stats=record_stats, plan=plan, resolved=resolved)
    weights, slots = table_lib.apply_rows(
        state.weights, state.slots, make_optimizer(optimizer), *merged,
        pulled=None if resolved is None else resolved.rows,
        record_stats=record_stats)
    return HashTableState(
        keys=keys_arr, weights=weights, slots=slots,
        init_rng=state.init_rng,
        insert_failures=state.insert_failures + failed)
