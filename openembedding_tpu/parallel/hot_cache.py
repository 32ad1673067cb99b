"""Frequency-aware hot-row replica cache: the ``"a2a+cache"`` data plane.

Rec-sys key streams are heavily Zipfian — the bench suite's own
zipf(a=1.08) workloads concentrate most lookups on a tiny head of rows —
yet the owner-routed exchange (``alltoall.py``) pays the full a2a round
for every entry. Systems like HET (VLDB '22) and Kraken replicate just
the hot rows on every worker and serve them locally; this module is that
idea layered on the sharded plane, kept **exactly equivalent** to the
uncached exchange:

* A host-side decayed frequency sketch (:class:`FreqSketch`) ranks keys;
  every N steps (outside the jitted step) the top-K set is admitted and
  its rows + optimizer slots are replicated into every device's HBM
  (:class:`HotCacheState`, carried next to the authoritative table in
  :class:`CachedState`).
* **Pull**: each batch is partitioned in-graph into cached/uncached
  halves (static shapes — a hit mask, never a dynamic split). Hits are
  served from the local replica with NO collective; the residue flows
  through the existing exchange with hits masked to the invalid
  sentinel, and the two row sets sum (the exchange returns zero rows for
  masked entries).
* **Push**: hits are pre-reduced locally into K bins over each device's
  distinct sub-slice (the same split the exchange uses), one ``psum``
  over the K cached rows merges the global (grad sum, count) per key —
  the same MpscGradientReducer-style merge the owner performs — and
  every device applies the identical optimizer update to its replica
  while the owner scatters the updated row back into its table shard.
  The table therefore stays authoritative at every step: a refresh only
  re-gathers rows, it never writes back.

Replica coherence argument: the psum result is identical on every
device, the optimizer update is deterministic, and cached keys are
excluded from the exchange (membership is a pure function of the key),
so each key's update is applied exactly once with the same totals as the
uncached plane — parameters match to float-summation-order tolerance.

Counters (gated like the a2a accumulators, see
``observability.set_evaluate_performance``): ``cache_hits`` /
``cache_misses`` count batch entries against the cached set on each
device's distinct sub-slice (host accumulation over shards sums to the
global total); ``ici_bytes_saved`` is the entry-granularity estimate of
exchange traffic the hits skipped (row + key/count words per entry,
pre-dedup — an upper bound on bucket bytes, the measurement the
reference takes pre-dedup too, laboratory/benchmark/analyze.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import hash_table as hash_lib
from .. import table as table_lib
from ..analysis import scope
from ..analysis.lint import host_fn
from . import alltoall as a2a


def _reject_tracer(x, where: str) -> None:
    """The admission plane is host-side BY CONTRACT: a tracer reaching it
    means someone moved sketch/counter maintenance inside a jitted step —
    the exact regression graftlint rule JG001 flags statically. Fail with
    the design pointer instead of numpy's opaque TracerArrayConversion."""
    if isinstance(x, jax.core.Tracer):
        raise TypeError(
            f"{where} received a JAX tracer: the frequency sketch must be "
            "fed OUTSIDE the jitted step (host-side admission is what "
            "keeps the cache plane's ICI contract — see the module "
            "docstring and analysis/lint.py JG001)")

DEFAULT_CACHE_K = 512


@struct.dataclass
class HotCacheState:
    """Replicated top-K row replica (every device holds the whole thing).

    ``keys`` is SORTED (ascending; signed order for narrow keys, unsigned
    u64 order for wide pairs — :func:`lookup` binary-searches it) and
    padded with the plane's invalid sentinel, which can never equal a
    valid query. ``rows``/``slots`` mirror the owner table's current
    values for those keys.
    """

    keys: jnp.ndarray                    # [K] or [K, 2] (wide), sorted
    rows: jnp.ndarray                    # [K, dim]
    slots: Dict[str, jnp.ndarray]        # each [K, ...]

    @property
    def k(self) -> int:
        return self.keys.shape[0]

    @property
    def wide(self) -> bool:
        return self.keys.ndim == 2


@struct.dataclass
class CachedState:
    """Authoritative table + its hot-row replica, threaded as one pytree."""

    table: Any                           # TableState | HashTableState
    cache: HotCacheState


def unwrap(state: Any) -> Any:
    """The authoritative table of a possibly-wrapped state (checkpoint
    and serving paths read through the wrapper — the hot-row replica and
    the int8_ef push residual are both derived state)."""
    from . import precision
    if isinstance(state, precision.EFState):
        return state.table
    return state.table if isinstance(state, CachedState) else state


# --- device-side lookup ------------------------------------------------------

def _pair_less(alo, ahi, blo, bhi) -> jnp.ndarray:
    """a < b in unsigned-u64 order over (lo, hi) int32 pairs (x64-off)."""
    au, bu = ahi.astype(jnp.uint32), bhi.astype(jnp.uint32)
    al, bl = alo.astype(jnp.uint32), blo.astype(jnp.uint32)
    return (au < bu) | ((au == bu) & (al < bl))


def lookup(cache_keys: jnp.ndarray, query: jnp.ndarray,
           valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cache position + hit mask for each query key.

    ``cache_keys`` is the sorted [K] (or [K, 2]) replica key set; ``query``
    [n] (or [n, 2]); ``valid`` [n] masks entries that are valid keys at all
    (sentinel pads never hit). Returns ``(pos [n] int32, hit [n] bool)``.
    """
    k = cache_keys.shape[0]
    if cache_keys.ndim == 2:
        n = query.shape[0]
        lo = jnp.zeros((n,), jnp.int32)
        hi = jnp.full((n,), k, jnp.int32)
        for _ in range(max(1, int(k).bit_length())):
            active = lo < hi
            mid = (lo + hi) // 2
            km = jnp.take(cache_keys, jnp.minimum(mid, k - 1), axis=0)
            less = _pair_less(km[:, 0], km[:, 1], query[:, 0], query[:, 1])
            lo = jnp.where(active & less, mid + 1, lo)
            hi = jnp.where(active & ~less, mid, hi)
        pos = jnp.minimum(lo, k - 1)
        at = jnp.take(cache_keys, pos, axis=0)
        hit = (at[:, 0] == query[:, 0]) & (at[:, 1] == query[:, 1]) & valid
        return pos, hit
    ck = cache_keys.astype(query.dtype)
    pos = jnp.minimum(jnp.searchsorted(ck, query).astype(jnp.int32), k - 1)
    hit = (jnp.take(ck, pos) == query) & valid
    return pos, hit


def mask_hits(flat: jnp.ndarray, hit: jnp.ndarray, sentinel) -> jnp.ndarray:
    """Replace cache-served entries with the plane's invalid sentinel so the
    residue rides the existing exchange untouched (static shapes: the
    cached/uncached partition is a mask, never a dynamic split)."""
    s = jnp.asarray(sentinel, flat.dtype)
    if flat.ndim == 2:
        return jnp.where(hit[:, None], s, flat)
    return jnp.where(hit, s, flat)


def cache_pre_reduce(pos: jnp.ndarray, hit: jnp.ndarray, grads: jnp.ndarray,
                     k: int, split_axes, split_sizes, grid_axes
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-key (grad sum, count) over the GLOBAL batch for the K cached rows.

    Each device pre-reduces its distinct sub-slice (the same
    ``split_slice`` partition the exchange push uses, so no entry is
    counted twice across model-axis peers), then one psum over the shard
    grid merges the partials — the cached keys' replacement for the
    routed exchange, O(K * dim) ICI bytes regardless of batch size.
    """
    parts = math.prod(split_sizes)
    my_part = a2a.linear_shard_id(split_axes, split_sizes)
    binpos = jnp.where(hit, pos, jnp.int32(k))
    sl_bin, _m = a2a.split_slice(binpos, parts, my_part, k)
    sl_g = a2a.split_slice_rows(grads, parts, my_part)
    summed = jnp.zeros((k + 1, grads.shape[-1]), grads.dtype
                       ).at[sl_bin].add(sl_g)
    counts = jnp.zeros((k + 1,), jnp.int32).at[sl_bin].add(
        (sl_bin < k).astype(jnp.int32))
    summed = lax.psum(summed[:k], tuple(grid_axes))
    counts = lax.psum(counts[:k], tuple(grid_axes))
    return summed, counts


def update_replica(optimizer, cache: HotCacheState, summed: jnp.ndarray,
                   counts: jnp.ndarray) -> HotCacheState:
    """Apply the psum-merged update to the replica rows/slots.

    Rows with a zero count stay bit-identical (stateful optimizers like
    adam would otherwise decay untouched rows — the framework-wide
    touched-rows-only contract)."""
    new_w, new_s = table_lib.optimizer_block_update(
        optimizer, cache.rows, cache.slots, summed, counts)
    touched = counts > 0
    rows = jnp.where(touched[:, None], new_w, cache.rows)
    slots = {}
    for name, cur in cache.slots.items():
        m = touched.reshape((-1,) + (1,) * (cur.ndim - 1))
        slots[name] = jnp.where(m, new_s[name], cur)
    return HotCacheState(keys=cache.keys, rows=rows, slots=slots)


def record_cache_stats(hit: jnp.ndarray, valid: jnp.ndarray, *,
                       entry_bytes: int, split_axes, split_sizes,
                       record: bool) -> None:
    """Gated cache_hits / cache_misses / ici_bytes_saved accumulation.

    Counted on each device's distinct sub-slice so summing the per-device
    callbacks host-side yields the global totals (the a2a accumulators'
    convention). ``entry_bytes`` = exchange bytes one served entry skips
    (row + key/count words, pre-dedup)."""
    parts = math.prod(split_sizes)
    my_part = a2a.linear_shard_id(split_axes, split_sizes)
    h, _ = a2a.split_slice(hit.astype(jnp.int32), parts, my_part, 0)
    v, _ = a2a.split_slice(valid.astype(jnp.int32), parts, my_part, 0)
    hits = jnp.sum(h).astype(jnp.int32)
    a2a.record_stat("cache_hits", hits, record)
    a2a.record_stat("cache_misses", (jnp.sum(v) - hits).astype(jnp.int32),
                    record)
    a2a.record_stat("ici_bytes_saved", hits * jnp.int32(entry_bytes), record)


# --- cache construction (host side, outside the jitted step) -----------------

def empty_cache_like(table_state: Any, k: int, *, mesh: Mesh,
                     wide: bool = False,
                     key_dtype=jnp.int32) -> HotCacheState:
    """All-pad cache (zero hits — the plane behaves exactly like "a2a"
    until the first refresh admits keys)."""
    repl = NamedSharding(mesh, P())
    dim = table_state.weights.shape[-1]
    if wide:
        keys = np.full((k, 2), hash_lib.empty_key(np.int32), np.int32)
    else:
        kd = np.dtype(key_dtype)
        keys = np.full((k,), np.iinfo(kd).min, kd)
    rows = np.zeros((k, dim), np.dtype(table_state.weights.dtype))
    put = functools.partial(jax.device_put, device=repl)
    slots = {name: put(np.zeros((k,) + tuple(arr.shape[1:]),
                                np.dtype(arr.dtype)))
             for name, arr in table_state.slots.items()}
    return HotCacheState(keys=put(keys), rows=put(rows), slots=slots)


def attach_empty(table_state: Any, spec, mesh: Mesh):
    """Wrap a bare table in a :class:`CachedState` with an all-pad replica
    when ``spec`` is on the cached plane (pass-through otherwise) — THE
    one place the pad sentinel / replica key dtype are derived, shared by
    both plane creators and the collection/checkpoint wrappers."""
    if not getattr(spec, "is_cached", False) \
            or isinstance(table_state, CachedState):
        return table_state
    is_hash = hasattr(table_state, "keys")
    wide = bool(is_hash and table_state.keys.ndim == 2)
    return CachedState(
        table=table_state,
        cache=empty_cache_like(
            table_state, spec.cache_k, mesh=mesh, wide=wide,
            key_dtype=table_state.keys.dtype if is_hash and not wide
            else jnp.int32))


def _sort_for_device(keys: np.ndarray, wide: bool) -> np.ndarray:
    """Host sort matching the device comparator: signed ascending for
    narrow keys, unsigned-u64 for wide (joined int64) keys."""
    if wide:
        return keys[np.argsort(keys.view(np.uint64), kind="stable")]
    return np.sort(keys, kind="stable")


@functools.lru_cache(maxsize=None)
def _gather_table_program(mesh: Mesh, spec, slot_names: tuple):
    """keys [K] replicated -> (rows, slots, found) replicated: each shard
    contributes its owned rows, one psum merges (the K-row refresh pull)."""
    axes = spec.shard_axes
    sizes = tuple(mesh.shape[a] for a in axes)

    def _gather(weights, slots, keys):
        me = a2a.linear_shard_id(axes, sizes)
        shard, local = spec.shard_and_local(keys)
        mine = (keys >= 0) & (keys < spec.padded_vocab) & (shard == me)
        safe = jnp.where(mine, local, 0)
        rows = jnp.take(weights, safe, axis=0, mode="clip")
        rows = jnp.where(mine[:, None], rows, jnp.zeros_like(rows))
        srows = {}
        for name, v in slots.items():
            r = jnp.take(v, safe, axis=0, mode="clip")
            m = mine.reshape((-1,) + (1,) * (r.ndim - 1))
            srows[name] = lax.psum(jnp.where(m, r, jnp.zeros_like(r)), axes)
        rows = lax.psum(rows, axes)
        found = lax.psum(mine.astype(jnp.int32), axes) > 0
        return rows, srows, found

    row = spec.row_spec()
    slot_specs = {name: row for name in slot_names}
    fn = shard_map(_gather, mesh=mesh, in_specs=(row, slot_specs, P()),
                   out_specs=(P(), {name: P() for name in slot_names}, P()),
                   check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _gather_hash_program(mesh: Mesh, spec, slot_names: tuple):
    axes = spec.shard_axes
    sizes = tuple(mesh.shape[a] for a in axes)

    def _gather(tkeys, weights, slots, q):
        me = a2a.linear_shard_id(axes, sizes)
        empty = hash_lib.empty_key(tkeys.dtype)
        if hash_lib.is_wide(tkeys):
            owned = (spec.owner_shard(q) == me) & (q[:, 1] != empty)
            masked = jnp.where(owned[:, None], q, empty)
        else:
            owned = (spec.owner_shard(q) == me) & (q != empty)
            masked = jnp.where(owned, q, empty)
        slot = hash_lib.find_rows(tkeys, masked, spec.max_probes)
        hitv = slot >= 0
        safe = jnp.where(hitv, slot, 0)
        rows = jnp.take(weights, safe, axis=0, mode="clip")
        rows = jnp.where(hitv[:, None], rows, jnp.zeros_like(rows))
        srows = {}
        for name, v in slots.items():
            r = jnp.take(v, safe, axis=0, mode="clip")
            m = hitv.reshape((-1,) + (1,) * (r.ndim - 1))
            srows[name] = lax.psum(jnp.where(m, r, jnp.zeros_like(r)), axes)
        rows = lax.psum(rows, axes)
        found = lax.psum(hitv.astype(jnp.int32), axes) > 0
        return rows, srows, found

    row = spec.row_spec()
    slot_specs = {name: row for name in slot_names}
    fn = shard_map(_gather, mesh=mesh,
                   in_specs=(row, row, slot_specs, P()),
                   out_specs=(P(), {name: P() for name in slot_names}, P()),
                   check_vma=False)
    return jax.jit(fn)


def build_cache(table_state: Any, candidates: np.ndarray, k: int, *,
                mesh: Mesh, spec) -> HotCacheState:
    """Admit up to ``k`` candidate keys: pad, sort, gather rows + slots.

    ``candidates`` are host keys (int64 for wide tables — joined pairs;
    the table's key/index dtype otherwise), frequency-ranked by the
    caller. Hash-table candidates not yet PRESENT in the table are
    rejected (a replica must never shadow the deterministic-init contract
    for unseen keys); array-table keys are always present. The returned
    state's arrays are replicated over the mesh.
    """
    from . import sharded_hash as sh  # late: avoids a module cycle
    is_hash = isinstance(spec, sh.HashShardingSpec)
    wide = bool(is_hash and spec.wide)
    repl = NamedSharding(mesh, P())
    slot_names = tuple(table_state.slots)
    cand = np.asarray(candidates).ravel()[:k]

    if wide:
        pad = np.int64(np.uint64(0x8000000080000000))  # the EMPTY pair
    else:
        kd = np.dtype(table_state.keys.dtype) if is_hash \
            else np.dtype(np.int32)
        pad = np.iinfo(kd).min

    def _pack(keys64: np.ndarray):
        if wide:
            full = np.full((k,), pad, np.int64)
            full[:keys64.size] = keys64.astype(np.int64)
            full = _sort_for_device(full, wide=True)
            return full, hash_lib.split64(full)
        full = np.full((k,), pad, kd)
        full[:keys64.size] = keys64.astype(kd)
        full = _sort_for_device(full, wide=False)
        return full, full

    packed, device_keys = _pack(cand)
    program = (_gather_hash_program if is_hash else _gather_table_program)(
        mesh, spec, slot_names)
    for _ in range(2):
        dk = jax.device_put(device_keys, repl)
        if is_hash:
            rows, srows, found = program(table_state.keys,
                                         table_state.weights,
                                         table_state.slots, dk)
        else:
            rows, srows, found = program(table_state.weights,
                                         table_state.slots, dk)
        found_np = np.asarray(found)
        if (found_np | (packed == pad)).all():
            break
        # some candidates are absent from the table (hash keys never yet
        # pushed): drop them, re-pack, re-gather once — absent keys must
        # keep the uncached plane's deterministic-init contract
        packed, device_keys = _pack(packed[found_np])
    return HotCacheState(keys=dk, rows=rows, slots=srows)


# --- admission policy (host side) -------------------------------------------

# dense-mode cutoff: a bounded vocab up to this many rows keeps exact
# per-row float32 counts (<= 256 MB host RAM); bigger / unbounded key
# spaces fall back to the dict sketch
DENSE_SKETCH_MAX = 1 << 26


class FreqSketch:
    """Decayed per-key frequency counter driving cache admission.

    Two backings behind one interface:

    * ``dense_vocab`` set (bounded key spaces up to
      :data:`DENSE_SKETCH_MAX` rows): a flat float32 count array —
      ``update`` is one vectorized ``np.add.at`` per batch, the shape the
      per-STEP hot path needs (the dict loop costs milliseconds per batch
      at rec-sys batch sizes, which would out-bill a ~1.5 ms device
      step); ``topk`` is an argpartition, paid only at refresh.
    * otherwise (hash / unbounded keys): dict-backed exact counts.

    Both decay by ``decay`` once per refresh window (exponential
    forgetting). The dict backing prunes entries below ``prune_below``
    and hard-caps at ``max_entries`` (the coldest half is dropped when it
    trips — hot keys re-accumulate every window, the tail never does).
    """

    def __init__(self, decay: float = 0.8, prune_below: float = 0.5,
                 max_entries: int = 1 << 20,
                 dense_vocab: Optional[int] = None):
        if not 0.0 < decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay}")
        self.decay_factor = float(decay)
        self.prune_below = float(prune_below)
        self.max_entries = int(max_entries)
        self._counts: Dict[int, float] = {}
        self._sample_phase = 0
        self._dense: Optional[np.ndarray] = None
        if dense_vocab is not None and 0 < dense_vocab <= DENSE_SKETCH_MAX:
            self._dense = np.zeros(int(dense_vocab), np.float32)

    def __len__(self) -> int:
        if self._dense is not None:
            return int(np.count_nonzero(self._dense))
        return len(self._counts)

    # measured CPython cost of one dict entry (int key + float value +
    # table slot share) — an estimate for the memory gauges
    DICT_ENTRY_NOMINAL_BYTES = 100

    def approx_bytes(self) -> int:
        """Approximate host RAM this sketch holds (graftwatch memory
        ledger): exact for the dense backing, nominal-per-entry for the
        dict one."""
        if self._dense is not None:
            return int(self._dense.nbytes)
        return len(self._counts) * self.DICT_ENTRY_NOMINAL_BYTES

    # per-batch sample cap: scatter-adding every entry of a 4096x26 batch
    # costs ~7 ms of host time per step (np.add.at), which would out-bill
    # a ~1.5 ms device step; a uniform stride sample preserves frequency
    # RANKS (the only thing admission consumes) at ~0.5 ms
    SAMPLE_CAP = 16384

    @host_fn
    def update(self, keys: np.ndarray) -> None:
        """Count one batch's (valid, in-range) keys (stride-sampled past
        :attr:`SAMPLE_CAP` entries — ranking-preserving)."""
        _reject_tracer(keys, "FreqSketch.update")
        flat = np.asarray(keys).ravel()
        if flat.size > self.SAMPLE_CAP:
            stride = flat.size // self.SAMPLE_CAP + 1
            # rotate the phase per call: a fixed phase aliases with any
            # structured period in the flattened layout (e.g. the F
            # columns of a row-major [B, F] fused batch when
            # gcd(stride, F) > 1 would sample only a few features); over
            # a refresh window every residue class gets visited
            phase = self._sample_phase % stride
            self._sample_phase += 1
            flat = flat[phase::stride]
        if self._dense is not None:
            np.add.at(self._dense, flat.astype(np.int64), 1.0)
            return
        u, c = np.unique(flat, return_counts=True)
        counts = self._counts
        get = counts.get
        for key, n in zip(u.tolist(), c.tolist()):
            counts[key] = get(key, 0.0) + n
        if len(counts) > self.max_entries:
            # vectorized trim: a python sorted() over >1M dict items costs
            # ~1 s on the per-step path
            ks = np.fromiter(counts.keys(), np.int64, len(counts))
            vs = np.fromiter(counts.values(), np.float64, len(counts))
            keep = self.max_entries // 2
            sel = np.argpartition(-vs, keep - 1)[:keep]
            self._counts = dict(zip(ks[sel].tolist(), vs[sel].tolist()))

    def decay(self) -> None:
        f = self.decay_factor
        if self._dense is not None:
            self._dense *= f
            # prune like the dict backing: without zeroing, every key
            # ever touched stays nonzero for hundreds of windows and
            # topk's flatnonzero working set grows toward the full-array
            # argpartition cost this path exists to avoid
            self._dense[self._dense < self.prune_below] = 0.0
            return
        floor = self.prune_below
        self._counts = {key: v * f for key, v in self._counts.items()
                        if v * f >= floor}

    def topk(self, k: int) -> np.ndarray:
        """The ``k`` highest-count keys (count-desc, key-asc ties so
        refreshes are deterministic), as int64. Zero-count keys never
        qualify."""
        if self._dense is not None:
            d = self._dense
            # partition only the touched rows: argpartition over the full
            # array costs ~0.7 s at 2^26 rows; over the live working set
            # it is tens of ms (refresh-time only, amortized over N steps)
            nz = np.flatnonzero(d)
            k_eff = min(k, nz.size)
            if k_eff == 0:
                return np.empty((0,), np.int64)
            vals = d[nz]
            sel = np.argpartition(-vals, k_eff - 1)[:k_eff] \
                if k_eff < nz.size else np.arange(nz.size)
            idx = nz[sel]
            order = np.lexsort((idx, -d[idx]))
            return idx[order].astype(np.int64)
        items = sorted(self._counts.items(), key=lambda kv: (-kv[1], kv[0]))
        return np.asarray([key for key, _ in items[:k]], np.int64)


class HotCacheManager:
    """Per-variable refresh driver: observe batches, rebuild the replica
    every ``refresh_every`` steps (host-side, outside the jitted step).

    Typical wiring (the Trainer does this automatically for every
    ``plane="a2a+cache"`` variable)::

        mgr.observe(batch_ids)          # after each step
        if mgr.due:
            state = mgr.refresh(state)  # new CachedState, same table
    """

    def __init__(self, *, mesh: Mesh, spec, k: int = DEFAULT_CACHE_K,
                 refresh_every: int = 64, decay: float = 0.8,
                 name: str = ""):
        from . import sharded_hash as sh  # late: avoids a module cycle
        self.mesh = mesh
        self.spec = spec
        self.name = name
        self.k = int(k)
        self.refresh_every = max(1, int(refresh_every))
        self._is_hash = isinstance(spec, sh.HashShardingSpec)
        self._wide = bool(self._is_hash and spec.wide)
        # bounded vocabs get the vectorized dense counter (per-step cost
        # is one np.add.at); hash key spaces use the dict sketch
        self.sketch = FreqSketch(
            decay=decay,
            dense_vocab=None if self._is_hash else spec.padded_vocab)
        self._owns_sketch = True
        self._since = 0
        self.refreshes = 0
        # per-device bytes of the replica this manager last BUILT (the
        # CachedState itself lives in the training state; the manager
        # accounts what it created) — graftwatch memory ledger
        self.last_replica_bytes = 0
        from ..utils import observability
        observability.register_memory_source("hot_cache", name or "cache",
                                             self)

    def memory_stats(self) -> Dict[str, float]:
        """Host+replica memory gauges (``observability.memory_stats``):
        the admission sketch's host RAM and the per-device byte size of
        the replica built at the last refresh (keys + rows + optimizer
        slots, replicated on every device)."""
        return {
            "replica_bytes": float(self.last_replica_bytes),
            "sketch_bytes": float(self.sketch.approx_bytes()),
            "sketch_keys": float(len(self.sketch)),
            "refreshes": float(self.refreshes),
        }

    def share_sketch(self, other: "HotCacheManager") -> None:
        """Reuse ``other``'s frequency sketch: twin variables fed by the
        SAME id column (e.g. ``name`` and ``name:linear``) should pay the
        per-step count once. The sharer stops decaying (the owner's
        refresh does it) and advances its clock with :meth:`tick`."""
        self.sketch = other.sketch
        self._owns_sketch = False

    def tick(self) -> None:
        """Advance the refresh clock without re-counting (the column was
        already observed into a shared sketch this step)."""
        self._since += 1

    def _valid_keys(self, ids) -> np.ndarray:
        arr = np.asarray(ids)
        if self._wide and arr.ndim >= 2 and arr.shape[-1] == 2:
            # same ambiguity rule as embedding._widen: on a wide table a
            # trailing dim of 2 IS a (lo, hi) pair axis — the training
            # plane reads such a batch as pairs, so admission must too
            arr = hash_lib.join64(arr.reshape(-1, 2))
        arr = arr.ravel().astype(np.int64)
        if not self._is_hash:
            return arr[(arr >= 0) & (arr < self.spec.padded_vocab)]
        if self._wide:
            # the EMPTY band: hi word == INT32_MIN (hash_table.py contract)
            return arr[(arr >> np.int64(32))
                       != np.int64(np.iinfo(np.int32).min)]
        # narrow tables: the EMPTY sentinel is the key dtype's minimum;
        # dropping both widths' minima costs at most one 1-in-2^64 key
        return arr[(arr != np.iinfo(np.int32).min)
                   & (arr != np.iinfo(np.int64).min)]

    @host_fn
    def observe(self, ids) -> None:
        _reject_tracer(ids, "HotCacheManager.observe")
        keys = self._valid_keys(ids)
        if keys.size:
            self.sketch.update(keys)
        self._since += 1

    @property
    def due(self) -> bool:
        return self._since >= self.refresh_every

    def refresh(self, state: CachedState) -> CachedState:
        """New CachedState with the current top-K admitted (table rows are
        authoritative, so no writeback happens — this is a pure re-gather)."""
        with scope.span("cache.refresh"):
            self._since = 0
            self.refreshes += 1
            cand = self.sketch.topk(self.k)
            if self._owns_sketch:
                # a shared sketch decays once per window (at its owner's
                # refresh), not once per sharing variable
                self.sketch.decay()
            cache = build_cache(state.table, cand, self.k, mesh=self.mesh,
                                spec=self.spec)
            self.last_replica_bytes = int(
                cache.keys.nbytes + cache.rows.nbytes
                + sum(v.nbytes for v in cache.slots.values()))
            return CachedState(table=state.table, cache=cache)
