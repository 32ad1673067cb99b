"""The adapter to the program for a table of UNBOUNDED keys behind the
offload tier: ``offload_system.py``'s twin for a tier built without a
``vocab``.

It builds the tier as a user builds it (two ``ShardedOffloadedTable`` s
without a ``vocab``, ``fields`` and its ``:linear`` twin, their
``embedding_spec()`` s in one collection, ``Trainer(offload=...)``), with
the hash configuration's ``fresh_rows`` rule as the tier's initializer (a
key no store has seen is born in the step under it). The host store is
filled from the seed under the keys the traffic will send: ranks 1..K of
every feature's Zipf stream as 62-bit keys (``store_keys_at_start``) and,
of the rarer keys the pool carries, the share ``seen_share_of_tail``
picked by a seeded hash of the key; rows are made on the device a feature
at a time and loaded by key. The HBM cache is warmed through the tier's
own bulk call with ranks 1..``prefill_ranks_per_feature``. What does not
depend on where rows live comes from ``system.py`` by import.
"""

import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import reference, reference_offload_keys as keyed, seeded, \
    system as base
from .offload_system import span_read
from .system import (TABLE_NAMES, dense_leaves, first_grad_norms,   # noqa: F401
                     found_devices, insert_failures, program_batch, step_hlo)

COUNTERS = ("offload_miss_rows", "offload_unique_rows", "offload_h2d_bytes",
            "offload_evictions", "offload_gen_retries",
            "offload_fresh_keys", "offload_index_probes")
SPANS = ("offload.host_prepare", "offload.wait_prepare",
         "offload.apply_prepared", "offload.insert_pack",
         "offload.insert_dispatch", "offload.note_update",
         "offload.key_index", "offload.store_grow")
FAULTS = ("miss_from_initializer", "writeback_dropped", "fresh_key_dropped",
          "key_aliased")
WARM_FEATURES = 3       # features a warming call takes: 3 x 1.29M keys fill
                        # two bulk inserts of 2^21 to 92%


@dataclasses.dataclass
class KeyedOffloadSystem(base.System):
    tiers: dict = None          # table name -> ShardedOffloadedTable
    inputs: object = None       # the traffic's pool, as a future
    raw_pool: list = None       # the pool: which rare keys it has


def build(config):
    """Mesh, the two keyed tiers, collection, trainer, key mapper."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import FusedMapper
    from openembedding_tpu.meta import EmbeddingVariableMeta
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu.parallel.mesh import create_mesh

    features = tuple(criteo.SPARSE_NAMES[:config["sparse_features"]])
    mesh = create_mesh(config["mesh"]["data"], config["mesh"]["model"],
                       jax.devices()[:config["chips"]])
    mapper = FusedMapper(features, (-1,) * len(features))
    adagrad = dict(config["adagrad"], category="adagrad")
    fresh = {k: config["fresh_rows"][k]
             for k in ("category", "mean", "stddev")}
    try:
        fields = ShardedOffloadedTable(
            TABLE_NAMES["fields"], EmbeddingVariableMeta(
                embedding_dim=config["embedding_dim"], vocabulary_size=-1),
            adagrad, fresh, cache_capacity=config["cache_capacity"],
            mesh=mesh)
        # the twin reads the same column: one key space for both. The
        # dim-1 row of a fresh key is nought, as the hash configuration
        # states
        tiers = {fields.name: fields,
                 TABLE_NAMES["linear"]: fields.companion(
                     TABLE_NAMES["linear"], EmbeddingVariableMeta(
                         embedding_dim=config["linear_dim"],
                         vocabulary_size=-1),
                     initializer={"category": "constant", "value": 0.0})}
    except TypeError as e:      # a program whose tier must have a vocab
        raise SystemExit("benchmark: this program's offload tier holds "
                         f"bounded ids only: {e}")
    coll = EmbeddingCollection(
        [tier.embedding_spec() for tier in tiers.values()], mesh)
    trainer = Trainer(
        deepctr.build_model(config["model"], features,
                            dnn_units=tuple(config["dnn_units"])),
        coll, optax.adam(reference.ADAM["lr"]), offload=tiers)
    stated = {k: config[k] for k in ("occupancy_threshold", "keep_fraction",
                                     "pipeline_depth")}
    tier = tiers[TABLE_NAMES["fields"]]
    found = {"occupancy_threshold": tier.occupancy_threshold,
             "keep_fraction": tier.keep_fraction,
             "pipeline_depth": trainer.pipeline_depth}
    if stated != found:
        raise RuntimeError(f"the configuration states {stated}, the "
                           f"program's defaults are {found}")
    return KeyedOffloadSystem(config, mesh, coll, trainer, mapper,
                              tiers=tiers)


def fused_keys(system, feature, ids):
    """The program's int64 keys of raw 62-bit ``ids`` of feature columns
    ``feature`` (both ``[n]``): ``FusedMapper``'s ``key * F + feature``,
    as the tier holds them."""
    from openembedding_tpu import hash_table as hash_lib
    features = system.config["sparse_features"]
    fused = ids.astype(np.uint64) * np.uint64(features) \
        + feature.astype(np.uint64)
    pairs = hash_lib.split64(fused.view(np.int64))
    band = pairs[:, 1] == hash_lib.empty_key(np.int32)
    pairs[band, 1] += 1             # the mapper's own rule for the band
    return hash_lib.join64(pairs)


def batch_keys(system, batch):
    """int64 keys ``[B, F]`` of a program batch's column."""
    from openembedding_tpu import hash_table as hash_lib
    return hash_lib.join64(batch["sparse"][TABLE_NAMES["fields"]])


def _load(system, seed, feature, ids):
    """Rows of raw (feature, id) pairs, made on the device from the seed
    and loaded into both stores under their fused keys, sorted (the tier
    then sees that each key comes once)."""
    keys = fused_keys(system, feature, ids)
    order = np.argsort(keys, kind="stable")
    keys, feature, ids = keys[order], feature[order], ids[order]
    lo, hi = seeded.split_words(ids)
    # made at the call's own length (one program a length), cut after
    rows = system.rows_of(system.lead, feature.astype(np.int32), lo, hi)
    once = np.concatenate([[True], keys[1:] != keys[:-1]])
    keys = keys[once]
    # a table a thread: each has its own index, and numpy lets go of the
    # interpreter while it walks one
    list(system.loaders.map(
        lambda item: item[1].load_rows(
            keys, np.asarray(rows[item[0]])[once]), system.tiers.items()))
    return len(keys)


def _fill_store(system, seed):
    """The store at the start: ``store_keys_at_start`` keys (ranks 1..K of
    every feature) and the share of the pool's rarer keys the job has
    met; returns how many keys each part brought."""
    from .traffic_gen.zipf_train import feature_ids
    config, coll = system.config, system.coll
    n_feat = config["sparse_features"]
    per_feature = keyed.store_ranks(config)
    tables = [(n, name, coll.specs[name].output_dim,
               config["init_scale"]["fields" if n == 0 else "linear"])
              for n, name in enumerate(coll.specs)]

    @jax.jit
    def rows_of(lead, f, lo, hi):
        return {name: seeded.table_rows(lead[0], n, f, lo, hi, dim, scale,
                                        jnp)
                for n, name, dim, scale in tables}

    system.rows_of = rows_of
    system.loaders = concurrent.futures.ThreadPoolExecutor(len(system.tiers))
    ranks = np.arange(1, per_feature + 1, dtype=np.uint64)
    head = 0
    for j in range(n_feat):
        head += _load(system, seed, np.full(per_feature, j, np.int64),
                      feature_ids(ranks, j, None))
    if system.raw_pool is None:         # drawn on a thread meanwhile
        system.raw_pool = system.inputs.result()
    feature, ids = keyed.seen_tail(seed, config, system.raw_pool)
    # padded to the next 2^16: a seed's tail runs the compiled program
    pad = -len(ids) % (1 << 16)
    tail = _load(system, seed,
                 np.concatenate([feature, np.repeat(feature[:1], pad)]),
                 np.concatenate([ids, np.repeat(ids[:1], pad)])) \
        if len(ids) else 0
    system.loaders.shutdown()
    return {"head_keys": head, "tail_keys": tail}


def prefill_keys(system):
    """Fused keys the cache holds at the start, a group of features at a
    time: ranks 1..K of every feature's Zipf stream."""
    from .traffic_gen.zipf_train import feature_ids
    config = system.config
    ranks = np.arange(1, config["prefill_ranks_per_feature"] + 1,
                      dtype=np.uint64)
    for j0 in range(0, config["sparse_features"], WARM_FEATURES):
        group = range(j0, min(j0 + WARM_FEATURES,
                              config["sparse_features"]))
        yield np.concatenate([
            fused_keys(system, np.full(len(ranks), j, np.int64),
                       feature_ids(ranks, j, None)) for j in group])


def initial_state(system, seed, on_device=True):
    """The TrainState the cell starts from. The lead and then the two
    caches are the first things on the device; then the store is filled
    and the cache warmed. ``system.raw_pool`` (the traffic's pool) says
    which rare keys the store has met."""
    from openembedding_tpu.training import TrainState
    config, coll, trainer = system.config, system.coll, system.trainer
    replicated = NamedSharding(system.mesh, P())
    if on_device and jax.live_arrays():
        raise RuntimeError("something was put on the device before the "
                           "tables: their addresses would move run to run")
    lead = np.zeros(base.TABLES_START_AT // 4, np.uint32)
    lead[0] = seeded.seed_word(seed)
    system.lead = jax.block_until_ready(jax.device_put(lead, replicated))
    emb = jax.block_until_ready(coll.init(jax.random.PRNGKey(0)))
    # fresh rows are drawn under the key the configuration states
    emb = {name: s.replace(init_rng=jax.device_put(
        np.asarray(reference.fresh_key(seed)), replicated))
        for name, s in emb.items()}
    system.filled = _fill_store(system, seed)
    for keys in prefill_keys(system):
        for name, tier in system.tiers.items():
            emb[name] = tier.warm(emb[name], keys)
    jax.block_until_ready(emb)
    params = jax.device_put(
        base._flax_params(reference.dense_init(seed, config)), replicated)
    opt_state = jax.device_put(trainer.tx.init(params), replicated)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, emb=emb)


def pull_rows(system, state, batches):
    """(state, {table: [n, B, F, dim]}): rows of both tables for every
    lookup of ``batches`` through the tier's own read path: each batch is
    prepared (its misses fetched from the store and inserted; a key no
    store has seen is booked and reads as its initial row), then pulled."""
    out = {t: [] for t in TABLE_NAMES}
    for b in batches:
        state = system.trainer.prepare_offload(state, b)
        idx = jax.device_put(b["sparse"], system.by_batch)
        rows = system.pull(state.emb, idx)
        for t, name in TABLE_NAMES.items():
            out[t].append(np.asarray(rows[name]))
    return state, {t: np.stack(v) for t, v in out.items()}


def flush(system, state):
    """Write every update a step has returned back to the store, and wait
    for it (the guarantee the configuration states of ``flush``)."""
    for name, tier in system.tiers.items():
        tier.flush(state.emb[name])
        tier.finish()


def store_rows(system, batches):
    """({table: [n, B, F, dim]}, held [n, B, F]) read straight from the
    host store under each lookup's key; ``held`` is false where a table's
    store has no row for the key (its rows read nought there), or where
    the two tables' stores disagree about it."""
    keys = np.stack([batch_keys(system, b) for b in batches])
    uniq, inverse = np.unique(keys.ravel(), return_inverse=True)
    out, held = {}, np.ones(uniq.shape, bool)
    for t, name in TABLE_NAMES.items():
        tier = system.tiers[name]
        rows = tier.rows_of(uniq)
        there = rows >= 0
        there[there] = ~tier._unborn[rows[there]]
        got = np.zeros((len(uniq),) + tier.host_weights.shape[1:],
                       tier.host_weights.dtype)
        got[there] = tier.host_weights[rows[there]]
        out[t] = got[inverse].reshape(keys.shape + got.shape[1:])
        held &= there
    return out, held[inverse].reshape(keys.shape)


def store_keys_shared(system, batches):
    """Lookups of ``batches`` whose key shares its store row with another
    key, in either table: nought in a sound index."""
    keys = np.unique(np.concatenate(
        [batch_keys(system, b).ravel() for b in batches]))
    shared = 0
    for tier in system.tiers.values():
        rows = tier.rows_of(keys)
        held = rows >= 0
        shared += int(held.sum() - np.unique(rows[held]).size)
        shared += int((tier._keys[rows[held]] != keys[held]).sum())
    return shared


def tier_counts(system):
    """The tier's counters and spans, summed over both tables, as they
    stand now; a counter or span the program lacks reads nought."""
    from openembedding_tpu.analysis import scope
    out = {c: scope.HISTOGRAMS.counter(c) for c in COUNTERS}
    out.update({span: span_read(span, system.tiers) for span in SPANS})
    return out


def store_gauges(system):
    """Rows handed out and the index's load, a table: the tier's own
    ledger (``memory_stats``) as it stands now."""
    out = {}
    for name, tier in system.tiers.items():
        stats = tier.memory_stats()
        out[name] = {k: stats.get(k) for k in (
            "store_rows", "index_load", "index_bytes", "key_bytes",
            "store_bytes", "book_bytes", "resident_rows")}
    return out


def fill_to_budget(system, state, short_of=64):
    """Warm further stored keys (the last the store took in that the cache
    does not hold) until each table is ``short_of`` rows under its budget:
    the state just before an eviction (``offload_keys_controls``)."""
    emb = dict(state.emb)
    for name, tier in system.tiers.items():
        budget = int(tier.occupancy_threshold * tier.cache_capacity)
        short = budget - short_of - int(tier.memory_stats()["resident_rows"])
        if short > 0:
            n = tier._index.rows
            cold = np.nonzero(~np.asarray(tier._resident)[:n]
                              & ~np.asarray(tier._unborn)[:n])[0][-short:]
            emb[name] = tier.warm(emb[name], tier._keys[cold])
    return state.replace(emb=emb)


def plant(system, fault):
    """Plant one of ``FAULTS`` in the built system (read at a cell's size
    by ``benchmark/offload_keys_controls.py``; the benchmark's runs never
    call this): a miss served from the initializer and not from the
    store; a writeback that drops its rows; a fresh key whose trained row
    never reaches the store; two keys that share a store row."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    for tier in system.tiers.values():
        if fault == "miss_from_initializer":
            def initial(ids, tier=tier):
                rows = np.zeros((len(ids),) + tier.host_weights.shape[1:],
                                tier.host_weights.dtype)
                return rows, {k: np.full((len(ids),) + v.shape[1:],
                                         tier.optimizer.slot_init(k), v.dtype)
                              for k, v in tier.host_slots.items()}
            tier._gather_host = initial
        elif fault == "writeback_dropped":
            def dropped(cache, dirty_ids, tier=tier):
                with tier._book:
                    tier._dirty.clear_chunks(dirty_ids)
            tier._start_writeback = dropped
        elif fault == "fresh_key_dropped":
            # the write-back leaves out the rows the store has nothing
            # for yet: a key born in a step stays in the cache alone
            start = tier._start_writeback

            def born_only(cache, dirty_ids, tier=tier, start=start):
                unborn = tier._unborn[dirty_ids]
                with tier._book:
                    tier._dirty.clear_chunks(dirty_ids[unborn])
                return start(cache, dirty_ids[~unborn])
            tier._start_writeback = born_only
        else:
            # a key the index has not seen is handed the row of the key
            # before it: two keys, one store row
            if getattr(tier._index, "aliased", False):
                continue            # the twin's index is this one
            tier._index.aliased = True
            find = tier._index.find_or_insert

            def aliased(keys, tier=tier, find=find):
                before = tier._index.rows
                rows = find(keys)
                new = np.nonzero(rows >= before)[0]
                rows[new[1::2]] = rows[new[:-1:2]]
                return rows
            tier._index.find_or_insert = aliased
