"""The adapter to the program for a table behind the offload tier.

Beside ``system.py``, and the only other file that imports
``openembedding_tpu``: it builds the tier as a user builds it (two
``ShardedOffloadedTable`` s, ``fields`` and its ``:linear`` twin, their
``embedding_spec()`` s in one collection, ``Trainer(offload=...)``), fills
the host store from the seed in chunks made on the device, warms the HBM
cache with the head of the traffic's Zipf stream through the tier's own
bulk call, and reads back what the comparison needs: rows through the
tier's read path (prepared, then pulled) and rows of the host store.
What does not depend on where rows live comes from ``system.py`` by import.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import reference, seeded, system as base
from .system import (TABLE_NAMES, dense_leaves, first_grad_norms,   # noqa: F401
                     found_devices, insert_failures, program_batch, step_hlo)

STORE_CHUNK = 1 << 22           # rows made on the device per fill call
COUNTERS = ("offload_miss_rows", "offload_unique_rows", "offload_h2d_bytes",
            "offload_evictions", "offload_gen_retries")
SPANS = ("offload.host_prepare", "offload.wait_prepare",
         "offload.apply_prepared", "offload.insert_pack",
         "offload.insert_dispatch", "offload.note_update")
FAULTS = ("miss_from_initializer", "writeback_dropped")


@dataclasses.dataclass
class OffloadSystem(base.System):
    tiers: dict = None          # table name -> ShardedOffloadedTable


def build(config):
    """Mesh, the two offloaded tables, collection, trainer, id mapper."""
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
    mapper = FusedMapper(features,
                         (config["rows_per_feature"],) * len(features))
    adagrad = dict(config["adagrad"], category="adagrad")
    initializer = {k: config["cache_initializer"][k]
                   for k in ("category", "value")}
    tiers = {
        name: ShardedOffloadedTable(
            name, EmbeddingVariableMeta(embedding_dim=dim,
                                        vocabulary_size=mapper.total_vocab),
            adagrad, initializer, vocab=mapper.total_vocab,
            cache_capacity=config["cache_capacity"], mesh=mesh)
        for name, dim in ((TABLE_NAMES["fields"], config["embedding_dim"]),
                          (TABLE_NAMES["linear"], config["linear_dim"]))}
    coll = EmbeddingCollection(
        [tier.embedding_spec() for tier in tiers.values()], mesh)
    trainer = Trainer(
        deepctr.build_model(config["model"], features,
                            dnn_units=tuple(config["dnn_units"])),
        coll, optax.adam(reference.ADAM["lr"]), offload=tiers)
    # the configuration states the constructors' defaults; a default that
    # moved would otherwise change the cell in silence
    stated = {k: config[k] for k in ("occupancy_threshold", "keep_fraction",
                                     "pipeline_depth")}
    tier = tiers[TABLE_NAMES["fields"]]
    found = {"occupancy_threshold": tier.occupancy_threshold,
             "keep_fraction": tier.keep_fraction,
             "pipeline_depth": trainer.pipeline_depth}
    if stated != found:
        raise RuntimeError(f"the configuration states {stated}, the "
                           f"program's defaults are {found}")
    return OffloadSystem(config, mesh, coll, trainer, mapper, tiers=tiers)


def prefill_ids(system):
    """Fused row ids the cache holds at the start: ranks 1..K of every
    feature's Zipf stream, as the traffic maps a rank to an id."""
    from .traffic_gen.zipf_train import feature_ids
    config, mapper = system.config, system.mapper
    ranks = np.arange(1, config["prefill_ranks_per_feature"] + 1,
                      dtype=np.uint64)
    cols = {name: feature_ids(ranks, j, config["rows_per_feature"])
            .astype(np.int64)
            for j, name in enumerate(mapper.feature_names)}
    return np.unique(mapper.fuse(cols)[mapper.name])


def _fill_store(system, seed):
    """Every row of both host stores from the seed: made on the device a
    chunk at a time (the next chunk computes while this one is copied and
    written), loaded through the tier's ``load_rows`` by id range. The
    accumulators keep the constructor's fill, the stated start."""
    config = system.config
    per_feature = config["rows_per_feature"]
    vocab = system.mapper.total_vocab
    chunk = min(STORE_CHUNK, 1 << (vocab - 1).bit_length())
    tables = [(n, name, system.coll.specs[name].output_dim,
               config["init_scale"]["fields" if n == 0 else "linear"])
              for n, name in enumerate(system.coll.specs)]

    @jax.jit
    def rows_of(lead, start):
        row = start + jnp.arange(chunk, dtype=jnp.int32)
        return {name: seeded.table_rows(
            lead[0], n, row // per_feature, row % per_feature,
            jnp.zeros_like(row), dim, scale, jnp)
            for n, name, dim, scale in tables}

    def made(lo):
        rows = rows_of(system.lead, np.int32(lo))
        for r in rows.values():
            r.copy_to_host_async()
        return rows

    ahead = made(0)
    for lo in range(0, vocab, chunk):
        rows, hi = ahead, min(lo + chunk, vocab)
        if hi < vocab:
            ahead = made(hi)
        for name, tier in system.tiers.items():
            tier.load_rows(slice(lo, hi), np.asarray(rows[name])[:hi - lo])


def initial_state(system, seed, on_device=True):
    """The TrainState the cell starts from. The lead and then the two
    caches are the first things on the device, as ``system.py`` puts its
    tables; then the store is filled and the cache warmed."""
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
    _fill_store(system, seed)
    ids = prefill_ids(system)
    for name, tier in system.tiers.items():
        emb[name] = tier.warm(emb[name], ids)
    jax.block_until_ready(emb)
    params = jax.device_put(
        base._flax_params(reference.dense_init(seed, config)), replicated)
    opt_state = jax.device_put(trainer.tx.init(params), replicated)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, emb=emb)


def pull_rows(system, state, batches):
    """(state, {table: [n, B, F, dim]}): rows of both tables for every
    lookup of ``batches`` through the tier's own read path: each batch is
    prepared (its misses fetched from the store and inserted, which
    donates the cache: hence the state returned), then pulled."""
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
    """{table: [n, B, F, dim]} read straight from the host store."""
    return {t: np.stack([system.tiers[name].host_weights[b["sparse"][name]]
                         for b in batches])
            for t, name in TABLE_NAMES.items()}


def span_read(span, tables=()):
    """Seconds and calls of one of the program's spans as they stand now,
    unlabelled and under each of ``tables``; nought for a span the program
    lacks."""
    from openembedding_tpu.analysis import scope
    series = scope._hist_name(span)
    labels = [{}] + [{"table": name} for name in tables]
    return {"s": sum(scope.HISTOGRAMS.sum(series, **l) for l in labels),
            "calls": sum(scope.HISTOGRAMS.count(series, **l)
                         for l in labels)}


def tier_counts(system):
    """The tier's counters and spans, summed over both tables, as they
    stand now; a counter or span the program lacks reads nought."""
    from openembedding_tpu.analysis import scope
    out = {c: scope.HISTOGRAMS.counter(c) for c in COUNTERS}
    out.update({span: span_read(span, system.tiers) for span in SPANS})
    return out


def fill_to_budget(system, state, short_of=64):
    """Warm further rows (the highest row ids the cache does not hold)
    until each table is ``short_of`` rows under its budget: the state
    just before an eviction (``offload_controls``)."""
    emb = dict(state.emb)
    for name, tier in system.tiers.items():
        budget = int(tier.occupancy_threshold * tier.cache_capacity)
        short = budget - short_of - int(tier.memory_stats()["resident_rows"])
        if short > 0:
            emb[name] = tier.warm(
                emb[name], np.nonzero(~tier._resident)[0][-short:])
    return state.replace(emb=emb)


def plant(system, fault):
    """Plant one of ``FAULTS`` in the built system (read at a cell's size
    by ``benchmark/offload_controls.py``; the benchmark's runs never call
    this): a miss served from the initializer and not from the store, or
    a writeback that drops its rows."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    for tier in system.tiers.values():
        if fault == "miss_from_initializer":
            def initial(ids, tier=tier):
                rows = np.full((len(ids),) + tier.host_weights.shape[1:],
                               tier.initializer.value,
                               tier.host_weights.dtype)
                return rows, {k: np.full((len(ids),) + v.shape[1:],
                                         tier.optimizer.slot_init(k), v.dtype)
                              for k, v in tier.host_slots.items()}
            tier._gather_host = initial
        else:
            def dropped(cache, dirty_ids, tier=tier):
                with tier._book:
                    tier._dirty.clear_chunks(dirty_ids)
            tier._start_writeback = dropped
