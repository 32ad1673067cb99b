"""The one file that touches the system under test.

It builds ``openembedding_tpu``'s objects the way ``examples/
criteo_deepctr.py`` and ``chip_smoke.py`` do (one fused table, DeepFM,
Adagrad rows, optax Adam, plane ``a2a``), hands them the benchmark's seeded
weights, and reads back what the comparison needs. Everything else under
``benchmark/`` is independent of the program.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import reference, seeded

FLAX_LEAVES = {                 # reference leaf -> path in DeepFM's params
    "h0_w": ("MLP_0", "Dense_0", "kernel"), "h0_b": ("MLP_0", "Dense_0", "bias"),
    "h1_w": ("MLP_0", "Dense_1", "kernel"), "h1_b": ("MLP_0", "Dense_1", "bias"),
    "out_w": ("Dense_0", "kernel"), "out_b": ("Dense_0", "bias"),
    "bias": ("bias",),
}
TABLE_NAMES = {"fields": "fields", "linear": "fields:linear"}
FILL_CHUNK = 1 << 21            # keys per bulk-insert call
# Bytes held on each chip ahead of the tables, for the whole run. With the
# tables at the very start of the heap a step takes 50.4 ms, 1 KiB in
# 45.9 ms, 1 MiB in 49.9 ms (PERF.md, PR 25): 1 KiB is where a PRNG key and
# a sample batch put them on Trainer.init's own path, near enough.
TABLES_START_AT = 1024


def found_devices():
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


@dataclasses.dataclass
class System:
    config: dict
    mesh: object
    coll: object
    trainer: object
    mapper: object
    lead: object = None         # the buffer ahead of the tables

    @property
    def by_batch(self):
        from openembedding_tpu.parallel.mesh import DATA_AXIS
        return NamedSharding(self.mesh, P(DATA_AXIS))

    @functools.cached_property
    def pull(self):
        """Jitted pull of both tables for a placed batch of ids."""
        return jax.jit(lambda emb, idx: self.coll.pull(emb, idx))

    @functools.cached_property
    def accumulated(self):
        """sqrt of all that Adagrad's accumulator holds above its start."""
        start = self.config["adagrad"]["initial_accumulator_value"]
        return jax.jit(
            lambda a: jnp.sqrt(jnp.sum(a - jnp.float32(start))))


def build(config):
    """Mesh over the cell's chips, collection, trainer and id mapper."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh

    features = tuple(criteo.SPARSE_NAMES[:config["sparse_features"]])
    devices = jax.devices()[:config["chips"]]
    mesh = create_mesh(config["mesh"]["data"], config["mesh"]["model"],
                       devices)
    adagrad = dict(config["adagrad"], category="adagrad")
    use_hash = config["table_kind"] == "hash"
    specs, mapper = make_fused_specs(
        features, -1 if use_hash else config["rows_per_feature"],
        config["embedding_dim"], optimizer=adagrad,
        hash_capacity=config.get("hash_capacity", 0), plane=config["plane"])
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(
        deepctr.build_model(config["model"], features,
                            dnn_units=tuple(config["dnn_units"])),
        coll, optax.adam(reference.ADAM["lr"]))
    return System(config, mesh, coll, trainer, mapper)


def program_batch(system, raw):
    """A raw batch of the traffic generator as ``Trainer.fit`` takes it:
    the program's own mapper fuses the id columns on the host."""
    ids = raw["ids"].astype(np.int64)
    cols = {name: ids[:, j]
            for j, name in enumerate(system.mapper.feature_names)}
    return {"label": raw["label"], "dense": raw["dense"],
            "sparse": system.mapper.fuse(cols)}


def _flax_params(leaves):
    tree = {}
    for name, path in FLAX_LEAVES.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaves[name]
    return tree


def dense_leaves(params):
    """The program's flax params as the reference names its leaves."""
    out = {}
    for name, path in FLAX_LEAVES.items():
        node = params
        for part in path:
            node = node[part]
        out[name] = node
    return out


def _array_table(system, seed, number, name):
    """One array table filled from the seed where the program will look
    for each row: physical row -> (shard, local) -> the id the program's
    own layout gives it -> (feature, id within the feature)."""
    from openembedding_tpu import table as table_lib
    config, spec = system.config, system.coll.sharding_spec(name)
    per_feature = config["rows_per_feature"]
    dim = system.coll.specs[name].output_dim
    scale = config["init_scale"]["fields" if number == 0 else "linear"]
    phys = jnp.arange(spec.padded_vocab, dtype=jnp.int32)
    row = spec.global_row(phys // spec.rows_per_shard,
                          phys % spec.rows_per_shard)
    live = row < per_feature * config["sparse_features"]
    rows = seeded.table_rows(seed, number, row // per_feature,
                             row % per_feature, jnp.zeros_like(row), dim,
                             scale, jnp)
    accum = jnp.full((spec.padded_vocab, dim),
                     config["adagrad"]["initial_accumulator_value"],
                     jnp.float32)
    return table_lib.TableState(
        weights=jnp.where(live[:, None], rows, 0.0), slots={"accum": accum})


def initial_state(system, seed, on_device=True):
    """The TrainState the cell starts from, made on the device from the
    seed in one jitted call (hash tables: allocated by the program, then
    filled with its own bulk insert)."""
    from openembedding_tpu.training import TrainState
    config, coll, trainer = system.config, system.coll, system.trainer
    replicated = NamedSharding(system.mesh, P())
    # The tables are the first thing this process puts on the device, and
    # they are in place before anything else is. The runtime allocates
    # lazily and frees on a thread of its own, so a table allocated among
    # other buffers starts at an address that differs from run to run, and
    # the step's random reads and writes are that sensitive to it: the
    # same program took 45.7 or 46.5 ms a step by the run (PERF.md, PR 25).
    if on_device and jax.live_arrays():
        raise RuntimeError("something was put on the device before the "
                           "tables: their addresses would move run to run")
    lead = np.zeros(TABLES_START_AT // 4, np.uint32)
    lead[0] = seeded.seed_word(seed)     # the fill's only argument: a new
    system.lead = jax.block_until_ready(  # seed runs the compiled program
        jax.device_put(lead, replicated))
    if config["table_kind"] == "array":
        emb = jax.jit(
            lambda lead: {name: _array_table(system, lead[0], n, name)
                          for n, name in enumerate(coll.specs)},
            out_shardings=coll.state_shardings())(system.lead)
    else:
        emb = _filled_hash_tables(system, seed)
    jax.block_until_ready(emb)

    params = _flax_params(reference.dense_init(seed, config))
    sample_rows = {
        name: jax.ShapeDtypeStruct(
            (1, config["sparse_features"], coll.specs[name].output_dim),
            jnp.float32) for name in coll.specs}
    want = jax.eval_shape(
        lambda dense, rows: trainer.module.init(
            jax.random.PRNGKey(0), dense, rows)["params"],
        jax.ShapeDtypeStruct((1, config["dense_features"]), jnp.float32),
        sample_rows)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise RuntimeError(
            f"the program's DeepFM params {want} are not the reference's "
            f"{got}: benchmark/system.py FLAX_LEAVES is out of date")
    params = jax.device_put(params, replicated)
    opt_state = jax.device_put(trainer.tx.init(params), replicated)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, emb=emb)


def prefill_keys(config):
    """(feature [N], key [N]) of every key a hash table holds at the start:
    ranks 1..K of every feature, the head of the traffic's Zipf stream."""
    from .traffic_gen.zipf_train import feature_ids
    ranks = np.arange(1, config["prefill_ranks_per_feature"] + 1,
                      dtype=np.uint64)
    n_feat = config["sparse_features"]
    keys = np.stack([feature_ids(ranks, j, None) for j in range(n_feat)],
                    axis=1)
    feat = np.broadcast_to(np.arange(n_feat, dtype=np.int32), keys.shape)
    return feat.ravel(), keys.ravel()


def _filled_hash_tables(system, seed):
    from openembedding_tpu import hash_table as hash_lib
    from openembedding_tpu.parallel import sharded_hash
    config, coll, mapper = system.config, system.coll, system.mapper
    emb = coll.init(jax.random.PRNGKey(0))
    # fresh rows are drawn under the key the configuration states
    replicated = NamedSharding(system.mesh, P())
    emb = {name: s.replace(init_rng=jax.device_put(   # a buffer per table:
        np.asarray(reference.fresh_key(seed)), replicated))  # both donated
        for name, s in emb.items()}
    feat, keys = prefill_keys(config)
    n_feat = config["sparse_features"]
    fused = mapper.fuse({name: keys.reshape(-1, n_feat)[:, j].astype(np.int64)
                         for j, name in enumerate(mapper.feature_names)}
                        )[mapper.name].reshape(-1, 2)
    lo, hi = seeded.split_words(keys)
    chunk_keys = min(FILL_CHUNK, 1 << (len(keys) - 1).bit_length())
    pad = -len(keys) % chunk_keys
    if pad:                      # EMPTY-sentinel keys are skipped on insert
        empty = np.full((pad, 2), hash_lib.empty_key(np.int32), np.int32)
        fused = np.concatenate([fused, empty])
        feat, lo, hi = (np.concatenate([a, np.zeros(pad, a.dtype)])
                        for a in (feat, lo, hi))

    @jax.jit
    def rows_of(word, f, lo, hi):
        return {name: seeded.table_rows(
            word, n, f, lo, hi, coll.specs[name].output_dim,
            config["init_scale"]["fields" if n == 0 else "linear"], jnp)
            for n, name in enumerate(coll.specs)}

    # The table's own bulk insert, under a jit that donates the table: the
    # program's call alone returns a second copy of a 9.5 GiB table.
    inserts = {
        name: jax.jit(
            lambda state, keys, rows, name=name:
            sharded_hash.insert_rows_sharded(
                state, keys, rows, mesh=system.mesh,
                spec=coll.sharding_spec(name)), donate_argnums=0)
        for name in coll.specs}
    for at in range(0, len(fused), chunk_keys):
        cut = slice(at, at + chunk_keys)
        rows = rows_of(seeded.seed_word(seed), feat[cut], lo[cut], hi[cut])
        chunk = jnp.asarray(fused[cut])
        for name in coll.specs:
            emb[name] = inserts[name](emb[name], chunk, rows[name])
    return emb


def insert_failures(system, emb):
    """Probe-window overflows the hash tables counted (0 for arrays)."""
    return sum(int(s.insert_failures) for s in emb.values()
               if hasattr(s, "insert_failures"))


def pull_rows(system, emb, batches):
    """Rows of both tables for every lookup of ``batches`` (program
    batches), through the program's own pull: {table: [n, B, F, dim]}.
    A missing hash key reads as its initializer row; nothing is mutated."""
    out = {t: [] for t in TABLE_NAMES}
    for b in batches:
        idx = jax.device_put(b["sparse"], system.by_batch)
        rows = system.pull(emb, idx)
        for t, name in TABLE_NAMES.items():
            out[t].append(np.asarray(rows[name]))
    return {t: np.stack(v) for t, v in out.items()}


def first_grad_norms(system, state):
    """Norm of the first step's gradient per leaf, worked out from the
    state one step leaves: Adam's first moment is (1 - b1) g, Adagrad's
    accumulator grew by g^2 on every row."""
    mu = dense_leaves(state.opt_state[0].mu)
    out = {name: float(jnp.linalg.norm(m)) / (1 - reference.ADAM["b1"])
           for name, m in mu.items()}
    for t, name in TABLE_NAMES.items():
        out[t] = float(system.accumulated(state.emb[name].slots["accum"]))
    return out


def step_hlo(system, state, batch):
    """Optimized HLO text of the step program as the window ran it (a
    compile-cache hit): the device trace names operations by instruction,
    and this text maps each instruction to the program's named scopes."""
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        state)
    placed = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=system.by_batch), batch)
    return system.trainer.lower_train_step(abstract, placed) \
        .compile().as_text()
