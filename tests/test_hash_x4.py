"""Upstream's key path across chips: 62-bit hashed keys in a hash table
sharded over a 2x2 mesh (``HashStore`` behind ``exchange_pull`` /
``exchange_push``), the configuration of the benchmark's
``deepfm_dim9_hash_x4`` at a size a CPU test holds.

The push's conditional holds no table: its branches combine what the owner
received (distinct keys, summed gradients, counts), and one find-or-insert
a table follows it, taking the slots the step's pull resolved where the
buckets held the step and finding for itself where they did not.

(a) ``Trainer.fit`` over wide keys with fresh keys, a fresh key that both
data shards send in one step, buckets that hold the step or spill
(``a2a_capacity`` 2): rows, accumulators, dense parameters and losses
equal a plain reference kept here (a dict from key to ``[weights,
accumulator]``, float32 numpy Adagrad, no mesh) to the hash
configuration's limits; and the tables equal the one-chip hash table's
bit for bit, through the collection's own pull and push under gradients
whose sums are exact in float32 (the order of a float sum differs between
one device and four senders, and nothing else may). (b) The same
reference in bfloat16 fails a limit. (c) The routed hash step's ``cond``
carries no operand of a table's shape, and the steps that are not this
one lower to the text they had at PR 39. (d) The counters of the routed
path read what a hand count of the batch gives.
"""

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import optax

from openembedding_tpu import EmbeddingCollection, Trainer
from openembedding_tpu import hash_table as hash_lib
from openembedding_tpu import training
from openembedding_tpu.fused import make_fused_specs
from openembedding_tpu.models import deepctr
from openembedding_tpu.optim.initializers import make_initializer
from openembedding_tpu.parallel import alltoall as a2a
from openembedding_tpu.parallel import sharded, sharded_hash
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.training import SAME_COLUMNS
from openembedding_tpu.utils import observability

FEATURES = ("c0", "c1", "c2")
DIM, BATCH, STEPS, DENSE = 9, 32, 4, 4
CAPACITY = 4096                 # slots over the mesh: 1,024 a chip on 2x2
UNIVERSE, HELD = 40, 24         # keys a feature: the table holds the first
ADAGRAD = {"category": "adagrad", "learning_rate": 0.01,
           "initial_accumulator_value": 0.1, "epsilon": 1e-7}
ADAM_LR = 1e-3
PROGRAMS = (sharded._plan_program, sharded._pull_program,
            sharded._apply_program)
with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                       "configs", "deepfm_dim9_hash.json")) as _f:
    LIMITS = json.load(_f)["limits"]


def _keys():
    """[F, UNIVERSE] 62-bit keys, as ``to_hash_bucket_fast(col, 2**62)``
    hands them out."""
    rng = np.random.Generator(np.random.PCG64(40))
    return rng.integers(0, 1 << 62, size=(len(FEATURES), UNIVERSE),
                        dtype=np.int64)


def _batches(keys, steps=STEPS):
    """Host batches of ids. Ranks are Zipf over a feature's universe, so
    duplicates abound and ranks past ``HELD`` arrive as fresh keys; in
    step 0 the two halves of the batch (the two data shards) both meet
    one key no table holds, and no other position does."""
    rng = np.random.Generator(np.random.PCG64(41))
    shared = UNIVERSE - 1
    for t in range(steps):
        ranks = np.minimum(rng.zipf(1.2, size=(BATCH, len(FEATURES))),
                           UNIVERSE - 1) - 1
        if t == 0:
            ranks[0, 0] = ranks[BATCH // 2 + 1, 0] = shared
        yield {"label": (rng.random(BATCH) < 0.3).astype(np.float32),
               "dense": rng.standard_normal((BATCH, DENSE))
               .astype(np.float32),
               "ids": {f: keys[j][ranks[:, j]]
                       for j, f in enumerate(FEATURES)}}


def _build(shape, a2a_capacity=0):
    mesh = create_mesh(*shape, jax.devices()[:shape[0] * shape[1]])
    specs, mapper = make_fused_specs(
        FEATURES, -1, DIM, optimizer=ADAGRAD, hash_capacity=CAPACITY,
        plane="a2a", a2a_capacity=a2a_capacity)
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", FEATURES,
                                          dnn_units=(16, 8)),
                      coll, optax.adam(ADAM_LR))
    return mesh, coll, trainer, mapper


def _pairs(mapper, keys, held=HELD):
    """[F * held, 2] the fused wide keys of the first ``held`` keys of
    every feature, as the mapper makes them."""
    cols = {f: keys[j][:held] for j, f in enumerate(FEATURES)}
    return np.asarray(mapper.fuse(cols)[mapper.name]).reshape(-1, 2)


def _seeded_rows(pairs, dim):
    rng = np.random.Generator(np.random.PCG64(42 + dim))
    return rng.uniform(-0.05, 0.05, size=(len(pairs), dim)) \
        .astype(np.float32)


def _filled(mesh, coll, mapper, keys):
    """Both tables with the first ``HELD`` keys of every feature under
    seeded random rows, through the program's own bulk insert."""
    emb = coll.init(jax.random.PRNGKey(7))
    pairs = _pairs(mapper, keys)
    for name in coll.specs:
        rows = _seeded_rows(pairs, coll.specs[name].output_dim)
        emb[name] = sharded_hash.insert_rows_sharded(
            emb[name], jnp.asarray(pairs), jnp.asarray(rows), mesh=mesh,
            spec=coll.sharding_spec(name))
    return emb


def _read(mesh, coll, emb, pairs):
    """{table: (found, weights, accumulators)} of ``pairs`` as the tables
    hold them."""
    out = {}
    for name in coll.specs:
        found, w, slots = sharded_hash.read_rows_sharded(
            emb[name], jnp.asarray(pairs), mesh=mesh,
            spec=coll.sharding_spec(name))
        out[name] = tuple(np.asarray(x) for x in (found, w, slots["accum"]))
    return out


def _program_batch(mapper, batch):
    return {"label": batch["label"], "dense": batch["dense"],
            "sparse": mapper.fuse(batch["ids"])}


# --- the plain reference -------------------------------------------------

def _reference(coll, trainer, mapper, keys, rngs, params0, batches, dtype):
    """``len(batches)`` steps by a dict a table from key to ``[weights,
    accumulator]``: a key the dict lacks reads as its own initial row,
    duplicates of a batch are summed and applied once by Adagrad, the net
    by Adam. ``(losses, tables, params)``."""
    tables, fresh = {}, {}
    pairs = _pairs(mapper, keys)
    for name, spec in coll.specs.items():
        rows = _seeded_rows(pairs, spec.output_dim).astype(dtype)
        start = np.full(spec.output_dim, ADAGRAD["initial_accumulator_value"],
                        dtype)
        tables[name] = {tuple(k): [r, start.copy()]
                        for k, r in zip(pairs.tolist(), rows)}
        init = make_initializer(spec.initializer)
        rng = jnp.asarray(rngs[name])

        def draw(key, init=init, rng=rng, dim=spec.output_dim):
            row = hash_lib.init_rows(init, rng, jnp.asarray([key], jnp.int32),
                                     dim, jnp.float32)
            return np.asarray(row[0]).astype(dtype)

        fresh[name] = draw

    cast = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)
    params = cast(params0)
    tx = optax.adam(ADAM_LR)
    opt = tx.init(params)

    def loss_of(params, rows, dense, label):
        logits = trainer.module.apply({"params": params}, dense, rows)
        return training.binary_logloss(logits.astype(jnp.float32), label)

    grads_of = jax.value_and_grad(loss_of, argnums=(0, 1))
    lr, eps = (np.asarray(ADAGRAD[k], dtype)
               for k in ("learning_rate", "epsilon"))
    losses = []
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            sparse = mapper.fuse(batch["ids"])
            keyed = {name: [tuple(k) for k in np.asarray(
                sparse[name]).reshape(-1, 2).tolist()] for name in tables}
            for name, table in tables.items():
                for k in keyed[name]:
                    if k not in table:
                        table[k] = [fresh[name](k), np.full(
                            coll.specs[name].output_dim,
                            ADAGRAD["initial_accumulator_value"], dtype)]
            rows = {name: jnp.asarray(np.stack(
                [tables[name][k][0] for k in keyed[name]]).reshape(
                    BATCH, len(FEATURES), -1)) for name in tables}
            loss, (g_params, g_rows) = grads_of(
                params, rows, jnp.asarray(batch["dense"], dtype),
                jnp.asarray(batch["label"]))
            losses.append(float(loss))
            for name, table in tables.items():
                summed = {}
                g = np.asarray(g_rows[name]).reshape(len(keyed[name]), -1)
                for k, row in zip(keyed[name], g):
                    summed[k] = summed.get(k, 0) + row.astype(dtype)
                for k, s in summed.items():
                    w, a = table[k]
                    a = (a + s * s).astype(dtype)
                    table[k] = [(w - lr * s / (np.sqrt(a) + eps))
                                .astype(dtype), a]
            updates, opt = tx.update(g_params, opt, params)
            params = cast(optax.apply_updates(params, updates))
    return losses, tables, params


def _gap(got, want, start):
    """The norm of what differs over the norm of what the steps moved."""
    got, want, start = (np.asarray(x, np.float64) for x in (got, want, start))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want - start),
                                            1e-30)


def _followed(shape, a2a_capacity):
    """What ``Trainer.fit`` leaves after ``STEPS`` steps on a mesh of
    ``shape``, what the float32 and the bfloat16 reference leave, and the
    gaps between them: {reference: {number: gap}}."""
    for program in PROGRAMS:
        program.cache_clear()
    keys = _keys()
    mesh, coll, trainer, mapper = _build(shape, a2a_capacity)
    batches = list(_batches(keys))
    feed = [_program_batch(mapper, b) for b in batches]
    state = trainer.init(jax.random.PRNGKey(3), feed[0])
    state = state.replace(emb=_filled(mesh, coll, mapper, keys))
    params0 = jax.device_get(state.params)
    emb0 = state.emb
    rngs = {name: np.asarray(s.init_rng) for name, s in emb0.items()}
    every = _pairs(mapper, keys, UNIVERSE)
    rows0 = _read(mesh, coll, emb0, every)
    losses = []
    for b in feed:
        state, last = trainer.fit(state, [b])
        losses.append(float(last["loss"]))
    rows = _read(mesh, coll, state.emb, every)
    failures = sum(int(s.insert_failures) for s in state.emb.values())
    params = jax.device_get(state.params)

    gaps = {}
    for label, dtype in (("float32", np.float32),
                         ("bfloat16", jnp.bfloat16)):
        want_losses, tables, want_params = _reference(
            coll, trainer, mapper, keys, rngs, params0, batches, dtype)
        worst = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                                 zip(losses, want_losses))}
        deltas = []
        for name in coll.specs:
            found, w, acc = rows[name]
            held = np.array([tuple(k) in tables[name]
                             for k in every.tolist()])
            # the table holds the keys the reference met, and no other
            np.testing.assert_array_equal(found, held)
            want = np.stack([np.stack(tables[name][tuple(k)])
                             for k in every[held].tolist()]
                            ).astype(np.float32)
            _, w0, a0 = rows0[name]
            start_a = np.full_like(want[:, 1], ADAGRAD[
                "initial_accumulator_value"])
            deltas.append(_gap(acc[held], want[:, 1], start_a))
            deltas.append(_gap(w[held], want[:, 0],
                               _start_rows(want[:, 0], w0[held],
                                           rows0[name][0][held])))
        leaves = jax.tree.leaves(jax.tree.map(
            _gap, params, jax.device_get(want_params), params0))
        worst["delta_gap"] = max(deltas + leaves)
        gaps[label] = worst
    return gaps, failures


def _start_rows(want, stored, found):
    """Where a key was in the table its stored row, else nought: a fresh
    key's whole row counts as moved, so its init row is held to the
    reference's draw as tightly as a step's change."""
    return np.where(found[:, None], stored, np.zeros_like(want))


@pytest.fixture(scope="module")
def followed():
    """One run of ``_followed`` a case, shared by (a) and (b)."""
    memo = {}

    def get(shape, a2a_capacity):
        if (shape, a2a_capacity) not in memo:
            memo[shape, a2a_capacity] = _followed(shape, a2a_capacity)
        return memo[shape, a2a_capacity]
    return get


CASES = pytest.mark.parametrize("shape,a2a_capacity", [
    ((2, 2), 0), ((2, 2), 2), ((1, 1), 0)],
    ids=["2x2-held", "2x2-spilled", "1x1"])


@CASES
def test_fit_leaves_what_the_plain_reference_leaves(devices8, followed,
                                                    shape, a2a_capacity):
    """(a) Losses, rows, accumulators and dense parameters after
    ``STEPS`` steps of ``Trainer.fit``, to the hash configuration's
    limits; the table holds exactly the keys the batches brought; no
    insert fails."""
    gaps, failures = followed(shape, a2a_capacity)
    assert failures == 0
    assert gaps["float32"]["loss_gap"] <= LIMITS["loss_gap"], gaps
    assert gaps["float32"]["delta_gap"] <= LIMITS["delta_gap"], gaps
    # far inside them on a backend whose float32 is float32
    assert gaps["float32"]["delta_gap"] < 1e-4, gaps


@CASES
def test_the_bfloat16_reference_fails_a_limit(devices8, followed, shape,
                                              a2a_capacity):
    """(b) The control: the same reference with every array in bfloat16
    is outside at least one limit, so the limits tell at this size."""
    gaps, _ = followed(shape, a2a_capacity)
    assert gaps["bfloat16"]["loss_gap"] > LIMITS["loss_gap"] \
        or gaps["bfloat16"]["delta_gap"] > LIMITS["delta_gap"], gaps


# --- four chips against one, bit for bit ----------------------------------

def _pushed(shape, a2a_capacity, planned):
    """The tables ``STEPS`` pulls and pushes leave, through the
    collection's own ``plan`` / ``pull_resolved`` / ``apply_gradients``,
    under gradients that are multiples of 1/8: every sum of them is exact,
    whatever its order."""
    for program in PROGRAMS:
        program.cache_clear()
    keys = _keys()
    mesh, coll, _, mapper = _build(shape, a2a_capacity)
    emb = _filled(mesh, coll, mapper, keys)
    rng = np.random.Generator(np.random.PCG64(43))
    by_batch = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(coll.sharding_spec(
            mapper.name).data_axis))
    pulled = []
    for batch in _batches(keys):
        sparse = mapper.fuse(batch["ids"])
        inputs = jax.device_put(
            {name: np.asarray(sparse[name]) for name in coll.specs},
            by_batch)
        plan = coll.plan(inputs) if planned else {}
        rows, resolved = coll.pull_resolved(emb, inputs, plan=plan)
        pulled.append(jax.device_get(rows))
        grads = {name: jnp.asarray(rng.integers(
            -8, 9, size=rows[name].shape).astype(np.float32) / 8)
            for name in coll.specs}
        emb = coll.apply_gradients(emb, inputs, grads, plan=plan,
                                   resolved=resolved)
    every = _pairs(mapper, keys, UNIVERSE)
    failures = sum(int(s.insert_failures) for s in emb.values())
    return pulled, _read(mesh, coll, emb, every), failures


@pytest.mark.parametrize("planned", [True, False],
                         ids=["planned", "unplanned"])
@pytest.mark.parametrize("a2a_capacity", [0, 2], ids=["held", "spilled"])
def test_four_chips_leave_the_one_chip_tables_bit_for_bit(
        devices8, a2a_capacity, planned):
    """Every pull's rows, and after the last push every key's presence,
    weight row and accumulator: a fresh key read as its init row on every
    chip alike, inserted once at its owner, its summed gradient applied
    once, the table a step that spills leaves."""
    want = _pushed((1, 1), 0, True)
    got = _pushed((2, 2), a2a_capacity, planned)
    assert got[2] == want[2] == 0
    for a, b in zip(jax.tree.leaves(got[:2]), jax.tree.leaves(want[:2]),
                    strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))
    for name, (found, _, _) in got[1].items():
        assert HELD * len(FEATURES) < found.sum() <= len(found)


# --- (c) lowering ---------------------------------------------------------

def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for inner in _sub_jaxprs(eqn.params):
            yield from _conds(inner)


@pytest.mark.parametrize("planned", [True, False],
                         ids=["planned", "unplanned"])
def test_the_routed_hash_push_has_no_table_in_its_conditional(devices8,
                                                              planned):
    """The push program of a wide-key table over 2x2 whose buckets can
    spill: it has the conditional, and no operand or result of it is as
    long as a shard of the table (the key array, the weights, the
    accumulator). The array push's reads its weights there, as it did."""
    for program in PROGRAMS:
        program.cache_clear()
    keys = _keys()
    mesh, coll, _, mapper = _build((2, 2), a2a_capacity=2)
    emb = coll.init(jax.random.PRNGKey(7))
    batch = next(_batches(keys))
    sparse = mapper.fuse(batch["ids"])
    inputs = {name: jnp.asarray(sparse[name]) for name in coll.specs}
    plan = coll.plan(inputs) if planned else {}
    rows, resolved = coll.pull_resolved(emb, inputs, plan=plan)
    jaxpr = jax.make_jaxpr(
        lambda emb, grads: coll.apply_gradients(
            emb, inputs, grads, plan=plan, resolved=resolved))(emb, rows)
    per_shard = coll.sharding_spec(mapper.name).capacity_per_shard
    conds = list(_conds(jaxpr.jaxpr))
    assert len(conds) == len(coll.specs)
    for eqn in conds:
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(var.aval, "shape", ())
            assert not shape or shape[0] != per_shard, (var.aval, eqn)


# sha256 of the one-plan step of the benchmark's rehearsal configurations,
# lowered on the CPU backend. The array step on one chip (the saving cells
# run it too) and routed over 2x2 are the text they were at the parent of
# PR 40 (4d7b794); the cached, grouped and ``int8_ef`` steps are held by
# ``tests/test_plan_step.py``'s own pins, which stand. The hash
# steps are as PR 41 left them: their compact insert loop walks its
# misses in trips (``hash_table._insert_trips``), and nothing else of
# them changed (the one-chip hash step 254,067 -> 267,241 characters).
# The routed hash step was 541,620 characters at the parent of PR 40.
_PARENT_TEXTS = {
    "tiny_array":
    "4ca88b7d0911ec21e52b2c57ad5bbab94e1fa9a64e57a4698f62065e213053f7",
    "tiny_array_x4":
    "8b1a36f64fc673c9b5ee3c88379cc06419e810e46cc703bf188a058462504f89",
    "tiny_hash":
    "123b2a344528d98a0baeceaa87ed0dee27c78de974505048ad167e489a6dcb98",
    "tiny_offload":
    "a51d0d990f5c725ade306314a6f60a782be34cafe6ad5e14e6aaa6f93eab05ff",
    "tiny_hash_offload":
    "f71db69caf2cce5bd348946efb886b76effe8aef29b20e45f7cb3622c38a7ad6",
    "tiny_hash_x4":
    "bb8ad2d9bae0b7fd1bb2b6357c09d0a02448b38ea14d49bef22d85db2c9273a3"}
_PARENT_HASH_X4 = \
    "cba1bc0249f5329d0a49a09487298525c572407eaa9530e95383fbfdee969d66"


def _lowered_step(name):
    from benchmark import offload_keys_system, offload_system, system
    from benchmark import run as bench_run
    from benchmark.traffic_gen import zipf_train
    from plan_helpers import _abstract_step

    config = bench_run.load("configs", name)
    lib = offload_keys_system if name == "tiny_hash_offload" else \
        offload_system if "offload" in name else system
    built = lib.build(config)
    raw = zipf_train.make(dict(bench_run.load("traffic", "train_zipf"),
                               pool_batches=1), config, 3500000021)[0]
    batch = system.program_batch(built, raw)
    state, abstract = _abstract_step(built, batch)
    noted = dict(abstract, **{SAME_COLUMNS: built.coll.same_columns(
        batch["sparse"])})
    return built.trainer.lower_train_step(state, noted).as_text()


@pytest.mark.parametrize("name", sorted(_PARENT_TEXTS))
def test_the_other_steps_lower_to_the_text_they_had(devices8, name):
    text = _lowered_step(name)
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TEXTS[name]


def test_the_routed_hash_step_lowers_to_a_shorter_text(devices8):
    text = _lowered_step("tiny_hash_x4")
    assert hashlib.sha256(text.encode()).hexdigest() != _PARENT_HASH_X4
    assert len(text) < 541620
    assert text.count("call @hash_plan_a2a") == 1


# --- (d) counters ---------------------------------------------------------

COUNTERS = ("hash_insert_compact", "hash_insert_full", "hash_insert_missed",
            "hash_find_slots_live", "hash_find_slots_walked",
            "push_slots_carried", "routed_plan_owner_keys_live",
            "routed_plan_owner_slots", "routed_owner_fresh_keys")


def _counted(a2a_capacity, steps=2):
    """{counter: count} over ``steps`` train steps on 2x2 under
    ``record_stats``, and the batches they ran."""
    for program in PROGRAMS:
        program.cache_clear()
    keys = _keys()
    mesh, coll, trainer, mapper = _build((2, 2), a2a_capacity)
    batches = list(_batches(keys, steps))
    feed = [_program_batch(mapper, b) for b in batches]
    observability.GLOBAL.reset()
    observability.set_evaluate_performance(True)
    try:
        state = trainer.init(jax.random.PRNGKey(3), feed[0])
        state = state.replace(emb=_filled(mesh, coll, mapper, keys))
        jax.effects_barrier()
        observability.GLOBAL.reset()        # the fill's inserts counted too
        for b in feed:
            state, _ = trainer.train_step(state, b)
        jax.block_until_ready(state)
        jax.effects_barrier()
        snapshot = observability.GLOBAL.snapshot()
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    return {k: int(snapshot[k]["count"]) for k in COUNTERS
            if k in snapshot}, batches, mapper, coll


@pytest.mark.parametrize("a2a_capacity", [0, 2], ids=["held", "spilled"])
def test_the_routed_counters_read_a_hand_count(devices8, a2a_capacity):
    """Two steps on 2x2, two tables: the owner's find-or-insert runs once
    a device a table a step (``hash_insert_full`` counts a call; the
    push's conditional holds none of them any more), the plan once a
    device a step, and the sums over devices are the batch's: the
    distinct keys of a step, the keys among them that no table held, the
    slots a pull resolved."""
    steps, devices, tables = 2, 4, 2
    counted, batches, mapper, coll = _counted(a2a_capacity, steps)
    seen = {tuple(k) for k in _pairs(mapper, _keys()).tolist()}
    distinct = fresh = 0
    for b in batches:
        step = {tuple(k) for k in np.asarray(mapper.fuse(b["ids"])[
            mapper.name]).reshape(-1, 2).tolist()}
        distinct += len(step)
        fresh += len(step - seen)
        seen |= step
    assert fresh > 0
    # small calls are all insert loop: one call a device a table a step
    assert counted.get("hash_insert_full", 0) \
        + counted.get("hash_insert_compact", 0) == steps * devices * tables
    assert counted["hash_insert_missed"] == tables * fresh
    assert counted["routed_owner_fresh_keys"] == tables * fresh
    per_device = sharded._exchange_args(
        coll.mesh, coll.sharding_spec(mapper.name), True, False)
    cap = a2a.bucket_capacity(
        BATCH * len(FEATURES) // devices, devices, a2a_capacity,
        per_device["slack"])
    assert counted["routed_plan_owner_slots"] == steps * devices \
        * devices * cap
    if a2a_capacity == 0:
        # the buckets held both steps: a key has one owner, so the owners'
        # distinct keys are the step's, and the push took each one's slot
        # from its pull
        assert counted["routed_plan_owner_keys_live"] == distinct
        assert counted["push_slots_carried"] == tables * distinct
    else:
        # a step the buckets did not hold carries no slot: the owner
        # finds the gathered keys itself
        assert counted["routed_plan_owner_keys_live"] < distinct
        assert counted["push_slots_carried"] == 0
