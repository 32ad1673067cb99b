"""What the tests of the step's plan share (``test_plan*.py``): a
one-table collection of each kind on each mesh and the ids of each case,
the counters of the plans a step built, steps of a DeepFM over one fused
table and its ``:linear`` twin, and three ``Trainer.train_step``s at the
rehearsal shapes of the benchmark's cells."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import optax

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec, Trainer
from openembedding_tpu import hash_table as hash_lib
from openembedding_tpu import table as table_lib
from openembedding_tpu.embedding import SameColumns
from openembedding_tpu.fused import LINEAR_SUFFIX, make_fused_specs
from openembedding_tpu.models import deepctr
from openembedding_tpu.parallel import sharded
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.utils import observability

CHUNK = 8           # table.APPLY_CHUNK and FIND_CHUNK, set small for these
VOCAB, DIM, N = 96, 5, 40
EMPTY = int(hash_lib.empty_key(jnp.int32))
PROGRAMS = (sharded._plan_program, sharded._pull_program,
            sharded._apply_program)


@pytest.fixture
def small_chunks(monkeypatch):
    """The chunk is read when a program is traced: none traced before may
    be found again, and none of these after."""
    def clear():
        for program in PROGRAMS:
            program.cache_clear()

    monkeypatch.setattr(table_lib, "APPLY_CHUNK", CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", CHUNK)
    clear()
    yield
    clear()


def _ids(case, pad):
    rng = np.random.RandomState(11)
    if case == "padding":           # a third of the positions ask nothing
        ids = rng.randint(0, VOCAB, size=N)
        ids[rng.rand(N) < 0.33] = pad
    elif case == "all_duplicate":
        ids = np.full(N, 17)
    elif case == "all_distinct":
        ids = rng.permutation(VOCAB)[:N]
    elif case == "chunk_boundary":  # 2 * CHUNK distinct keys, no padding
        ids = np.concatenate([rng.permutation(VOCAB)[:2 * CHUNK]] * 3)[:N]
        assert len(set(ids.tolist())) == 2 * CHUNK
    else:                           # "zipf": duplicates as a batch has them
        ids = np.minimum(rng.zipf(1.3, size=N), VOCAB) - 1
    return ids.astype(np.int32).reshape(N // 4, 4)


# (data, model, plane): one shard, the masked-local body over two model
# shards, the routed body over a 2x2 mesh
MESHES = pytest.mark.parametrize(
    "data,model,plane", [(1, 1, "a2a"), (1, 2, "psum"), (2, 2, "a2a")],
    ids=["1x1", "psum2", "a2a2x2"])


def _mesh(devices, data, model):
    return create_mesh(data, model, devices[:data * model])


def _collection(kind, mesh, plane, a2a_capacity=0):
    spec = EmbeddingSpec(
        name="t", input_dim=VOCAB if kind == "array" else -1,
        output_dim=DIM, hash_capacity=1024, plane=plane,
        a2a_capacity=a2a_capacity,
        key_dtype={"array": None, "hash32": "int32",
                   "hashwide": "wide"}[kind],
        optimizer={"category": "adagrad", "learning_rate": 0.1},
        initializer={"category": "uniform", "minval": -1.0, "maxval": 1.0})
    coll = EmbeddingCollection([spec], mesh)
    return coll, coll.init(jax.random.PRNGKey(5))


def _same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))



def _same_bits(got, want):
    """Bit for bit: ``-0.0`` is not ``0.0``."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


PLAN_COUNTERS = ("plan_columns_same_object", "plan_columns_compared_equal",
                 "plan_columns_differ", "dedup_plans_built",
                 "dedup_plan_tables")

def _counts(snapshot):
    return {k: int(snapshot[k]["count"]) for k in PLAN_COUNTERS
            if k in snapshot}


STEP_CHUNK = 256    # under a tiny_* step's 1,664 positions a table


def _three_steps(name, seed, planned, monkeypatch, lookahead=True):
    """The state three ``Trainer.train_step``s leave on the rehearsal
    configuration ``name``; without ``planned`` the step's plan is empty,
    which makes it ``collection.pull`` and ``apply_gradients`` as they run
    without one; ``planned="each"`` hides from the step that its two
    tables are fed one array, which makes it a plan a table;
    ``planned="resolve_again"`` keeps from the push what the pull resolved,
    which makes it the push that has the plan alone. Without ``lookahead``
    no step is handed the batch after it."""
    from benchmark import offload_system, run as bench_run, system
    from benchmark.traffic_gen import zipf_train

    config = bench_run.load("configs", name)
    lib = offload_system if "offload" in name else system
    traffic = dict(bench_run.load("traffic", "train_zipf"), pool_batches=4)
    built = lib.build(config)
    if not planned:
        monkeypatch.setattr(built.coll, "plan", lambda *a, **kw: {})
    elif planned == "each":
        monkeypatch.setattr(built.coll, "same_columns",
                            lambda inputs: SameColumns())
    elif planned == "resolve_again":
        _resolve_again(built.coll)
    state = lib.initial_state(built, seed, on_device=False)
    pool = [system.program_batch(built, raw)
            for raw in zipf_train.make(traffic, config, seed)]
    losses = []
    for t in range(3):
        state, metrics = built.trainer.train_step(
            state, pool[t], next_batch=pool[t + 1] if lookahead else None)
        losses.append(float(metrics["loss"]))
    jax.effects_barrier()
    out = jax.device_get((state.params, state.opt_state, state.emb))
    if lib is offload_system:
        lib.flush(built, state)
        out = out, lib.store_rows(built, pool[:3])
    return losses, out


def _resolve_again(coll):
    """What the step ran before the push took the pull's resolution."""
    pull = coll.pull_resolved
    coll.pull_resolved = lambda *a, **kw: (pull(*a, **kw)[0], {})


def _abstract_step(built, batch):
    """(state, batch) as ``benchmark.system.step_hlo`` hands them to
    ``lower_train_step``: fresh ShapeDtypeStructs, one a name."""
    state = jax.eval_shape(built.trainer.init, jax.random.PRNGKey(0), batch)
    replicated = jax.sharding.NamedSharding(
        built.mesh, jax.sharding.PartitionSpec())

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    state = state.replace(
        emb=placed(state.emb, built.coll.state_shardings()),
        **{k: placed(getattr(state, k), jax.tree.map(
            lambda _: replicated, getattr(state, k)))
           for k in ("step", "params", "opt_state")})
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=built.by_batch), batch)
    return state, batch


FEATURES = ("c0", "c1", "c2", "c3")
STEPS, BATCH = 3, 32


def _fused_trainer(kind, shape=(1, 1)):
    """A DeepFM over one fused table and its ``:linear`` twin on one
    device (or routed over a 2x2 mesh), as ``examples/criteo_deepctr.py``
    builds it."""
    mesh = _mesh(jax.devices(), *shape)
    specs, mapper = make_fused_specs(
        FEATURES, VOCAB if kind == "array" else -1, DIM,
        optimizer={"category": "adagrad", "learning_rate": 0.1},
        hash_capacity=1024,
        key_dtype="int32" if kind == "hash32" else "wide")
    coll = EmbeddingCollection(specs, mesh)
    return coll, Trainer(deepctr.build_model("deepfm", FEATURES), coll,
                         optax.adam(1e-2)), mapper


def _fused_batches(mapper, twin):
    """``STEPS`` batches of the mapper's own; the ``:linear`` column is the
    table's array (``"same"``: what ``fuse`` returns), an equal
    ``"copy"``, or the ids of other examples (``"differ"``)."""
    rng = np.random.RandomState(21)
    for _ in range(STEPS):
        sparse = mapper.fuse({
            f: (np.minimum(rng.zipf(1.3, size=BATCH), VOCAB) - 1)
            .astype(np.int32) for f in FEATURES})
        assert sparse[mapper.name + LINEAR_SUFFIX] is sparse[mapper.name]
        if twin == "copy":
            sparse[mapper.name + LINEAR_SUFFIX] = sparse[mapper.name].copy()
        elif twin == "differ":
            sparse[mapper.name + LINEAR_SUFFIX] = np.roll(
                sparse[mapper.name], 1, axis=0)
        yield {"label": (rng.rand(BATCH) < 0.3).astype(np.float32),
               "dense": rng.randn(BATCH, 4).astype(np.float32),
               "sparse": sparse}


def _fused_steps(kind, twin, patch=None, shape=(1, 1)):
    """(losses, state and scores, plan counters) of ``STEPS`` steps under
    ``record_stats``; ``patch(collection)`` first."""
    for program in PROGRAMS:
        program.cache_clear()
    coll, trainer, mapper = _fused_trainer(kind, shape)
    if patch:
        patch(coll)
    batches = list(_fused_batches(mapper, twin))
    observability.GLOBAL.reset()
    observability.set_evaluate_performance(True)
    try:
        state = trainer.init(jax.random.PRNGKey(3), batches[0])
        losses = []
        for b in batches:
            state, metrics = trainer.train_step(state, b)
            losses.append(float(metrics["loss"]))
        jax.effects_barrier()
        counted = _counts(observability.GLOBAL.snapshot())
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    scores = trainer.eval_step(state, batches[0])
    return losses, jax.device_get(
        (state.params, state.opt_state, state.emb, scores)), counted


def _no_plan(coll):
    coll.plan = lambda *a, **kw: {}
