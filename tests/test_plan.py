"""One dedup a table a step (``dedup.Plan``): the masked-local pull resolves
each distinct key once and expands, the push takes the same plan.

With a plan or without, a pull returns the same rows and a push leaves the
same table, bit for bit: on one shard and on a two-shard ``psum`` mesh, for
array, int32-key and wide-key hash tables, whatever the ids (padding, all
one key, all distinct, the distinct keys ending on a chunk boundary), and
a hash key never pushed reads its init row. ``Trainer.train_step`` builds
the plan; three steps of it leave the state three steps without leave.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu import hash_table as hash_lib
from openembedding_tpu import table as table_lib
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


def _collection(kind, mesh, plane):
    spec = EmbeddingSpec(
        name="t", input_dim=VOCAB if kind == "array" else -1,
        output_dim=DIM, hash_capacity=1024, plane=plane,
        key_dtype={"array": None, "hash32": "int32",
                   "hashwide": "wide"}[kind],
        optimizer={"category": "adagrad", "learning_rate": 0.1},
        initializer={"category": "uniform", "minval": -1.0, "maxval": 1.0})
    coll = EmbeddingCollection([spec], mesh)
    return coll, coll.init(jax.random.PRNGKey(5))


def _same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["padding", "all_duplicate", "all_distinct",
                                  "chunk_boundary", "zipf"])
@pytest.mark.parametrize("model,plane", [(1, "a2a"), (2, "psum")],
                         ids=["1x1", "psum2"])
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_plan_changes_no_row_and_no_update(devices8, small_chunks, kind,
                                           model, plane, case):
    mesh = create_mesh(1, model, devices8[:model])
    coll, states = _collection(kind, mesh, plane)
    assert not coll.sharding_spec("t").routes
    inputs = {"t": jnp.asarray(_ids(case, -1 if kind == "array" else EMPTY))}
    asked = np.asarray(inputs["t"]) != (-1 if kind == "array" else EMPTY)
    grads = {"t": jnp.asarray(
        np.random.RandomState(12).randn(N // 4, 4, DIM), jnp.float32)}

    plan = coll.plan(inputs)
    assert set(plan) == {"t"}
    # the plan is of the keys as they come: a hash table's padding is the
    # fill, an array's -1 a key like another until its store masks it
    planned = np.asarray(inputs["t"])[asked | (kind == "array")]
    assert int(np.asarray(plan["t"].valid).sum()) == len(set(planned.tolist()))

    rows = coll.pull(states, inputs)
    _same(coll.pull(states, inputs, plan=plan), rows)
    rows = np.asarray(rows["t"])
    assert not rows[~asked].any()
    if kind != "array":     # no key is in the table yet: init rows, not 0
        assert np.abs(rows[asked]).min(axis=-1).max() > 0 and \
            (np.abs(rows[asked]).max(axis=-1) > 0).all()

    # two pushes: the second finds the keys the first inserted
    with_plan = without = states
    for _ in range(2):
        without = coll.apply_gradients(without, inputs, grads)
        with_plan = coll.apply_gradients(with_plan, inputs, grads, plan=plan)
        _same(with_plan, without)
        pulled = coll.pull(without, inputs)
        _same(coll.pull(with_plan, inputs, plan=plan), pulled)
    moved = np.asarray(pulled["t"]) - rows
    assert np.abs(moved[asked]).max() > 0 and not moved[~asked].any()


def test_plan_is_for_the_masked_local_body_alone(devices8):
    """A routed table dedups its own sender slice: the collection plans
    nothing for it, and the builder refuses a plan it is handed."""
    mesh = create_mesh(2, 2, devices8[:4])
    coll, states = _collection("array", mesh, "a2a")
    inputs = {"t": jnp.asarray(_ids("zipf", -1))}
    assert coll.plan(inputs) == {}
    one = create_mesh(1, 1, devices8[:1])
    plan = _collection("array", one, "a2a")[0].plan(inputs)["t"]
    with pytest.raises(ValueError, match="masked-local"):
        sharded.pull_sharded(states["t"], inputs["t"], mesh=mesh,
                             store=coll._stores["t"], plan=plan)


# --- the train step ----------------------------------------------------------

STEP_CHUNK = 256    # under a tiny_* step's 1,664 positions a table


def _three_steps(name, seed, planned, monkeypatch):
    """The state three ``Trainer.train_step``s leave on the rehearsal
    configuration ``name``; without ``planned`` the step's plan is empty,
    which makes it ``collection.pull`` and ``apply_gradients`` as they run
    without one."""
    from benchmark import offload_system, run as bench_run, system
    from benchmark.traffic_gen import zipf_train

    config = bench_run.load("configs", name)
    lib = offload_system if "offload" in name else system
    traffic = dict(bench_run.load("traffic", "train_zipf"), pool_batches=4)
    built = lib.build(config)
    if not planned:
        monkeypatch.setattr(built.coll, "plan", lambda *a, **kw: {})
    state = lib.initial_state(built, seed, on_device=False)
    pool = [system.program_batch(built, raw)
            for raw in zipf_train.make(traffic, config, seed)]
    losses = []
    for t in range(3):
        state, metrics = built.trainer.train_step(state, pool[t],
                                                  next_batch=pool[t + 1])
        losses.append(float(metrics["loss"]))
    jax.effects_barrier()
    out = jax.device_get((state.params, state.opt_state, state.emb))
    if lib is offload_system:
        lib.flush(built, state)
        out = out, lib.store_rows(built, pool[:3])
    return losses, out


@pytest.mark.parametrize("name", ["tiny_array", "tiny_hash", "tiny_offload"])
def test_three_train_steps_leave_the_state_they_left(name, monkeypatch):
    """At the rehearsal shapes of the array, hash and offload cells, under
    ``record_stats``: the same losses, dense state, tables (and host store)
    bit for bit, and the counters of a pull that has a plan."""
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", STEP_CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", STEP_CHUNK)
    for program in PROGRAMS:
        program.cache_clear()
    observability.set_evaluate_performance(True)
    try:
        want = _three_steps(name, 3300000007, False, monkeypatch)
        assert "pull_positions" not in observability.GLOBAL.snapshot()
        got = _three_steps(name, 3300000007, True, monkeypatch)
        counted = observability.GLOBAL.snapshot()
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    assert got[0] == want[0]
    _same(got[1], want[1])
    live, walked, positions = (counted[k]["count"] for k in (
        "pull_keys_live", "pull_keys_walked", "pull_positions"))
    assert positions == 3 * 2 * 64 * 26          # steps, tables, batch, ids
    assert 0 < live <= walked <= positions
    assert walked % STEP_CHUNK == 0 and walked < positions
