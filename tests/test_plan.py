"""One dedup a table a step (``dedup.Plan``): the masked-local pull resolves
each distinct key once and expands, the push takes the same plan. The
routed body has the step's plan too (``alltoall.RoutedPlan``): one dedup
and one bucketing at the sender, the keys sent once, one dedup and one row
read a key at the owner.

With a plan or without, a pull returns the same rows and a push leaves the
same table, bit for bit: on one shard, on a two-shard ``psum`` mesh and
routed over a 2x2 mesh, for array, int32-key and wide-key hash tables,
whatever the ids (padding, all one key, all distinct, the distinct keys
ending on a chunk boundary), buckets that hold a step's keys or not, and a
hash key never pushed reads its init row. ``Trainer.train_step`` builds
the plan; three steps of it leave the state three steps without leave.

One plan a distinct id column: tables fed the same ids (a fused table and its
``:linear`` twin: the same host array, or an equal copy) run ONE plan a step,
which leaves what a plan each leaves, bit for bit, routed too; columns that
differ keep a plan each; the cached, grouped and pipelined planes and an
``int8_ef`` push have none and lower to the text they had before the routed
plan came; and ``lower_train_step`` lowers the program the steps ran.

What the pull resolved goes to the push (``dedup.Resolution``): the slot and
the weight row of every distinct key. Steps whose push takes them leave the
tables, key arrays, accumulators and failure counts of steps whose push
resolves again, bit for bit, and the planned push's program holds no find
and half the apply's gathers.
"""

import re

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
from openembedding_tpu.parallel import alltoall as a2a
from openembedding_tpu.parallel import sharded
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.training import SAME_COLUMNS
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


@pytest.mark.parametrize("case", ["padding", "all_duplicate", "all_distinct",
                                  "chunk_boundary", "zipf"])
@MESHES
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_plan_changes_no_row_and_no_update(devices8, small_chunks, kind,
                                           data, model, plane, case):
    mesh = _mesh(devices8, data, model)
    coll, states = _collection(kind, mesh, plane)
    routed = coll.sharding_spec("t").routes
    assert routed == (data * model == 4)
    inputs = {"t": jnp.asarray(_ids(case, -1 if kind == "array" else EMPTY))}
    asked = np.asarray(inputs["t"]) != (-1 if kind == "array" else EMPTY)
    grads = {"t": jnp.asarray(
        np.random.RandomState(12).randn(N // 4, 4, DIM), jnp.float32)}

    plan = coll.plan(inputs)
    assert set(plan) == {"t"}
    if routed:
        # every key a table can hold reaches one owner, which holds it
        # once however many senders asked for it; nothing spills at
        # this size (a bucket holds a slice)
        assert isinstance(plan["t"], a2a.RoutedPlan)
        distinct = len(set(np.asarray(inputs["t"])[asked].tolist()))
        assert int(np.asarray(plan["t"].owner.valid).sum()) == distinct
        assert int(np.asarray(plan["t"].ok).sum()) >= distinct
        assert int(plan["t"].spilled) == 0
    else:
        # the plan is of the keys as they come: a hash table's padding is
        # the fill, an array's -1 a key like another until its store masks
        # it
        planned = np.asarray(inputs["t"])[asked | (kind == "array")]
        assert int(np.asarray(plan["t"].valid).sum()) == \
            len(set(planned.tolist()))

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


@pytest.mark.parametrize("plane,planned", [
    ("a2a", True), ("a2a+bf16", True), ("a2a+pipelined", True),
    ("a2a+cache", False), ("a2a+grouped", False), ("a2a+int8", False),
    ("psum", False)])
def test_which_planes_have_a_plan_and_which_refuse_one(devices8, plane,
                                                       planned):
    """Over a 2x2 mesh. The plain exchange has a routed plan, whatever its
    wire; so have the per-table programs of the pipelined plane (its
    schedule builds none: ``Trainer``). The cached plane (hits leave the
    keys between pull and push), the grouped one (its own exchange), an
    ``int8_ef`` push (its residual is positional in its own sender buffer)
    and ``psum`` over a data axis (the push gathers other devices' slices)
    have none: the collection plans nothing for them, and the builder
    refuses a plan it is handed."""
    mesh = create_mesh(2, 2, devices8[:4])
    coll, states = _collection("array", mesh, plane)
    inputs = {"t": jnp.asarray(_ids("zipf", -1))}
    spec, store = coll.sharding_spec("t"), coll._stores["t"]
    assert sharded.shares_plan(spec, mesh, True) == planned
    plan = coll.plan(inputs)
    if planned:
        assert isinstance(plan["t"], a2a.RoutedPlan)
        rows, resolved = sharded.pull_sharded(
            states["t"], inputs["t"], mesh=mesh, store=store, plan=plan["t"])
        np.testing.assert_array_equal(
            rows, sharded.pull_sharded(states["t"], inputs["t"], mesh=mesh,
                                       store=store))
        assert resolved.rows.shape[0] == plan["t"].owner.uniq.shape[0]
        return
    assert plan == {}
    with pytest.raises(ValueError, match="has no plan"):
        sharded.plan_sharded(inputs["t"], mesh=mesh, store=store)
    other = _collection("array", mesh, "a2a")[0].plan(inputs)["t"]
    with pytest.raises(ValueError, match="has no plan"):
        sharded.pull_sharded(states["t"], inputs["t"], mesh=mesh,
                             store=store, plan=other)


# --- the push takes what the pull resolved ----------------------------------

def _same_bits(got, want):
    """Bit for bit: ``-0.0`` is not ``0.0``."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _fresh_batches(kind, steps, per_step=N):
    """``steps`` batches whose keys are half the batch before's, half never
    seen: every push finds keys and inserts keys."""
    rng = np.random.RandomState(31)
    space = VOCAB if kind == "array" else 1 << 20
    ids = rng.randint(0, space, size=per_step)
    for _ in range(steps):
        ids = np.where(rng.rand(per_step) < 0.5, ids,
                       rng.randint(0, space, size=per_step))
        ids[:3] = -1 if kind == "array" else EMPTY      # and some padding
        grads = rng.randn(per_step // 4, 4, DIM).astype(np.float32)
        yield ({"t": jnp.asarray(ids.astype(np.int32).reshape(-1, 4))},
               {"t": jnp.asarray(grads)})


def _pushed(coll, states, batches, how):
    """The states after each batch's pull and push: ``"carried"`` hands the
    push what the pull resolved, ``"planned"`` the plan alone, ``"alone"``
    nothing."""
    out = []
    for inputs, grads in batches:
        plan = coll.plan(inputs) if how != "alone" else None
        rows, resolved = coll.pull_resolved(states, inputs, plan=plan)
        assert set(resolved) == (set() if plan is None else {"t"})
        states = coll.apply_gradients(
            states, inputs, grads, plan=plan,
            resolved=resolved if how == "carried" else None)
        out.append((rows, states))
    return out


@pytest.mark.parametrize("chunks", ["one_chunk", "several_chunks"])
@MESHES
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_the_push_takes_what_the_pull_resolved(devices8, request, kind,
                                               data, model, plane, chunks):
    """Fresh keys every step, a buffer of one chunk or of several: four
    steps whose push takes the pull's slots and rows leave, step by step,
    the state of steps whose push has the plan alone and of steps without a
    plan. The resolution is each shard's own: rows of the plan's length a
    shard (routed: of the bucket slots an owner received), a hash table's
    slots beside them."""
    if chunks == "several_chunks":
        request.getfixturevalue("small_chunks")
    mesh = _mesh(devices8, data, model)
    coll, states = _collection(kind, mesh, plane)
    batches = list(_fresh_batches(kind, 4))
    want = _pushed(coll, states, batches, "alone")
    for how in ("planned", "carried"):
        _same_bits(_pushed(coll, states, batches, how), want)
    inputs, _ = batches[0]
    plan = coll.plan(inputs)
    resolved = coll.pull_resolved(states, inputs, plan=plan)[1]["t"]
    # a slice of N / 4 keys fits its bucket: 4 buckets of it an owner
    slots = 4 * N if data * model == 4 else model * N
    assert resolved.rows.shape == (slots, DIM)
    if kind == "array":
        assert resolved.slot is None
    else:       # nothing is in the table before the first push
        assert resolved.slot.shape == (slots,)
        assert (np.asarray(resolved.slot) == -1).all()
    if kind != "array":
        final = want[-1][1]["t"]
        assert int(final.insert_failures) == 0
        assert int(final.num_used()) > N    # keys were inserted, step by step


def _spilling_batches(kind):
    """Six batches over a 2x2 mesh whose buckets hold two keys: fresh keys
    (a slice of ten has more than two for some owner), one key alone (no
    bucket is passed), fresh keys again."""
    fresh = list(_fresh_batches(kind, 5))
    inputs, grads = fresh[2]
    fresh.insert(2, ({"t": jnp.full_like(inputs["t"], 17)}, grads))
    return fresh


@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_a_step_the_buckets_do_not_hold_runs_as_it_does_without(
        devices8, small_chunks, kind):
    """``a2a_capacity`` 2 over a 2x2 mesh: round 1 is the plan's, the keys
    it leaves go through the pull's residue rounds and the push takes its
    gathered branch, both under the plan and both as without one: rows,
    tables, key arrays, accumulators and failure counts bit for bit, step
    by step, whether the push has what the pull resolved, the plan alone
    or nothing; a step that fits (one key) takes the plan's way in between.
    ``a2a_extra_entries_*`` count the same keys with a plan as without."""
    mesh = create_mesh(2, 2, devices8[:4])
    coll, states = _collection(kind, mesh, "a2a", a2a_capacity=2)
    batches = _spilling_batches(kind)
    spilled = [int(coll.plan(inputs)["t"].spilled) for inputs, _ in batches]
    assert spilled[2] == 0 and all(n > 0 for n in spilled[:2] + spilled[3:])
    counted = {}
    observability.set_evaluate_performance(True)
    try:
        for how in ("alone", "planned", "carried"):
            for program in PROGRAMS:
                program.cache_clear()
            observability.GLOBAL.reset()
            counted[how] = _pushed(coll, states, batches, how), {
                k: int(v["count"])
                for k, v in observability.GLOBAL.snapshot().items()
                if k.startswith(("a2a_extra_entries", "push_rows_carried"))}
            jax.effects_barrier()
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    want, extra = counted["alone"]
    assert extra == {"a2a_extra_entries_pull": sum(spilled),
                     "a2a_extra_entries_push": sum(spilled)}
    for how in ("planned", "carried"):
        _same_bits(counted[how][0], want)
        assert {k: v for k, v in counted[how][1].items()
                if k.startswith("a2a")} == extra
    # the apply took its rows from the pull in every step: the owner's
    # read where round 1 held the step, the gathered branch's own elsewhere
    assert counted["carried"][1]["push_rows_carried"] > 0
    assert "push_rows_carried" not in counted["planned"][1]


@MESHES
@pytest.mark.parametrize("kind", ["hash32", "hashwide"])
def test_a_key_no_window_holds_fails_the_same(devices8, small_chunks, kind,
                                              data, model, plane):
    """A table too full for its keys: a carried slot of -1 runs the insert
    that fails as it fails without, and the failures are counted the
    same."""
    mesh = _mesh(devices8, data, model)
    spec = EmbeddingSpec(
        name="t", input_dim=-1, output_dim=DIM,
        hash_capacity=64 * model,
        plane=plane, key_dtype="int32" if kind == "hash32" else "wide",
        optimizer={"category": "adagrad", "learning_rate": 0.1})
    coll = EmbeddingCollection([spec], mesh)
    states = coll.init(jax.random.PRNGKey(5))
    batches = list(_fresh_batches(kind, 6 * data))
    want = _pushed(coll, states, batches, "alone")
    assert int(want[-1][1]["t"].insert_failures) > 0
    for how in ("planned", "carried"):
        _same_bits(_pushed(coll, states, batches, how), want)


@MESHES
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_a_negative_zero_weight_keeps_its_sign(devices8, small_chunks, kind,
                                               data, model, plane):
    """The carried row is the shard's own read, before the sum over the
    model axis (routed: the owner's, before the response crosses the wire
    and is added into the sender's buffer): ``-0.0 + 0.0`` is ``0.0``, and
    a weight that a zero gradient leaves alone would lose its sign on the
    way to the push."""
    mesh = _mesh(devices8, data, model)
    coll, states = _collection(kind, mesh, plane)
    (inputs, grads), = _fresh_batches(kind, 1)
    grads = {"t": grads["t"].at[..., 0].set(0.0)}
    states = coll.apply_gradients(states, inputs, grads)    # the keys are in
    table = states["t"]
    states = {"t": table.replace(
        weights=table.weights.at[:, 0].set(-0.0))}
    batches = [(inputs, grads)] * 2
    want = _pushed(coll, states, batches, "alone")
    column = np.asarray(want[-1][1]["t"].weights)[:, 0]
    assert np.signbit(column).all() and not column.any()
    for how in ("planned", "carried"):
        _same_bits(_pushed(coll, states, batches, how), want)


# --- the train step ----------------------------------------------------------

STEP_CHUNK = 256    # under a tiny_* step's 1,664 positions a table


def _three_steps(name, seed, planned, monkeypatch):
    """The state three ``Trainer.train_step``s leave on the rehearsal
    configuration ``name``; without ``planned`` the step's plan is empty,
    which makes it ``collection.pull`` and ``apply_gradients`` as they run
    without one; ``planned="each"`` hides from the step that its two
    tables are fed one array, which makes it a plan a table;
    ``planned="resolve_again"`` keeps from the push what the pull resolved,
    which makes it the push that has the plan alone."""
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
        state, metrics = built.trainer.train_step(state, pool[t],
                                                  next_batch=pool[t + 1])
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
        observability.GLOBAL.reset()
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
    # the mapper hands both tables one array: one plan a step for the two
    assert _counts(counted) == {"plan_columns_same_object": 3,
                                "dedup_plans_built": 3,
                                "dedup_plan_tables": 6}


CARRY_COUNTERS = ("pull_keys_live", "push_slots_carried", "push_rows_carried",
                  "hash_find_slots_live", "hash_find_slots_walked",
                  "apply_slots_live")


@pytest.mark.parametrize("name", ["tiny_array", "tiny_hash", "tiny_offload"])
def test_a_step_resolves_a_key_once_and_counts_it(name, monkeypatch):
    """At the rehearsal shapes, under ``record_stats``: three steps whose
    push takes the pull's resolution leave the losses, dense state, tables
    (and host store) of three whose push resolves again, bit for bit. The
    push took a slot and a row for every live distinct key the pull
    resolved; the push that resolves again counts nothing carried, and
    its finds walk the pushes' keys on top of what the table fills (and
    the offload tier's inserts) walk in both."""
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", STEP_CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", STEP_CHUNK)
    counted = {}
    observability.set_evaluate_performance(True)
    try:
        for how in ("resolve_again", True):
            for program in PROGRAMS:
                program.cache_clear()
            observability.GLOBAL.reset()
            counted[how] = _three_steps(name, 3700000003, how, monkeypatch), {
                k: int(v["count"])
                for k, v in observability.GLOBAL.snapshot().items()
                if k in CARRY_COUNTERS}
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    (want, again), (got, took) = counted["resolve_again"], counted[True]
    assert got[0] == want[0]
    _same_bits(got[1], want[1])
    live = took["pull_keys_live"]
    assert live > 0 and again["pull_keys_live"] == live
    assert took["push_rows_carried"] == took["apply_slots_live"] == live
    assert not {"push_rows_carried", "push_slots_carried"} & set(again)
    if name == "tiny_array":
        assert "push_slots_carried" not in took
    else:
        assert took["push_slots_carried"] == live
        assert took["hash_find_slots_live"] == again["hash_find_slots_live"]
        pushes = again["hash_find_slots_walked"] - \
            took["hash_find_slots_walked"]
        assert pushes >= live and pushes % STEP_CHUNK == 0


# --- one plan a distinct id column ------------------------------------------

PLAN_COUNTERS = ("plan_columns_same_object", "plan_columns_compared_equal",
                 "plan_columns_differ", "dedup_plans_built",
                 "dedup_plan_tables")
FEATURES = ("c0", "c1", "c2", "c3")
STEPS, BATCH = 3, 32


def _counts(snapshot):
    return {k: int(snapshot[k]["count"]) for k in PLAN_COUNTERS
            if k in snapshot}


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


def _a_plan_each(coll):
    """What the step ran before tables shared a plan: nothing is seen to
    be the same."""
    coll.same_columns = lambda inputs: SameColumns()


def _no_plan(coll):
    coll.plan = lambda *a, **kw: {}


@pytest.mark.parametrize("twin,seen", [
    ("same", "plan_columns_same_object"),
    ("copy", "plan_columns_compared_equal")])
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "a2a2x2"])
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_tables_fed_one_column_share_one_plan(kind, twin, seen, shape):
    """The same array or an equal copy: one plan a step for the two tables,
    and losses, dense state, tables and scores are what a plan each
    leaves, bit for bit; routed over a 2x2 mesh they are also what the
    step without any plan leaves (held for the mapper's own batch)."""
    want = _fused_steps(kind, twin, _a_plan_each, shape)
    assert want[2] == {"dedup_plans_built": 2 * STEPS,
                       "dedup_plan_tables": 2 * STEPS}
    got = _fused_steps(kind, twin, None, shape)
    assert got[2] == {seen: STEPS, "dedup_plans_built": STEPS,
                      "dedup_plan_tables": 2 * STEPS}
    assert got[0] == want[0]
    _same(got[1], want[1])
    if shape != (1, 1) and twin == "same":
        alone = _fused_steps(kind, twin, _no_plan, shape)
        assert alone[2] == {seen: STEPS}
        assert got[0] == alone[0]
        _same(got[1], alone[1])


@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_columns_that_differ_keep_a_plan_each(kind):
    """Nothing is bound: two plans a step, and the state the step leaves
    without any plan (``collection.pull`` and ``apply_gradients`` as they
    run alone)."""
    got = _fused_steps(kind, "differ")
    assert got[2] == {"plan_columns_differ": STEPS,
                      "dedup_plans_built": 2 * STEPS,
                      "dedup_plan_tables": 2 * STEPS}
    want = _fused_steps(kind, "differ", _no_plan)
    assert got[0] == want[0]
    _same(got[1], want[1])
    shared = _fused_steps(kind, "same")
    assert shared[0] != got[0]          # other ids do train another model


def test_a_plan_is_shared_in_one_key_form_alone(devices8):
    """One array handed to an array table, an int32-key and a wide-key
    hash table and a second array table: the two array tables share a
    plan, each hash table has its own (``sharded.plan_form``), and every
    table pulls what it pulls alone."""
    mesh = create_mesh(1, 1, devices8[:1])
    kinds = {"a": "array", "h": "hash32", "w": "hashwide", "b": "array"}
    specs = [EmbeddingSpec(
        name=name, input_dim=VOCAB if kind == "array" else -1,
        output_dim=DIM if name != "b" else 1, hash_capacity=1024,
        key_dtype={"array": None, "hash32": "int32",
                   "hashwide": "wide"}[kind]) for name, kind in kinds.items()]
    coll = EmbeddingCollection(specs, mesh)
    states = coll.init(jax.random.PRNGKey(5))
    ids = jnp.asarray(_ids("zipf", 0))
    inputs = {name: ids for name in kinds}
    assert coll.same_columns(inputs).twins == (
        ("h", "a"), ("w", "a"), ("b", "a"))
    plan = coll.plan(inputs)
    assert plan["a"] is plan["b"]
    assert len({id(p) for p in plan.values()}) == 3
    assert plan["w"].uniq.shape == (N, 2) and plan["h"].uniq.shape == (N,)
    _same(coll.pull(states, inputs, plan=plan), coll.pull(states, inputs))
    # a column of its own, equal or not, is a plan of its own
    apart = coll.plan(dict(inputs, b=jnp.asarray(_ids("zipf", 0))))
    assert apart["a"] is not apart["b"]
    _same(apart["a"], apart["b"])


def test_same_columns_are_seen_on_the_host_alone(devices8):
    """Device arrays are compared by identity, never read back; a plane
    that has no plan (the cached one) is left as it is, the routed one is
    observed as one chip is; the counters say what each column was."""
    one = create_mesh(1, 1, devices8[:1])
    coll, _ = _collection("array", one, "a2a")
    assert coll.same_columns({"t": np.zeros(4, np.int32)}).twins == ()
    specs = [EmbeddingSpec(name=n, input_dim=VOCAB, output_dim=DIM)
             for n in ("t", "u", "v")]
    ids = _ids("zipf", -1)
    cases = [
        ({"t": ids, "u": ids, "v": ids.copy()}, (("u", "t"), ("v", "t")),
         {"plan_columns_same_object": 1, "plan_columns_compared_equal": 1}),
        ({"t": ids, "u": ids + 1, "v": ids + 1}, (("v", "u"),),
         {"plan_columns_differ": 1, "plan_columns_compared_equal": 1}),
        ({"t": ids, "u": ids.astype(np.int64), "v": ids.reshape(-1)}, (),
         {"plan_columns_differ": 2}),
        ({"t": jnp.asarray(ids), "u": jnp.asarray(ids), "v": ids}, (),
         {"plan_columns_differ": 2}),
    ]
    for inputs, twins, counted in cases:
        observability.GLOBAL.reset()
        try:
            got = EmbeddingCollection(specs, one).same_columns(inputs)
            assert got.twins == twins
            assert _counts(observability.GLOBAL.snapshot()) == counted
        finally:
            observability.GLOBAL.reset()
        four = create_mesh(2, 2, devices8[:4])
        assert EmbeddingCollection(specs, four).same_columns(
            inputs).twins == twins
        assert EmbeddingCollection(
            [EmbeddingSpec(name=n, input_dim=VOCAB, output_dim=DIM,
                           plane="a2a+cache") for n in ("t", "u", "v")],
            four).same_columns(inputs).twins == ()
    bound = SameColumns((("u", "t"),)).bind({"t": 1, "u": 2, "v": 3})
    assert bound == {"t": 1, "u": 1, "v": 3}


@pytest.mark.parametrize("name", ["tiny_array", "tiny_hash", "tiny_offload"])
def test_one_plan_leaves_what_a_plan_each_leaves(name, monkeypatch):
    """At the rehearsal shapes of the array, hash and offload cells: three
    steps through one plan a step leave the losses, dense state, tables
    (and host store) that a plan a table leaves."""
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", STEP_CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", STEP_CHUNK)
    for program in PROGRAMS:
        program.cache_clear()
    observability.GLOBAL.reset()    # of what other tests' steps counted
    observability.set_evaluate_performance(True)
    try:
        want = _three_steps(name, 3500000011, "each", monkeypatch)
        each = _counts(observability.GLOBAL.snapshot())
        observability.GLOBAL.reset()
        got = _three_steps(name, 3500000011, True, monkeypatch)
        shared = _counts(observability.GLOBAL.snapshot())
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()
        for program in PROGRAMS:
            program.cache_clear()
    assert each == {"dedup_plans_built": 6, "dedup_plan_tables": 6}
    assert shared == {"plan_columns_same_object": 3, "dedup_plans_built": 3,
                      "dedup_plan_tables": 6}
    assert got[0] == want[0]
    _same(got[1], want[1])


# --- the program the steps ran ----------------------------------------------

def _tiny(name, seed=3500000021):
    from benchmark import run as bench_run, system
    from benchmark.traffic_gen import zipf_train

    config = bench_run.load("configs", name)
    built = system.build(config)
    traffic = dict(bench_run.load("traffic", "train_zipf"), pool_batches=2)
    pool = [system.program_batch(built, raw)
            for raw in zipf_train.make(traffic, config, seed)]
    return system, built, pool


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


def _plan_calls(lowered_text):
    return re.findall(r"call @(\w*plan_a2a)\w*\(", lowered_text)


@pytest.mark.parametrize("name,program", [("tiny_array", "plan_a2a"),
                                          ("tiny_hash", "hash_plan_a2a")])
def test_lower_train_step_lowers_the_program_the_steps_ran(name, program):
    """``step_hlo``'s path: shapes under both names cannot say that the
    two columns are one. Before any step the text is the two-plan
    program's; after a step of the mapper's batch it calls the plan's
    program once, and so it does for a batch that brings its own note;
    after a step whose columns differ it is the two-plan text again."""
    system, built, pool = _tiny(name)
    trainer = built.trainer
    abstract = _abstract_step(built, pool[0])
    before = trainer.lower_train_step(*abstract).as_text()
    assert _plan_calls(before) == [program] * 2
    noted = dict(abstract[1], **{SAME_COLUMNS: built.coll.same_columns(
        pool[0]["sparse"])})
    one = trainer.lower_train_step(abstract[0], noted).as_text()
    assert _plan_calls(one) == [program]

    state = system.initial_state(built, 3500000021, on_device=False)
    state, _ = trainer.train_step(state, pool[0])
    assert trainer.lower_train_step(*abstract).as_text() == one
    apart = dict(pool[1], sparse={
        k: np.roll(v, int(k.endswith(LINEAR_SUFFIX)), axis=0)
        for k, v in pool[1]["sparse"].items()})
    state, _ = trainer.train_step(state, apart)
    assert trainer.lower_train_step(*abstract).as_text() == before
    jax.block_until_ready(state)


@pytest.mark.parametrize("name", ["tiny_array", "tiny_hash"])
def test_the_planned_push_neither_finds_nor_gathers_weights(name,
                                                            monkeypatch):
    """The compiled one-plan step (CPU), chunks small enough for loops:
    beside the two insert loops a table (three ``while``: the compact one
    holds the loop over a level's trips), the push that resolves again
    holds a find loop under ``probe``, the push that takes the pull's
    resolution none; and its apply gathers the accumulator alone, half
    the gathers under ``apply_gather``. The pull's find and read are what
    they were."""
    from benchmark import stage_reduce, trace_reduce
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", STEP_CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", STEP_CHUNK)
    prefix = "hash_" if name == "tiny_hash" else ""

    def counts(patch):
        for program in PROGRAMS:
            program.cache_clear()
        _, built, pool = _tiny(name)
        if patch:
            patch(built.coll)
        state, batch = _abstract_step(built, pool[0])
        batch = dict(batch, **{SAME_COLUMNS: built.coll.same_columns(
            pool[0]["sparse"])})
        hlo = built.trainer.lower_train_step(state, batch).compile().as_text()
        paths = trace_reduce.scope_names(hlo)
        stages = stage_reduce.instruction_stages(hlo, paths)
        found = re.findall(
            r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\(.*?\)|\S+) (gather|while)\(",
            hlo, re.M)

        def count(op, stage, verb):
            return sum(o == op and stages.get(inst) == stage
                       and f"{prefix}{verb}_a2a" in paths.get(inst, "").split("/")
                       for inst, o in found)

        return {"push finds and inserts": count("while", "probe", "push"),
                "pull finds": count("while", "probe", "pull"),
                "pull reads": count("while", "resolve", "pull"),
                "apply gathers": count("gather", "apply_gather", "push")}

    try:
        again, took = counts(_resolve_again), counts(None)
    finally:
        for program in PROGRAMS:
            program.cache_clear()
    hashed = name == "tiny_hash"
    assert again == {"push finds and inserts": 8 * hashed,
                     "pull finds": 2 * hashed, "pull reads": 2,
                     "apply gathers": 4}
    assert took == dict(again, **{"push finds and inserts": 6 * hashed,
                                  "apply gathers": 2})


def test_the_routed_step_binds_the_twin_column_and_plans_once():
    """2x2: the step of the mapper's batch (one array under both names)
    sees the twin, reads both tables' ids from ONE parameter and calls the
    plan's program once; a batch whose columns are apart keeps both
    parameters and a plan each; ``lower_train_step`` lowers the program
    the last step ran."""
    system, built, pool = _tiny("tiny_array_x4")
    same = built.coll.same_columns(pool[0]["sparse"])
    assert same == SameColumns((("fields" + LINEAR_SUFFIX, "fields"),))
    plan = built.coll.plan(same.bind(pool[0]["sparse"]))
    assert plan["fields"] is plan["fields" + LINEAR_SUFFIX]
    assert isinstance(plan["fields"], a2a.RoutedPlan)
    abstract = _abstract_step(built, pool[0])

    def columns(text):
        main = re.search(r"func\.func public @main\((.*?)\) ->", text, re.S)
        return main[1].count("tensor<64x26xi32>")

    apart = built.trainer.lower_train_step(*abstract).as_text()
    assert _plan_calls(apart) == ["plan_a2a"] * 2 and columns(apart) == 2
    state = system.initial_state(built, 3500000021, on_device=False)
    state, _ = built.trainer.train_step(state, pool[0])
    jax.block_until_ready(state)
    one = built.trainer.lower_train_step(*abstract).as_text()
    assert _plan_calls(one) == ["plan_a2a"] and columns(one) == 1
    noted = dict(abstract[1], **{SAME_COLUMNS: same})
    assert built.trainer.lower_train_step(abstract[0], noted).as_text() == one


# sha256 of the step programs of the planes that take no plan, lowered on
# the CPU backend over a 2x2 mesh at the parent of PR 39 (85d4baa): the
# routed plan changes nothing of a program that is handed none
_UNPLANNED_TEXTS = {
    "a2a+cache":
    "d6676542729d6b1628f303d5924a305e9e2913f105a4e201b1f0efc8b71429cc",
    "a2a+grouped":
    "b2e717cebafc60e50069e862b3cfce015dd9ff726c2192f54c23e58c928adec8",
    "a2a+pipelined":
    "e0379a84535e585b165b41e08d1b7c33708e6bb00b287d9bb7bb1ceb03c684bd",
    "a2a+int8":
    "69653fedc2a0a74ad398a1dde71e1a3a1b4b1fa67bf53a7ac05a0b02f4dc92d6"}


@pytest.mark.parametrize("plane", sorted(_UNPLANNED_TEXTS))
def test_a_plane_without_a_plan_lowers_to_the_text_it_had(devices8, plane):
    """The cached, grouped and pipelined steps and the step of an
    ``int8_ef`` push over a 2x2 mesh: a DeepFM over two features and their
    ``:linear`` twins (``analysis.programs``' harness), fed one array a
    pair. No plan program is called, and the text is the parent's."""
    import hashlib
    mesh = create_mesh(2, 2, devices8[:4])
    features, vocab, batch = ("c0", "c1"), 4096, 64
    coll = EmbeddingCollection(
        deepctr.make_feature_specs(features, vocab, 8, plane=plane), mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adam(1e-2))
    rng = np.random.RandomState(0)
    data = {"label": rng.randint(0, 2, size=batch).astype(np.float32),
            "dense": rng.randn(batch, 4).astype(np.float32),
            "sparse": {f: rng.randint(0, vocab, size=batch).astype(np.int32)
                       for f in features}}
    for f in features:
        data["sparse"][f + deepctr.LINEAR_SUFFIX] = data["sparse"][f]
    # (the pipelined plane's per-table programs have a plan; it is the
    # schedule that builds none)
    assert coll.same_columns(data["sparse"]) == SameColumns() \
        or "pipelined" in plane
    placed = trainer.shard_batch(data)
    state = trainer.init(jax.random.PRNGKey(0), placed)
    if "pipelined" in plane:
        state = trainer._prime_pipeline(state, data)
        pull_inputs, _ = trainer._split_sparse(data["sparse"])
        text = trainer._build_pipelined_train_step().lower(
            state, placed, trainer.shard_batch(pull_inputs)).as_text()
    else:
        text = trainer.lower_train_step(state, placed).as_text()
    assert "plan_a2a" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _UNPLANNED_TEXTS[plane]
