"""What the pull resolved goes to the push (``dedup.Resolution``): the slot
and the weight row of every distinct key. Steps whose push takes them leave
the tables, key arrays, accumulators and failure counts of steps whose push
has the plan alone and of steps without a plan, bit for bit: buckets that
hold a step's keys or not, a table too full for its keys, a weight of
``-0.0``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.utils import observability

from plan_helpers import (DIM, EMPTY, MESHES, N, PROGRAMS, VOCAB,
                          _collection, _mesh, _same_bits,
                          small_chunks)  # noqa: F401


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


