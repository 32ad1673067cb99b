"""One dedup a table a step (``dedup.Plan``): the masked-local pull resolves
each distinct key once and expands, the push takes the same plan. The
routed body has the step's plan too (``alltoall.RoutedPlan``): one dedup
and one bucketing at the sender, the keys sent once, one dedup and one row
read a key at the owner.

With a plan or without, a pull returns the same rows and a push leaves the
same table, bit for bit: on one shard, on a two-shard ``psum`` mesh and
routed over a 2x2 mesh, for array, int32-key and wide-key hash tables,
whatever the ids (padding, all one key, all distinct, the distinct keys
ending on a chunk boundary). Which planes have a plan, which refuse one,
and which planes there are.
"""

import re

import numpy as np
import pytest

import jax.numpy as jnp

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu.parallel import alltoall as a2a
from openembedding_tpu.parallel import sharded
from openembedding_tpu.parallel.mesh import create_mesh

from plan_helpers import (DIM, EMPTY, MESHES, N, VOCAB, _collection, _ids,
                          _mesh, _same, small_chunks)  # noqa: F401


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
    ("a2a", True), ("a2a+bf16", True),
    ("a2a+cache", False), ("a2a+grouped", False), ("a2a+int8", False),
    ("psum", False)])
def test_which_planes_have_a_plan_and_which_refuse_one(devices8, plane,
                                                       planned):
    """Over a 2x2 mesh. The plain exchange has a routed plan, whatever its
    wire. The cached plane (hits leave the
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


@pytest.mark.parametrize("plane", ["a2a+pipelined", "a2a+grouped+pipelined",
                                   "a2a+pipelined+bf16"])
def test_a_plane_there_is_not_is_refused_with_the_planes_there_are(
        devices8, plane):
    """A spec naming a plane that is not there (these three were once) is
    refused where the collection lays its tables out, and the message
    names every plane there is."""
    mesh = create_mesh(2, 2, devices8[:4])
    spec = EmbeddingSpec(name="t", input_dim=VOCAB, output_dim=DIM,
                         plane=plane)
    with pytest.raises(ValueError, match="unknown plane") as refused:
        EmbeddingCollection([spec], mesh)
    known = str(refused.value).split("known:")[1]
    assert re.findall(r"'([\w+]+)'", known) == [
        "a2a", "a2a+cache", "a2a+grouped", "psum"]
