"""One plan a distinct id column: columns that differ keep a plan each,
a plan is shared in one key form alone, which columns are the same is seen
on the host, and at the rehearsal shapes one plan a step leaves what a plan
a table leaves, bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu import table as table_lib
from openembedding_tpu.embedding import SameColumns
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.utils import observability

from plan_helpers import (DIM, N, PROGRAMS, STEP_CHUNK, STEPS, VOCAB,
                          _collection, _counts, _fused_steps, _ids, _no_plan,
                          _same, _three_steps)


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


