"""The train step and the program it runs. ``Trainer.train_step`` builds
the plan: three steps of it leave the state three steps without leave, and
the state of three steps whose push resolves again; the next batch handed
to a step feeds the offload lookahead and leaves the state as it was.
``lower_train_step`` lowers the program the steps ran, the planned push's
program holds no find and half the apply's gathers, and the cached and
grouped planes and an ``int8_ef`` push, which have no plan, lower to the
text they had before the routed plan came.
"""

import re

import numpy as np
import pytest

import jax

import optax

from openembedding_tpu import EmbeddingCollection, Trainer
from openembedding_tpu import table as table_lib
from openembedding_tpu.embedding import SameColumns
from openembedding_tpu.fused import LINEAR_SUFFIX
from openembedding_tpu.models import deepctr
from openembedding_tpu.parallel import alltoall as a2a
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.training import SAME_COLUMNS
from openembedding_tpu.utils import observability

from plan_helpers import (PROGRAMS, STEP_CHUNK, _abstract_step, _counts,
                          _resolve_again, _same, _same_bits, _three_steps)


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


@pytest.mark.parametrize("name", ["tiny_array", "tiny_hash"])
def test_the_next_batch_leaves_the_state_it_was_handed(name, monkeypatch):
    """With no offload table the batch handed as ``next_batch`` feeds
    nothing: three steps that are handed it leave the losses, dense state
    and tables (array, wide-key hash) of three that are not, bit for
    bit."""
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", STEP_CHUNK)
    monkeypatch.setattr(table_lib, "FIND_CHUNK", STEP_CHUNK)
    for program in PROGRAMS:
        program.cache_clear()
    try:
        want = _three_steps(name, 3900000001, True, monkeypatch,
                            lookahead=False)
        got = _three_steps(name, 3900000001, True, monkeypatch)
    finally:
        for program in PROGRAMS:
            program.cache_clear()
    assert got[0] == want[0]
    _same_bits(got[1], want[1])


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


def _tiny(name, seed=3500000021):
    from benchmark import run as bench_run, system
    from benchmark.traffic_gen import zipf_train

    config = bench_run.load("configs", name)
    built = system.build(config)
    traffic = dict(bench_run.load("traffic", "train_zipf"), pool_batches=2)
    pool = [system.program_batch(built, raw)
            for raw in zipf_train.make(traffic, config, seed)]
    return system, built, pool


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
    "a2a+int8":
    "69653fedc2a0a74ad398a1dde71e1a3a1b4b1fa67bf53a7ac05a0b02f4dc92d6"}


@pytest.mark.parametrize("plane", sorted(_UNPLANNED_TEXTS))
def test_a_plane_without_a_plan_lowers_to_the_text_it_had(devices8, plane):
    """The cached and grouped steps and the step of an
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
    assert coll.same_columns(data["sparse"]) == SameColumns()
    placed = trainer.shard_batch(data)
    state = trainer.init(jax.random.PRNGKey(0), placed)
    text = trainer.lower_train_step(state, placed).as_text()
    assert "plan_a2a" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _UNPLANNED_TEXTS[plane]
