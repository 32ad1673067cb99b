"""The stages of the step program carry their names where a device trace
and the compile cache read them.

Each stage runs as an inner jitted function named after it
(``analysis.scope.stage``). For the benchmark's rehearsal configurations
(array, hash, four virtual devices) the step is lowered and compiled on the
CPU, and every stage the configuration has must show (a) as a function
symbol of the lowered module printed without debug info, which is what the
persistent compile cache hashes, so a step that differs from an older one
only in its stage names never loads the older executable, and (b) in the
``op_name`` of the compiled HLO, which is what maps a trace's operations to
stages. A stage the configuration does not have must show in neither.
"""

import json
import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import stage_reduce, system as system_lib
from openembedding_tpu.analysis import programs
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.training import SAME_COLUMNS

CONFIGS = os.path.join(os.path.dirname(system_lib.__file__), "configs")
EVERYWHERE = {"dedup", "route", "resolve", "apply_gather", "apply_update",
              "apply_scatter", "dense_fwd", "dense_bwd", "dense_update"}
# one chip takes the masked-local body: nothing is bucketed and no push
# branches; its pull reads the distinct keys of the step's plan and expands
# them (``dedup.Plan``); its psum over one device is lowered and then
# compiled away, so ``exchange`` is a symbol only. Four devices route, with
# the step's plan too (``alltoall.RoutedPlan``): the same stage names, which
# is how the benchmark's readers find their stages
STAGES = {
    "tiny_array": (EVERYWHERE | {"exchange", "expand"},
                   EVERYWHERE | {"expand"}),
    "tiny_hash": (EVERYWHERE | {"exchange", "expand", "probe", "init_rows"},
                  EVERYWHERE | {"expand", "probe", "init_rows"}),
    "tiny_array_x4": (EVERYWHERE | {"exchange", "expand", "push_routed",
                                    "push_spilled"},) * 2,
}


def _symbols(lowered_text):
    """Function symbols of a lowered module; ``dedup_3`` is a ``dedup``."""
    return {re.sub(r"_\d+$", "", name) for name in
            re.findall(r"func\.func \w+ @([\w.]+)", lowered_text)}


def _path_parts(hlo_text):
    return {part for path in
            stage_reduce.trace_reduce.scope_names(hlo_text).values()
            for part in path.split("/")}


def _lower_step(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        config = json.load(f)
    system = system_lib.build(config)
    rows = config["batch"]
    batch = system_lib.program_batch(system, {
        "ids": np.zeros((rows, config["sparse_features"]), np.int64),
        "label": np.zeros((rows,), np.float32),
        "dense": np.zeros((rows, config["dense_features"]), np.float32)})
    same = system.coll.same_columns(batch["sparse"])
    state = jax.eval_shape(system.trainer.init, jax.random.PRNGKey(0), batch)
    replicated = NamedSharding(system.mesh, P())

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    state = state.replace(
        emb=placed(state.emb, system.coll.state_shardings()),
        **{k: placed(getattr(state, k), jax.tree.map(
            lambda _: replicated, getattr(state, k)))
           for k in ("step", "params", "opt_state")})
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=system.by_batch), batch)
    # the program the cell's steps run: the mapper hands the table and its
    # ``:linear`` twin one array, which shapes alone do not say
    assert len(same.twins) == 1
    return system.trainer.lower_train_step(state,
                                           {**batch, SAME_COLUMNS: same})


@pytest.mark.parametrize("name", sorted(STAGES))
def test_stage_names_reach_the_cache_key_and_the_hlo(name):
    lowered = _lower_step(name)
    symbols, compiled = STAGES[name]
    absent = set(stage_reduce.STAGES) - symbols
    got = _symbols(lowered.as_text())        # no debug info: the cache's view
    assert symbols <= got, sorted(symbols - got)
    assert not absent & got, sorted(absent & got)
    hlo = lowered.compile().as_text()
    parts = _path_parts(hlo)
    assert compiled <= parts, sorted(compiled - parts)
    assert not absent & parts, sorted(absent & parts)
    named = stage_reduce.instruction_stages(hlo)
    assert compiled <= set(named.values())


def test_a_push_that_cannot_spill_has_no_spilled_branch(devices8):
    """Buckets that hold a whole slice (``cap >= m``) leave no ``cond``:
    the routed branch is the program."""
    hlo, _ = programs.lower_push(create_mesh(2, 2, devices8[:4]), "a2a",
                                 vocab=1 << 10, dim=8, batch=64)
    parts = _path_parts(hlo)
    assert "push_routed" in parts and "push_spilled" not in parts
