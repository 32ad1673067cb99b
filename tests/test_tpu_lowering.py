"""AOT compilation for TPU v5e from a host without one.

libtpu serves its compilers (XLA:TPU and Mosaic) for a described
topology even where no chip is attached, so what ``chip_smoke.py`` runs
on the chip is compiled here first: the DeepFM train step at full Criteo
width on 1x1 and 2x2, and each Pallas kernel at one shape its guard
admits and one it refuses (a ``ValueError`` before lowering, never a
``MosaicError`` out of the compiler).

``slow``, and never in a lane that runs in parallel: libtpu takes
``/tmp/libtpu_lockfile``, and a second process compiling at the same time
aborts.
"""

import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from benchmark import stage_reduce, trace_reduce
from openembedding_tpu import hash_table as hl
from openembedding_tpu.analysis import contracts
from openembedding_tpu.data import criteo
from openembedding_tpu.ops import pallas_gather as pg, pallas_hash as ph
from openembedding_tpu.parallel.mesh import DATA_AXIS, create_mesh
from openembedding_tpu.training import SAME_COLUMNS

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu says is the reason
        pytest.skip(f"libtpu yields no v5e:2x2 topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


# The benchmark's cells (benchmark/configs): 26 x 3 * 2**20 array rows a chip,
# 2**26 hash slots. chip_smoke's own sizes make arrays small enough (a key
# array of 32 MiB, a linear column of 104 MiB) for the compiler to stage
# them through fast memory in copies; the cells' are not.
ROWS_PER_FEATURE = 3 << 20
HASH_CAPACITY = 1 << 26


def _abstract_step(mesh, coll, trainer, mapper, rows):
    """(state, batch) of the step as shapes and shardings alone, and with
    them what a step sees in the mapper's host batch and shapes do not
    say: the table and its ``:linear`` twin are fed one array, so the
    program is the one-plan step, on one chip and routed over 2x2."""
    batch = mapper.fuse_batch(next(iter(criteo.synthetic_criteo(
        chip_smoke.BATCH, num_buckets=rows, num_batches=1))))
    same = coll.same_columns(batch["sparse"])
    assert len(same.twins) == 1
    state = jax.eval_shape(trainer.init, jax.random.PRNGKey(0), batch)
    repl = NamedSharding(mesh, P())
    state = state.replace(
        emb=_abstract(state.emb, coll.state_shardings()),
        **{k: _abstract(getattr(state, k),
                        jax.tree.map(lambda _: repl, getattr(state, k)))
           for k in ("step", "params", "opt_state")})
    by_batch = NamedSharding(mesh, P(DATA_AXIS))
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jax.dtypes.canonicalize_dtype(x.dtype),
            sharding=by_batch), batch)
    return state, {**batch, SAME_COLUMNS: same}


@functools.lru_cache(maxsize=None)      # several tests read each program
def _compile_deepfm_step(mesh, *, use_hash, key_dtype="wide"):
    """The step program of chip_smoke.py's training phases, from shapes
    alone."""
    rows = ROWS_PER_FEATURE * mesh.size
    coll, trainer, mapper = chip_smoke.build_deepfm(
        mesh, use_hash=use_hash, rows_per_feature=rows,
        hash_capacity=HASH_CAPACITY, key_dtype=key_dtype)
    return trainer.lower_train_step(
        *_abstract_step(mesh, coll, trainer, mapper, rows)).compile()


@pytest.mark.parametrize("use_hash", [False, True], ids=["array", "hash"])
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_deepfm_step_compiles_for_v5e(v5e, shape, use_hash):
    data, model = shape
    mesh = create_mesh(data, model, v5e[:data * model])
    compiled = _compile_deepfm_step(mesh, use_hash=use_hash)
    ops = contracts.summarize(compiled.as_text())
    if mesh.size == 1:
        assert not ops, f"one chip has no peer to exchange with: {ops}"
    else:
        assert "all-to-all" in ops, ops
    # two 82M-row tables + Adagrad slots (array) must fit the 16 GB chip
    assert compiled.memory_analysis().argument_size_in_bytes < 15 << 30


_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:\(.*?\)|\S+) "
                     r"(gather|scatter|sort|while|fusion)\(")


@pytest.mark.parametrize("use_hash", [False, True], ids=["array", "hash"])
def test_v5e_step_keeps_its_stage_names(v5e, use_hash):
    """What the chip's compiler leaves of the names: every gather, scatter,
    sort and while of the compiled step, and nine fusions in ten, belong to
    a stage (``stage_reduce.instruction_stages``: the stage in the
    instruction's own ``op_name`` as ``trace_reduce.scope_names`` reads it,
    else its caller's, else its operand's)."""
    mesh = create_mesh(1, 1, v5e[:1])
    hlo = _compile_deepfm_step(mesh, use_hash=use_hash).as_text()
    stages = stage_reduce.instruction_stages(hlo)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    assert {op for _, op in found} >= {"gather", "scatter", "sort", "fusion"}
    if use_hash:
        assert any(op == "while" and stages.get(inst) == "probe"
                   for inst, op in found)
    lost = [inst for inst, op in found
            if op != "fusion" and inst not in stages]
    assert not lost, lost
    fusions = [inst for inst, op in found if op == "fusion"]
    named = sum(inst in stages for inst in fusions)
    assert named >= 0.9 * len(fusions), (named, len(fusions))


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_v5e_hash_push_finds_then_inserts_in_place(v5e, shape):
    """A push that finds for itself holds three outermost loops under
    ``probe``: the find, the insert loop over the buffer of misses and the
    one over the whole call. Which of the two places keys is decided by
    what each is given, under no conditional of the probe's own: through
    one the chip's compiler copies the key array, and no copy of it is
    left, on one chip or on four. The loop over the buffer holds one loop
    more, the trips of a level, ``table.INSERT_CHUNK`` misses each: the
    key array goes through both where it is. On one chip the find is the
    pull's, over the distinct keys of the step's plan, one loop a table,
    and the push, which takes the slots it found (``dedup.Resolution``),
    holds the two insert loops alone. Behind the exchange the owner's one
    find-or-insert a table follows the push's conditional: its find walks
    the keys no pull resolved (three outermost loops a table)."""
    data, model = shape
    mesh = create_mesh(data, model, v5e[:data * model])
    hlo = _compile_deepfm_step(mesh, use_hash=True).as_text()
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    loops = [inst for inst, op in found
             if op == "while" and stages.get(inst) == "probe"]
    trips = [inst for inst in loops
             if paths[inst].endswith("/probe/while/body/while")]
    assert len(trips) == 2 and all(
        "hash_push_a2a" in paths[inst] for inst in trips), trips
    loops = [inst for inst in loops if inst not in trips]
    assert all(paths[inst].endswith("/probe/while") for inst in loops)
    pushing = [inst for inst in loops if "hash_push_a2a" in paths[inst]]
    assert not re.search(r'conditional\(.*op_name="[^"]*jit\(probe\)/cond',
                         hlo)
    if mesh.size > 1:
        assert len(pushing) == 6 and len(loops) == 8, loops     # two tables
    else:
        assert len(pushing) == 4 and len(loops) == 6, loops
    keys = f"s32[{HASH_CAPACITY // mesh.size},2]"
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if f"= {keys}" in line and " copy" in line]
    assert not copies, copies


def test_v5e_hash_finds_walk_chunks_in_place(v5e):
    """The find of each table, its pull's (the push takes what that found),
    is the loop under ``probe`` that neither sorts nor scatters: its trips
    gather a chunk of bucket rows, and nothing as large as the unique
    buffer's worth of them (26 x 4096 keys x 128 slots) is left in the
    program outside the insert loops, whose full-width one keeps it. The
    key array the finds read is copied nowhere, into a loop or out of
    it."""
    mesh = create_mesh(1, 1, v5e[:1])
    hlo = _compile_deepfm_step(mesh, use_hash=True).as_text()
    lines = hlo.splitlines()
    stages = stage_reduce.instruction_stages(hlo)
    _, comps = contracts.parse_hlo_computations(hlo)

    def reached(names):
        seen, todo = set(), list(names)
        while todo:
            name = todo.pop()
            if name not in seen and name in comps:
                seen.add(name)
                todo += [c for inst in comps[name] for c in inst.calls]
        return seen

    unique = chip_smoke.FEATURES * chip_smoke.BATCH
    chunk = hl.table_lib.FIND_CHUNK
    assert unique > chunk

    def makes(inst, rows):
        return re.search(rf"= \(?\w+\[{rows},{hl.BUCKET}\b",
                         lines[inst.line_no])

    finds, inserting = [], set()
    for inst in (i for body in comps.values() for i in body):
        if inst.opcode == "while" and stages.get(inst.name) == "probe":
            inside = reached(inst.calls)
            if {i.opcode for c in inside for i in comps[c]} & {"sort",
                                                               "scatter"}:
                inserting |= inside
            else:
                finds.append([i for c in inside for i in comps[c]])
    assert len(finds) == 2, len(finds)          # two tables, the pull's
    for body in finds:
        assert any(makes(i, chunk) for i in body)
        assert not [i.name for i in body if makes(i, unique)]
    left = [i.name for c, body in comps.items() if c not in inserting
            for i in body if makes(i, unique)]
    assert not left, left
    keys = f"s32[{HASH_CAPACITY},2]"
    copies = [line.strip()[:120] for line in lines
              if f"= {keys}" in line and " copy" in line]
    assert not copies, copies


@pytest.mark.parametrize("shape,use_hash", [((1, 1), False), ((1, 1), True),
                                            ((2, 2), False)],
                         ids=["1x1-array", "1x1-hash", "2x2-array"])
def test_v5e_apply_walks_its_buffer_in_place(v5e, shape, use_hash):
    """The sparse apply of each of the two tables is a loop over the chunks
    of the unique buffer, under an apply stage and under no conditional
    (on four chips the push's branches only merge; the apply follows
    them): a loop in a branch gets its table copied in. No copy of an
    array as long as a device's share of a table is left. The weight rows
    come with the step's plan from its pull (``dedup.Resolution``) and a
    trip gathers the accumulator alone; on four chips the push's gathered
    branch, which no pull resolved for, reads its weight rows itself, in
    one pass a table."""
    data, model = shape
    mesh = create_mesh(data, model, v5e[:data * model])
    hlo = _compile_deepfm_step(mesh, use_hash=use_hash).as_text()
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    loops = [inst for inst, op in found if op == "while"
             and stages.get(inst, "").startswith("apply_")]
    assert len(loops) == 2, loops
    assert not [inst for inst in loops if "/cond/" in paths.get(inst, "")]
    gathers = [inst for inst, op in found
               if op == "gather" and stages.get(inst) == "apply_gather"]
    branch = [inst for inst in gathers if "/cond/" in paths[inst]]
    assert len(gathers) - len(branch) == 2, gathers
    assert len(branch) == (0 if mesh.size == 1 else 2), branch
    assert all("push_spilled" in paths[inst] for inst in branch), branch
    rows = (HASH_CAPACITY // mesh.size if use_hash
            else chip_smoke.FEATURES * ROWS_PER_FEATURE)
    copied = [line.strip()[:120] for line in hlo.splitlines()
              if (" copy(" in line or " copy-start(" in line)
              and int((re.search(r"= \(?\w+\[(\d+)", line) or [0, 0])[1])
              >= rows]
    assert not copied, copied


@pytest.mark.parametrize("use_hash", [False, True], ids=["array", "hash"])
def test_v5e_one_chip_step_dedups_once_and_pulls_distinct_keys(v5e, use_hash):
    """The step's plan (``dedup.Plan``) is built once a distinct id column,
    for the pull and push of both tables that read it: the one-chip step
    sorts once (a ``lexsort`` for ids, an ``argsort`` a key word for wide
    keys), under the plan's program. Each table's pull reads the distinct
    keys' rows in a loop of chunk-sized gathers under ``resolve`` and
    hands every position its row in one ``expand`` gather; no gather of
    the pull is as long as the batch's positions but that one, and none of
    it sits under a conditional."""
    mesh = create_mesh(1, 1, v5e[:1])
    hlo = _compile_deepfm_step(mesh, use_hash=use_hash).as_text()
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    sorts = [paths[inst] for inst, op in found
             if op == "sort" and stages.get(inst) == "dedup"
             and ("argsort" if use_hash else "lexsort") in paths[inst]]
    assert len(sorts) == (2 if use_hash else 1), sorts
    assert all("plan_a2a" in path for path in sorts), sorts

    pull = "hash_pull_a2a" if use_hash else "pull_a2a"
    pulling = [(inst, op) for inst, op in found
               if pull in paths.get(inst, "").split("/")]
    assert not [inst for inst, _ in pulling if "/cond/" in paths[inst]]
    reads = [inst for inst, op in pulling
             if op == "while" and stages.get(inst) == "resolve"]
    assert len(reads) == 2, reads                       # two tables
    positions = chip_smoke.FEATURES * chip_smoke.BATCH
    chunk = hl.table_lib.APPLY_CHUNK
    lines = {m.group(1): line for line in hlo.splitlines()
             if (m := _OPCODE.match(line))}
    gathers = {inst: (stages.get(inst),
                      int(re.search(r"= \(?\w+\[(\d+)", lines[inst])[1]))
               for inst, op in pulling if op == "gather"}
    assert sorted(g for g in gathers.values() if g[1] >= positions) == \
        [("expand", positions)] * 2, gathers
    assert {g for g in gathers.values() if g[0] == "resolve"} == \
        {("resolve", chunk)}, gathers


def test_v5e_one_chip_push_follows_the_pull_and_copies_no_key_array(v5e):
    """int32 keys, the offload cell's caches (2**26 slots, 256 MiB of keys
    a table). The push's find and insert read the plan and the key array
    and nothing of the dense pass, so no operand orders them behind the
    pull's find, which reads the same array; free to order the two, the
    chip's compiler copied the array into the insert loop. The step ties
    plan and rows together after the pull (``Trainer._build_train_step``),
    and no copy of a key array is left."""
    mesh = create_mesh(1, 1, v5e[:1])
    hlo = _compile_deepfm_step(mesh, use_hash=True,
                               key_dtype="int32").as_text()
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if f"= s32[{HASH_CAPACITY}]" in line and " copy" in line]
    assert not copies, copies


@pytest.mark.parametrize("use_hash,key_dtype,sorts", [
    (False, "wide", 1), (True, "wide", 2), (True, "int32", 1)],
    ids=["array", "hash-wide", "hash-int32"])
def test_v5e_one_chip_step_builds_one_plan_for_both_tables(v5e, use_hash,
                                                           key_dtype, sorts):
    """The table and its ``:linear`` twin read one column, and the v5e step
    holds the plan's program once: the sorts of ONE dedup (a ``jnp.unique``
    of ids or int32 keys sorts once, ``unique_rows`` once a key word) lie
    under ``plan_a2a`` / ``hash_plan_a2a``, the program sorts nowhere else
    under ``dedup`` (the compiler may sort for a combine's scatter-add),
    and the pulls and pushes of both tables are there."""
    mesh = create_mesh(1, 1, v5e[:1])
    hlo = _compile_deepfm_step(mesh, use_hash=use_hash,
                               key_dtype=key_dtype).as_text()
    prefix = "hash_" if use_hash else ""
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    sorting = [paths[inst].split("/") for inst, op in found
               if op == "sort" and stages.get(inst) == "dedup"
               and paths[inst].endswith("/sort")]   # the program's own
    assert len(sorting) == sorts, sorting
    assert all(f"{prefix}plan_a2a" in path for path in sorting), sorting
    for verb, stage in (("pull", "resolve"), ("push", "apply_")):
        loops = [inst for inst, op in found if op == "while"
                 and f"{prefix}{verb}_a2a" in paths.get(inst, "").split("/")
                 and stages.get(inst, "").startswith(stage)]
        assert len(loops) == 2, (verb, loops)           # two tables


def test_v5e_routed_step_takes_the_steps_plan(v5e):
    """The planned 2x2 step at the x4 cell's size. The plan's program
    sorts once a distinct id column at the sender (its slice's dedup) and
    once at the owner (the bucket slots it received), beside the one
    bucketing; pull and push of both tables dedup nothing outside the
    push's gathered branch (the compiler may sort for a combine's
    scatter-add) and bucket nothing outside it and the pull's residue
    loop. Each table's owner reads the distinct keys' rows in a loop of
    chunk-sized gathers under ``resolve``; the push gathers no weight row
    outside the gathered branch (its apply gathers the accumulator alone),
    and no array as long as a device's share of a table is copied, inside
    the push's conditional or out of it."""
    mesh = create_mesh(2, 2, v5e[:4])
    hlo = _compile_deepfm_step(mesh, use_hash=False).as_text()
    paths = trace_reduce.scope_names(hlo)
    stages = stage_reduce.instruction_stages(hlo, paths)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    lines = {m.group(1): line for line in hlo.splitlines()
             if (m := _OPCODE.match(line))}

    def length(inst):
        return int(re.search(r"= \(?\w+\[(\d+)", lines[inst])[1])

    def under(inst, name):
        return name in paths.get(inst, "").split("/")

    slice_keys = chip_smoke.FEATURES * chip_smoke.BATCH // mesh.size
    planning = sorted((stages.get(inst), length(inst)) for inst, op in found
                      if op == "sort" and under(inst, "plan_a2a")
                      and paths[inst].endswith("/sort"))  # the program's own
    received = planning[1][1]       # four buckets of twice the mean
    assert received == 2 * slice_keys
    assert planning == [("dedup", slice_keys), ("dedup", received),
                        ("route", slice_keys)], planning
    # what is left of sorts under pull and push: the residue loop's
    # bucketing, the gathered branch, a combine's scatter-add
    for inst, op in found:
        if op != "sort" or stages.get(inst) not in ("dedup", "route"):
            continue
        if under(inst, "pull_a2a"):
            assert "/while/" in paths[inst], paths[inst]
        elif under(inst, "push_a2a") and "push_spilled" not in paths[inst]:
            assert not paths[inst].endswith("/sort"), paths[inst]
    reads = [inst for inst, op in found if op == "while"
             and under(inst, "pull_a2a") and stages.get(inst) == "resolve"]
    assert len(reads) == 2, reads                       # two tables
    chunk = hl.table_lib.APPLY_CHUNK
    assert chunk in {length(inst) for inst, op in found if op == "gather"
                     and stages.get(inst) == "resolve"
                     and under(inst, "pull_a2a")}
    weights = [inst for inst, op in found if op == "gather"
               and under(inst, "push_a2a")
               and stages.get(inst) == "apply_gather"
               and "push_spilled" not in paths[inst]]
    assert len(weights) == 2, weights       # the accumulators alone
    rows = chip_smoke.FEATURES * ROWS_PER_FEATURE
    copied = [line.strip()[:120] for line in hlo.splitlines()
              if (" copy(" in line or " copy-start(" in line)
              and int((re.search(r"= \(?\w+\[(\d+)", line) or [0, 0])[1])
              >= rows]
    assert not copied, copied


def _on(dev, shape, dtype):
    mesh = create_mesh(1, 1, [dev])
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def test_pallas_gather_admitted_and_refused(v5e):
    idx = _on(v5e[0], (4096,), jnp.int32)
    pg.gather_rows.trace(_on(v5e[0], (1 << 16, 128), jnp.float32),
                         idx).lower().compile()
    for dtype, dim in ((jnp.float32, 256), (jnp.bfloat16, 128)):
        with pytest.raises(ValueError, match="float32 rows of exactly 128"):
            pg.gather_rows.trace(_on(v5e[0], (1 << 16, dim), dtype), idx)


def test_pallas_probe_gather_admitted_and_refused(v5e):
    cap, n = 1 << 16, 4096
    bucket, _nb, chain = hl.table_layout(cap, hl.DEFAULT_MAX_PROBES)
    kw = dict(chain=chain, bucket=bucket, empty=int(hl.empty_key(jnp.int32)))
    keys = _on(v5e[0], (cap,), jnp.int32)
    q = _on(v5e[0], (n,), jnp.int32)
    ph.probe_gather.trace(keys, _on(v5e[0], (cap, 128), jnp.float32), q, q,
                          **kw).lower().compile()
    for dtype, dim in ((jnp.float32, 256), (jnp.bfloat16, 128)):
        with pytest.raises(ValueError, match="float32 rows of exactly 128"):
            ph.probe_gather.trace(keys, _on(v5e[0], (cap, dim), dtype), q, q,
                                  **kw)


@pytest.mark.parametrize("keys", [1 << 13, 1 << 21], ids=["step", "bulk"])
def test_v5e_offload_insert_updates_the_cache_in_place(v5e, keys):
    """The insert the offload tier runs between two steps (8,192 keys) and
    its bulk insert (2**21), at the offload cell's size: a cache of 2**26
    slots, dim 9 with its accumulator, 4.8 GiB of arguments on one chip.
    The table operands are donated and every output aliases its operand;
    no copy of a table-sized array is left, so the program fits beside the
    second table's cache, which the un-donated program's second copy of
    the table did not."""
    from openembedding_tpu import offload
    from openembedding_tpu.meta import EmbeddingVariableMeta
    from openembedding_tpu.parallel import sharded_hash as sh
    capacity, dim = 1 << 26, 9
    mesh = create_mesh(1, 1, v5e[:1])
    tier = offload.ShardedOffloadedTable(
        "fields", EmbeddingVariableMeta(embedding_dim=dim,
                                        vocabulary_size=1024),
        {"category": "adagrad"}, {"category": "constant", "value": 0.0},
        vocab=1024, cache_capacity=capacity, mesh=mesh)
    cache = jax.eval_shape(tier.create_cache)
    row = NamedSharding(mesh, tier.spec.row_spec())
    whole = NamedSharding(mesh, P())
    table = _abstract((cache.keys, cache.weights, cache.slots),
                      (row, row, {k: row for k in cache.slots}))
    _, columns, layout = tier._packed_layout(cache.keys.dtype)
    program = sh._insert_packed_program(mesh, tier.spec, dim, layout)
    compiled = program.lower(
        *table, _abstract(cache.insert_failures, whole),
        _abstract(cache.init_rng, whole),
        jax.ShapeDtypeStruct((keys, columns), jnp.float32,
                             sharding=whole)).compile()
    hlo = compiled.as_text()
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    assert sh.OFFLOAD_INSERT_STAGE in header
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         header)
    assert sorted(aliased) == [("0", "0"), ("1", "1"), ("2", "2")], header
    sized = (f"s32[{capacity}]", f"f32[{capacity},{dim}]")
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if " copy(" in line and any(f"= {s}" in line for s in sized)]
    assert not copies, copies
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= capacity * (4 + 2 * dim * 4)
    assert memory.temp_size_in_bytes < (5 << 30 if keys > 1 << 13
                                        else 1 << 30), memory


SNAPSHOT_ROWS = 6 << 19     # the staging length of the autosave cell's saves


def test_v5e_snapshot_gathers_rows_beside_the_table(v5e):
    """A delta save's snapshot at the autosave cell's size: the dim-9
    table and its accumulator (81.8M rows each, 9.8 GiB of arguments),
    3,145,728 staged rows. The program reads rows in trips of the apply's
    chunk under ``ckpt_gather``, donates nothing, and holds neither a copy
    nor a slice of a table array nor a temporary wider than its staging
    buffers: beside the tables it costs what it returns."""
    from openembedding_tpu import table as table_lib
    from openembedding_tpu.parallel import sharded_table as st
    mesh = create_mesh(1, 1, v5e[:1])
    coll, _, mapper = chip_smoke.build_deepfm(
        mesh, use_hash=False, rows_per_feature=ROWS_PER_FEATURE,
        hash_capacity=HASH_CAPACITY)
    spec = coll.sharding_spec(mapper.name)
    rows, dim = spec.padded_vocab, coll.specs[mapper.name].output_dim
    row = NamedSharding(mesh, spec.row_spec())
    whole = NamedSharding(mesh, P())
    table = [jax.ShapeDtypeStruct((rows, dim), jnp.float32, sharding=row)] * 2
    compiled = st._snapshot_program(mesh, spec, 2).lower(
        table, jax.ShapeDtypeStruct((SNAPSHOT_ROWS,), jnp.int32,
                                    sharding=whole),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
    hlo = compiled.as_text()
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    assert "ckpt_gather" in header and "alias" not in header, header
    paths = trace_reduce.scope_names(hlo)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    loops = [inst for inst, op in found if op == "while"]
    assert len(loops) == 1 and "ckpt_gather" in paths[loops[0]], loops
    chunk = table_lib.APPLY_CHUNK
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    assert gathers and all(f"f32[{chunk},{dim}]" in g for g in gathers)
    wide = [line.strip()[:120] for line in hlo.splitlines()
            if re.search(r" (copy|copy-start|slice|dynamic-slice)\(", line)
            and int((re.search(r"= \(?\w+\[(\d+)", line) or [0, 0])[1])
            > SNAPSHOT_ROWS]
    assert not wide, wide
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 9 << 30
    assert memory.temp_size_in_bytes <= memory.output_size_in_bytes


def test_v5e_snapshot_of_hash_keys_finds_and_gathers_beside_the_table(v5e):
    """A delta save's snapshot at the hash autosave cell's size: the dim-9
    wide-key table (2**26 slots: 512 MiB of keys, 4.5 GiB of rows and
    accumulators as the compiler lays them out), 3,145,728 staged keys.
    One program, named for its gather: a loop under ``ckpt_find`` that
    reads bucket rows a chunk of keys a trip, a loop under ``ckpt_gather``
    that reads rows a chunk a trip. It donates nothing, inserts nothing
    (no scatter into, no output of, the key array) and holds no copy or
    slice of the key array or of a table array: beside the tables it
    costs what it returns and the slots it found."""
    from openembedding_tpu import checkpoint_delta as cd
    from openembedding_tpu import table as table_lib
    from openembedding_tpu.parallel import sharded_hash as sh
    mesh = create_mesh(1, 1, v5e[:1])
    coll, _, mapper = chip_smoke.build_deepfm(
        mesh, use_hash=True, rows_per_feature=ROWS_PER_FEATURE,
        hash_capacity=HASH_CAPACITY)
    spec = coll.sharding_spec(mapper.name)
    dim = coll.specs[mapper.name].output_dim
    assert cd._staging_rows(2_870_000) == SNAPSHOT_ROWS
    row = NamedSharding(mesh, spec.row_spec())
    whole = NamedSharding(mesh, P())
    keys = jax.ShapeDtypeStruct((HASH_CAPACITY, 2), jnp.int32, sharding=row)
    table = [jax.ShapeDtypeStruct((HASH_CAPACITY, dim), jnp.float32,
                                  sharding=row)] * 2
    compiled = sh._snapshot_keys_program(mesh, spec, 2).lower(
        keys, table,
        jax.ShapeDtypeStruct((SNAPSHOT_ROWS, 2), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
    hlo = compiled.as_text()
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    assert "ckpt_gather" in header and "alias" not in header, header
    paths = trace_reduce.scope_names(hlo)
    found = [m.groups() for m in map(_OPCODE.match, hlo.splitlines()) if m]
    loops = [inst for inst, op in found if op == "while"]
    assert len(loops) == 2, loops
    assert sorted(("ckpt_find" in paths[loop].split("/"),
                   "ckpt_gather" in paths[loop].split("/"))
                  for loop in loops) == [(False, True), (True, False)]
    assert not [inst for inst, op in found if op == "scatter"]
    chunk = table_lib.APPLY_CHUNK
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    assert gathers and all(
        f"f32[{chunk},{dim}]" in g or f"s32[{chunk}," in g
        for g in gathers), gathers
    # neither the key array nor a table array is copied, sliced or made
    wide = [line.strip()[:120] for line in hlo.splitlines()
            if re.search(r" (copy|copy-start|slice|dynamic-slice|fusion)\(",
                         line)
            and int((re.search(r"= \(?\w+\[(\d+)", line) or [0, 0])[1])
            > SNAPSHOT_ROWS]
    assert not wide, wide
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > 4 << 30
    # the staged rows, and as much again at most: the found slots, the
    # padded keys, the loops' carries
    assert memory.temp_size_in_bytes <= 2 * memory.output_size_in_bytes


def test_step_lowers_the_same_with_dirty_tracking_armed(v5e):
    """The marks stay on the host: arming the tracking changes nothing of
    the step's program (the array cell's step is the autosave cell's)."""
    def lowered(armed):
        mesh = create_mesh(1, 1, v5e[:1])
        coll, trainer, mapper = chip_smoke.build_deepfm(
            mesh, use_hash=False, rows_per_feature=1 << 12,
            hash_capacity=1 << 12)
        if armed:
            coll.enable_dirty_tracking()
        return trainer.lower_train_step(*_abstract_step(
            mesh, coll, trainer, mapper, 1 << 12)).as_text()

    assert lowered(True) == lowered(False)


def _keyed_tiers(mesh, capacity=HASH_CAPACITY):
    """The keyed offload cell's two tiers (no ``vocab``: 64-bit keys)."""
    from openembedding_tpu import offload
    from openembedding_tpu.meta import EmbeddingVariableMeta
    return {name: offload.ShardedOffloadedTable(
        name, EmbeddingVariableMeta(embedding_dim=dim, vocabulary_size=-1),
        {"category": "adagrad"}, initializer, cache_capacity=capacity,
        mesh=mesh)
        for name, dim, initializer in (
            ("fields", 9, {"category": "normal", "stddev": 1e-4}),
            ("fields:linear", 1, {"category": "constant", "value": 0.0}))}


@pytest.mark.parametrize("keys", [1 << 13, 1 << 21], ids=["step", "bulk"])
def test_v5e_wide_key_offload_insert_updates_the_cache_in_place(v5e, keys):
    """The keyed tier's insert between two steps (8,192 keys) and its bulk
    insert (2**21) at the keyed offload cell's size: a wide-key cache of
    2**26 slots (512 MiB of keys), dim 9 with its accumulator. One int32
    buffer brings both key words and the rows; the table operands are
    donated and every output aliases its operand; the compiler copies no
    ``s32[2**26,2]`` key array and no table-sized row array (it copied the
    int32 key array twice before: PERF.md, PR 27 and PR 33); the module
    keeps the name the benchmark's reader finds it by."""
    from openembedding_tpu.parallel import sharded_hash as sh
    capacity, dim = HASH_CAPACITY, 9
    mesh = create_mesh(1, 1, v5e[:1])
    tier = _keyed_tiers(mesh)["fields"]
    assert tier.keyed and tier.spec.wide
    cache = jax.eval_shape(tier.create_cache)
    assert cache.keys.shape == (capacity, 2)
    row = NamedSharding(mesh, tier.spec.row_spec())
    whole = NamedSharding(mesh, P())
    table = _abstract((cache.keys, cache.weights, cache.slots),
                      (row, row, {k: row for k in cache.slots}))
    _, columns, layout = tier._packed_layout(cache.keys.dtype)
    assert columns == 2 + dim + dim
    program = sh._insert_packed_program(mesh, tier.spec, dim, layout)
    compiled = program.lower(
        *table, _abstract(cache.insert_failures, whole),
        _abstract(cache.init_rng, whole),
        jax.ShapeDtypeStruct((keys, columns), jnp.int32,
                             sharding=whole)).compile()
    hlo = compiled.as_text()
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    assert sh.OFFLOAD_INSERT_STAGE in header
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         header)
    assert sorted(aliased) == [("0", "0"), ("1", "1"), ("2", "2")], header
    sized = (f"s32[{capacity},2]", f"f32[{capacity},{dim}]")
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if " copy(" in line and any(f"= {s}" in line for s in sized)]
    assert not copies, copies
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= capacity * (8 + 2 * dim * 4)
    assert memory.temp_size_in_bytes < (5 << 30 if keys > 1 << 13
                                        else 1 << 30), memory


def test_v5e_keyed_offload_step_copies_no_key_array(v5e):
    """The keyed offload cell's step, built as the cell builds it (the
    tiers' own ``embedding_spec()`` s, ``Trainer(offload=)``): the hash
    cell's wide-key program, whose push follows the pull and inserts the
    keys no store has seen in place. No copy of a ``s32[2**26,2]`` key
    array, every table operand aliased, and the one-plan step."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.fused import FusedMapper
    from openembedding_tpu.models import deepctr
    mesh = create_mesh(1, 1, v5e[:1])
    tiers = _keyed_tiers(mesh)
    features = tuple(criteo.SPARSE_NAMES)
    coll = EmbeddingCollection(
        [t.embedding_spec() for t in tiers.values()], mesh)
    assert all(s.key_dtype == "wide" for s in coll.specs.values())
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adam(1e-3), offload=tiers)
    mapper = FusedMapper(features, (-1,) * len(features))
    compiled = trainer.lower_train_step(
        *_abstract_step(mesh, coll, trainer, mapper, 1 << 20)).compile()
    hlo = compiled.as_text()
    copies = [line.strip()[:120] for line in hlo.splitlines()
              if f"= s32[{HASH_CAPACITY},2]" in line and " copy" in line]
    assert not copies, copies
    header = next(line for line in hlo.splitlines()
                  if line.startswith("HloModule"))
    aliased = re.findall(r"\{[\d, ]*\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         header)
    # both tables' keys, weights and accumulators among the aliased
    assert len(aliased) >= 6, header
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * HASH_CAPACITY * 8
    assert memory.temp_size_in_bytes < 2 << 30, memory


# sha256 of programs lowered on the CPU backend at the parent of PR 38
# (a84d95b): a bounded tier's programs are the parent's, text for text
# (the tiny_* step programs were compared tree against tree: CHANGES.md)
_BOUNDED_TEXTS = {
    "offload_insert":
    "b3135e0a68c1ceb495b40894b1e789d7188830cdd120f5399c5209e4bddfef8c",
    "offload_read":
    "87359f73cc880fcb6f584f481cf9af2eeb9a59f1c0230383dc08b1af32c3bcb8"}


def test_bounded_tier_programs_lower_to_the_parents_text():
    """A tier built WITH a ``vocab`` keeps its arithmetic: its packed
    insert and its write-back read lower, on the CPU backend, to the text
    they had before the keyed tier came (PR 37's tree)."""
    import hashlib
    import numpy as np
    from openembedding_tpu import offload
    from openembedding_tpu.meta import EmbeddingVariableMeta
    from openembedding_tpu.parallel import sharded_hash as sh
    mesh = create_mesh(1, 1, jax.devices("cpu")[:1])
    tier = offload.ShardedOffloadedTable(
        "fields", EmbeddingVariableMeta(embedding_dim=9,
                                        vocabulary_size=1024),
        {"category": "adagrad"}, {"category": "constant", "value": 0.0},
        vocab=1024, cache_capacity=1 << 14, mesh=mesh)
    cache = tier.create_cache()
    _, columns, layout = tier._packed_layout(np.dtype(cache.keys.dtype))
    table = (cache.keys, cache.weights, cache.slots)
    texts = {
        "offload_insert": sh._insert_packed_program(
            mesh, tier.spec, 9, layout).lower(
                *table, cache.insert_failures, cache.init_rng,
                jnp.zeros((512, columns), jnp.float32)).as_text(),
        "offload_read": sh._read_rows_program(
            mesh, tier.spec, tuple(cache.slots)).lower(
                *table, jnp.zeros((512,), jnp.int32)).as_text()}
    assert {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in texts.items()} == _BOUNDED_TEXTS
