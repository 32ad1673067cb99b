"""On-chip smoke: the DeepFM trainer and the serving read path, once, on
every local TPU chip, through the entry points a user calls.

    python chip_smoke.py            # on a machine with 1 or 4 chips

Phases (any failure raises; exit 0 and the final JSON line only if every
phase ran on ``platform == "tpu"``):

  exchange_array / exchange_hash   pull and one SGD push on a small
      constant-initialised table, exactly equal to numpy (constant init:
      random init folds the PRNG per shard, so it depends on the layout)
  pallas        ``gather_rows`` and ``probe_gather`` compiled by Mosaic at
      the shape their guards admit, equal to ``jnp.take``
  train_array / train_hash   EmbeddingCollection + Trainer.init +
      Trainer.fit, DeepFM at full Criteo width (26 sparse + 13 dense, dim 9
      plus the linear column, MLP (256, 128), batch 4096, fused, Adagrad
      rows + optax dense) on synthetic Zipf batches; loss finite and lower
      than at step 1 on a repeated batch set; the default wide-key hash
      tables second
  serving       save_checkpoint -> ModelRegistry load -> lookups, rows
      exactly equal to ``coll.pull(..., read_only=True)``, same process

One process, no children: the chip (and the libtpu lockfile) belongs to
whoever touched JAX first. Uses all local devices: 1 chip -> 1x1, 4 chips
-> data 2 x model 2, where it also checks that table shards sit on
distinct chips with equal bytes, that ``bytes_in_use`` is even, and that
the compiled step exchanges rows by all-to-all.

Each phase prints one JSON line (wall, compile seconds and persistent-
cache hits from the program's load ledger, and what it measured). The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

import contextlib
import importlib.metadata
import json
import shutil
import sys
import tempfile
import time

import numpy as np

import jax

FEATURES = 26                    # Criteo sparse columns (13 dense beside)
DIM = 9
BATCH = 4096
ROWS_PER_FEATURE = 1 << 20       # the chip's share of rows; widths are full
HASH_CAPACITY = 1 << 22          # examples/criteo_deepctr.py's default
BATCH_SET = 6                    # distinct batches, cycled
WARM_STEPS = 2                   # Trainer.fit's own warmup before steady
TIMED_STEPS = 30


@contextlib.contextmanager
def phase(name):
    """One JSON line per phase: wall seconds, the seconds XLA spent
    compiling or fetching programs, how many, and how many of them the
    persistent cache held (the load ledger's totals at the phase's end
    less those at its start), plus what the phase puts in ``rec``."""
    from openembedding_tpu.analysis.retrace import LEDGER
    rec = {"phase": name}
    before, t0 = LEDGER.totals(), time.perf_counter()
    try:
        yield rec
    finally:
        after = LEDGER.totals()
    rec["compile_s"] = round(after["compile_s"] + after["fetch_s"]
                             - before["compile_s"] - before["fetch_s"], 2)
    rec["programs"] = after["programs"] - before["programs"]
    rec["cache_hits"] = after["hits"] - before["hits"]
    rec["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(rec), flush=True)


def device_memory():
    """``memory_stats()`` of every local device (bytes)."""
    out = []
    for d in jax.local_devices():
        st = d.memory_stats()
        out.append({k: int(st[k]) for k in
                    ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    return out


def check_exchange(mesh, *, use_hash, vocab=4096, dim=8, n=1024):
    """Pull == numpy ``table[idx]`` and one SGD push == the numpy update,
    exactly: the constant init, the learning rate and the gradients are
    small dyadic numbers, so no summation order can round."""
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec

    init, lr = 1.0, 0.5
    spec = EmbeddingSpec(
        name="t", input_dim=-1 if use_hash else vocab, output_dim=dim,
        hash_capacity=4 * vocab,
        optimizer={"category": "sgd", "learning_rate": lr},
        initializer={"category": "constant", "value": init})
    coll = EmbeddingCollection((spec,), mesh)
    rng = np.random.RandomState(0)
    # hash keys span the 64-bit space the wide default exists for
    space = (rng.randint(1, 1 << 62, size=vocab, dtype=np.int64)
             if use_hash else np.arange(vocab, dtype=np.int64))
    pick = rng.randint(0, vocab // 2, size=n)          # duplicates included
    idx = space[pick] if use_hash else space[pick].astype(np.int32)
    grads = rng.randint(-4, 5, size=(n, dim)).astype(np.float32)

    pull = jax.jit(lambda s, i: coll.pull(s, {"t": i})["t"])
    push = jax.jit(lambda s, i, g: coll.apply_gradients(s, {"t": i},
                                                        {"t": g}))
    states = coll.init(jax.random.PRNGKey(0))
    table = np.full((vocab, dim), init, np.float32)
    got = np.asarray(pull(states, idx))
    if not (got == table[pick]).all():
        raise AssertionError("pull of the constant table != numpy table[idx]")
    states = push(states, idx, grads)
    np.subtract.at(table, pick, lr * grads)
    # read every row back, touched or not, through the same exchange
    everything = space if use_hash else space.astype(np.int32)
    got = np.asarray(pull(states, everything))
    if not (got == table).all():
        bad = int((got != table).any(axis=1).sum())
        raise AssertionError(f"SGD push != numpy update on {bad} rows")
    return {"rows": vocab, "pulled": n, "distinct": int(np.unique(pick).size)}


def check_pallas():
    """Each kernel compiled by Mosaic (not interpreted) at the shape its
    guard admits, equal to the XLA gather."""
    import jax.numpy as jnp
    from openembedding_tpu import hash_table as hl
    from openembedding_tpu.ops import pallas_gather as pg, pallas_hash as ph

    rng = np.random.RandomState(1)
    vocab, cap, n = 1 << 16, 1 << 16, 4096
    table = jnp.asarray(rng.randn(vocab, pg.LANES).astype(np.float32))
    idx = rng.randint(0, vocab, size=n).astype(np.int32)
    idx[::97] = -1                                     # invalid -> zero rows
    want = np.where((idx >= 0)[:, None],
                    np.asarray(jnp.take(table, jnp.maximum(idx, 0), axis=0)),
                    0.0)
    got = np.asarray(pg.gather_rows(table, jnp.asarray(idx)))
    if not (got == want).all():
        raise AssertionError("pallas gather_rows != jnp.take")

    empty = hl.empty_key(jnp.int32)
    keys = jnp.asarray(rng.permutation(1 << 20)[:cap // 2].astype(np.int32)
                       + 1)
    tk = jnp.full((cap,), empty, jnp.int32)
    tk, _slot, _ins, failed = jax.jit(hl.find_or_insert)(tk, keys,
                                                         keys != empty)
    if int(failed.sum()):
        raise AssertionError("hash insert overflowed at half load")
    weights = jnp.asarray(rng.randn(cap, pg.LANES).astype(np.float32))
    q = jnp.concatenate([keys[:n // 2],                # hits
                         keys[:n // 2] + (1 << 21)])   # misses
    bucket, _nb, chain = hl.table_layout(cap, hl.DEFAULT_MAX_PROBES)
    rows, hit = ph.probe_gather(
        tk, weights, hl.probe_starts(q, cap, hl.DEFAULT_MAX_PROBES), q,
        chain=chain, bucket=bucket, empty=int(empty))
    slots = hl.find_rows(tk, q)
    want_hit = np.asarray(slots >= 0)
    want = np.where(want_hit[:, None],
                    np.asarray(jnp.take(weights, jnp.maximum(slots, 0),
                                        axis=0)), 0.0)
    if not ((np.asarray(hit) == want_hit).all()
            and (np.asarray(rows) == want).all()):
        raise AssertionError("pallas probe_gather != find_rows + jnp.take")
    return {"gather": [vocab, pg.LANES, n], "probe": [cap, pg.LANES, n],
            "hits": int(want_hit.sum())}


def check_layout(mesh, coll, emb):
    """Several chips: every state leaf laid out as the collection declares
    (``state_shardings``), and every sharded one in equal shards on
    distinct chips — nothing gathered onto chip 0."""
    def check(leaf, want):
        if not leaf.sharding.is_equivalent_to(want, leaf.ndim):
            raise AssertionError(
                f"state leaf {leaf.shape}: {leaf.sharding} != {want}")
        if want.is_fully_replicated:
            return
        shards = leaf.addressable_shards
        sizes = {s.data.nbytes for s in shards}
        if len({s.device for s in shards}) != mesh.size or len(sizes) != 1 \
                or sizes.pop() * mesh.size != leaf.nbytes:
            raise AssertionError(
                f"state leaf {leaf.shape} {leaf.dtype} is not one equal "
                f"shard per chip: {[(s.device.id, s.data.nbytes) for s in shards]}")
    jax.tree.map(check, emb, coll.state_shardings())


def build_deepfm(mesh, *, use_hash, rows_per_feature=ROWS_PER_FEATURE,
                 hash_capacity=HASH_CAPACITY, key_dtype="wide"):
    """(collection, trainer, mapper) for DeepFM at full Criteo width, as
    examples/criteo_deepctr.py builds it (tests/test_tpu_lowering.py
    compiles the same for a described v5e topology)."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr

    features = tuple(criteo.SPARSE_NAMES)
    assert len(features) == FEATURES
    specs, mapper = make_fused_specs(
        features, -1 if use_hash else rows_per_feature, DIM,
        optimizer={"category": "adagrad", "learning_rate": 0.01},
        hash_capacity=hash_capacity, key_dtype=key_dtype)
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adam(1e-3))
    return coll, trainer, mapper


def train(mesh, *, use_hash, rows_per_feature=ROWS_PER_FEATURE, batch=BATCH,
          hash_capacity=HASH_CAPACITY, timed_steps=TIMED_STEPS):
    """EmbeddingCollection + Trainer.init + Trainer.fit, the path
    examples/criteo_deepctr.py drives. Returns (report, coll, trainer,
    state, batches)."""
    from openembedding_tpu.analysis import contracts
    from openembedding_tpu.data import criteo
    from openembedding_tpu.utils.jaxcompat import compiled_memory_stats

    coll, trainer, mapper = build_deepfm(
        mesh, use_hash=use_hash, rows_per_feature=rows_per_feature,
        hash_capacity=hash_capacity)
    batches = [mapper.fuse_batch(b) for b in criteo.synthetic_criteo(
        batch, num_buckets=rows_per_feature, num_batches=BATCH_SET)]

    t0 = time.perf_counter()
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    if mesh.size > 1:
        check_layout(mesh, coll, state.emb)

    # step 1 alone (compiles), a warm-up, then the steady window — three
    # fits over one repeated batch set that ends where it began
    t0 = time.perf_counter()
    state, first = trainer.fit(state, batches[:1])
    loss_first = float(first["loss"])
    first_step_s = time.perf_counter() - t0
    state, _ = trainer.fit(state, batches[1:1 + WARM_STEPS])
    schedule = [batches[(1 + WARM_STEPS + i) % BATCH_SET]
                for i in range(timed_steps)]
    schedule[-1] = batches[0]
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state, last = trainer.fit(state, schedule, retrace_budget=0)
    jax.block_until_ready(state)
    step_s = (time.perf_counter() - t0) / timed_steps
    loss_last = float(last["loss"])
    if not (np.isfinite(loss_first) and np.isfinite(loss_last)
            and loss_last < loss_first):
        raise AssertionError(
            f"loss on the repeated batch went {loss_first} -> {loss_last}")

    compiled = trainer.lower_train_step(
        state, trainer.shard_batch(batches[0])).compile()
    collectives = contracts.summarize(compiled.as_text())
    if (mesh.size > 1) != ("all-to-all" in collectives):
        raise AssertionError(
            f"{mesh.size}-chip step compiled with collectives {collectives}")
    report = {
        "init_s": round(init_s, 2), "first_step_s": round(first_step_s, 2),
        "step_s": round(step_s, 5), "steps": 1 + WARM_STEPS + timed_steps,
        "loss_first": round(loss_first, 5), "loss_last": round(loss_last, 5),
        "step_memory": compiled_memory_stats(compiled),
        "step_collectives": {op: n for op, (n, _b) in collectives.items()},
        "device_memory": device_memory(),
    }
    return report, coll, trainer, state, batches


def check_even_memory(mesh):
    """Several chips: no chip holds what the others do not."""
    used = [m["bytes_in_use"] for m in device_memory()]
    if mesh.size > 1 and max(used) > 1.25 * min(used):
        raise AssertionError(f"bytes_in_use uneven across chips: {used}")


def check_pull_contract(mesh, coll, state, batches):
    """The a2a pull program on the real mesh: owner exchange by all-to-all,
    no all-gather beyond row re-assembly (``check_a2a_pull_hlo``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from openembedding_tpu.analysis import contracts
    from openembedding_tpu.parallel.mesh import DATA_AXIS

    name = next(iter(coll.specs))
    by_batch = NamedSharding(mesh, P(DATA_AXIS))
    idx = jax.device_put(batches[0]["sparse"][name], by_batch)
    txt = jax.jit(lambda s, i: coll.pull(s, {name: i})[name],
                  out_shardings=by_batch).lower(state.emb, idx) \
        .compile().as_text()
    rows_per_slice = idx.shape[0] * idx.shape[1] // mesh.shape[DATA_AXIS]
    contracts.check_a2a_pull_hlo(txt, batch_slice=rows_per_slice, dim=DIM)


def serve(mesh, coll, trainer, state, batches, n=4096):
    """save_checkpoint -> ModelRegistry load -> lookups on the chip, rows
    exactly those of ``coll.pull(..., read_only=True)`` on the trained
    state. The registry lives in this process: a replica daemon would be a
    second process reaching for the chip."""
    from openembedding_tpu import checkpoint as ckpt
    from openembedding_tpu.serving.registry import ModelRegistry

    read = jax.jit(lambda s, name, i: coll.pull(
        s, {name: i}, batch_sharded=False, read_only=True)[name],
        static_argnums=1)
    d = tempfile.mkdtemp(prefix="oe_chip_smoke_")
    registry = ModelRegistry(mesh)
    try:
        t0 = time.perf_counter()
        info = ckpt.save_checkpoint(d, coll, state.emb,
                                    model_sign=trainer.model_sign(state))
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sign = registry.create_model(d)
        load_s = time.perf_counter() - t0
        lookups = 0
        lookup_s = []
        for name in coll.specs:
            ids = batches[0]["sparse"][name]
            ids = ids.reshape((-1,) + ids.shape[2:])       # flat ids / pairs
            for q in (ids[:n], ids[n:n + n // 8]):
                q = q.copy()
                q[::7] += 1           # mostly trained keys, some never seen
                want = np.asarray(read(state.emb, name, q))
                registry.lookup(sign, name, q)              # compiles
                t0 = time.perf_counter()
                got = np.asarray(registry.lookup(sign, name, q))
                lookup_s.append(time.perf_counter() - t0)
                if got.shape != want.shape or not (got == want).all():
                    raise AssertionError(
                        f"registry lookup of {name!r} != coll.pull "
                        "(read_only) on the trained state")
                if not got.any():
                    raise AssertionError(f"lookup of {name!r} is all zeros")
                lookups += 1
    finally:
        registry.close()
        shutil.rmtree(d, ignore_errors=True)
    return {"ckpt_bytes": int(info["bytes"]), "save_s": round(save_s, 2),
            "load_s": round(load_s, 2), "lookups": lookups,
            "lookup_s_max": round(max(lookup_s), 5),
            "device_memory": device_memory()}


def main():
    devices = jax.devices()
    found = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if found["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {found}", file=sys.stderr)
        return 2
    # nothing reaches stdout before the package is known to be here
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    print(json.dumps({
        "device": found,
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache": enable_compile_cache()}), flush=True)

    n = len(devices)
    data = 2 if n > 1 and n % 2 == 0 else 1
    mesh = create_mesh(data, n // data, devices)
    print(json.dumps({"mesh": dict(mesh.shape)}), flush=True)

    with phase("exchange_array") as rec:
        rec.update(check_exchange(mesh, use_hash=False))
    with phase("exchange_hash") as rec:
        rec.update(check_exchange(mesh, use_hash=True))
    with phase("pallas") as rec:
        rec.update(check_pallas())

    for kind, use_hash in (("array", False), ("hash", True)):
        coll = trainer = state = batches = None    # the last tables leave HBM
        with phase(f"train_{kind}") as rec:
            report, coll, trainer, state, batches = train(mesh,
                                                          use_hash=use_hash)
            rec.update(report)
            check_even_memory(mesh)
        if mesh.size > 1:
            with phase(f"pull_contract_{kind}"):
                check_pull_contract(mesh, coll, state, batches)
    # the wide-key hash model, trained last, is the one served
    with phase("serving") as rec:
        rec.update(serve(mesh, coll, trainer, state, batches))

    print(json.dumps({"ok": True, "device": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
