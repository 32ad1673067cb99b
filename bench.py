"""Benchmark driver: sparse-embedding training throughput + checkpoint IO.

Default invocation (the driver contract) runs the headline config and prints
ONE JSON line. ``--suite`` runs the full matrix — the reference benchmarks
across model families, dims, table kinds and dataset skew
(test/benchmark/criteo_deepctr.py flags + documents/en/benchmark.md) — one
JSON line per config. A config that measures the device fails where JAX
finds no TPU; only the ``DEVICELESS`` configs run on the CPU backend.

Headline baseline: the reference's Criteo-1TB number (692k examples/s on
8 GPU workers + 1 PS, documents/en/benchmark.md:41-52) = 86.5k examples/s
per accelerator chip; ``vs_baseline`` is examples/s/chip against that.
Checkpoint baseline: 78 GB in 869 s = 0.09 GB/s (benchmark.md:52-55).

Per-config extras: ``emb_gbps`` estimates achieved HBM traffic on the
embedding path (gather reads + update read/writes incl. optimizer slots) —
the honest utilization number for a bandwidth-bound workload (an MXU-centric
MFU would flatter it: the dense MLP is a small fraction of the work).
"""

import argparse
import gc
import json
import sys
import time

import numpy as np

REF_PER_CHIP = 692_000 / 8     # examples/s per accelerator in the reference
REF_CKPT_GBPS = 78.0 / 869.0   # reference checkpoint throughput


def build(config, mesh):
    import jax
    import optax

    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr

    features = tuple(criteo.SPARSE_NAMES)
    if config.get("fused", True):
        specs, mapper = make_fused_specs(
            features, -1 if config.get("hash") else config["vocab"],
            config["dim"],
            optimizer={"category": "adagrad", "learning_rate": 0.01},
            hash_capacity=config.get("hash_capacity", 1 << 22),
            key_dtype=config.get("key_dtype", "wide"),
            plane=config.get("plane", "a2a"),
            cache_k=config.get("cache_k", 0),
            cache_refresh_every=config.get("cache_refresh_every", 64))
    else:
        specs = deepctr.make_feature_specs(
            features, config["vocab"], config["dim"],
            optimizer={"category": "adagrad", "learning_rate": 0.01},
            plane=config.get("plane", "a2a"),
            cache_k=config.get("cache_k", 0),
            cache_refresh_every=config.get("cache_refresh_every", 64))
        mapper = None
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model(config.get("model", "deepfm"),
                                          features),
                      coll, optax.adagrad(0.01))
    return features, coll, trainer, mapper


def make_batches(config, features, mapper, n=8):
    from openembedding_tpu.data import criteo
    batch = config["batch"]
    if config.get("zipf"):
        stream = criteo.synthetic_criteo(
            batch, num_buckets=config["vocab"], num_batches=n)
        raw = list(stream)
    else:
        rng = np.random.RandomState(0)
        raw = []
        for _ in range(n):
            sparse = {f: rng.randint(0, config["vocab"], batch)
                      .astype(np.int32) for f in features}
            raw.append({"label": (rng.rand(batch) > 0.75).astype(np.float32),
                        "dense": rng.randn(batch, 13).astype(np.float32),
                        "sparse": sparse})
    if mapper is not None:
        return [mapper.fuse_batch(b) for b in raw]
    return list(criteo.add_linear_columns(raw))


def emb_bytes_per_step(config, batch):
    """Estimated embedding-path HBM bytes per step: gather reads of B*F rows
    (dim + 1 linear) + update read/write of touched rows incl. one adagrad
    slot (approximating touched ~= B*F; dedup lowers it under zipf)."""
    f = 26
    row = (config["dim"] + 1) * 4
    gather = batch * f * row
    update = 2 * batch * f * (row * 2)   # read+write of weights+slot rows
    return gather + update


def _hbm_stats():
    """Device-memory context for a measurement (bytes in use / limit),
    when the backend exposes it. Localizes OOM-adjacent regressions."""
    try:
        import jax
        st = jax.local_devices()[0].memory_stats() or {}
        out = {}
        if "bytes_in_use" in st:
            out["hbm_in_use_gb"] = round(st["bytes_in_use"] / 1e9, 2)
        if "bytes_limit" in st:
            out["hbm_limit_gb"] = round(st["bytes_limit"] / 1e9, 2)
        return out
    except Exception:  # noqa: BLE001 — context, never a failure source
        return {}


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


PROFILE_DIR = None  # set by --profile; runners trace one block per config


def _maybe_profile(name):
    """Context manager: a ``jax.profiler.trace`` block under
    ``<PROFILE_DIR>/<config>`` when ``--profile`` was given (TensorBoard/
    Perfetto viewable) — the reference benchmark's ``--profile`` flag
    (test/benchmark/criteo_deepctr.py:290-293), else a no-op."""
    import contextlib
    if not PROFILE_DIR:
        return contextlib.nullcontext()
    import os
    import jax
    return jax.profiler.trace(os.path.join(PROFILE_DIR, name))


def run_config(name, config, *, steps, warmup, repeats=5):
    """Train-throughput config: median-of-N timed blocks + stage breakdown.

    The headline is the MEDIAN of ``repeats`` timed blocks with the spread
    reported, so one slow block does not decide it. ``pull_ms``/``update_ms`` time the sparse halves standalone
    (same compiled programs, run in isolation) so regressions localize;
    they overlap inside the fused step, so their sum exceeds ``step_ms``.
    """
    import jax
    from openembedding_tpu.parallel.mesh import create_mesh

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    data_ax = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = create_mesh(data_ax, n_dev // data_ax)
    batch = config["batch"]

    features, coll, trainer, mapper = build(config, mesh)
    batches = make_batches(config, features, mapper)
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    for i in range(warmup):
        state, m = trainer.train_step(state, batches[i % len(batches)])
    jax.block_until_ready(m["loss"])

    block_eps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for i in range(steps):
            state, m = trainer.train_step(state, batches[i % len(batches)])
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
        block_eps.append(steps * batch / dt)
    eps = _median(block_eps)
    dt_step = batch / eps
    if PROFILE_DIR:
        # one traced block OUTSIDE the timed ones (tracing skews timings)
        with _maybe_profile(name):
            for i in range(min(steps, 20)):
                state, m = trainer.train_step(state,
                                              batches[i % len(batches)])
            jax.block_until_ready(m["loss"])

    # stage isolation: sparse pull / sparse update on the trained state.
    # Each stage is ONE jitted program (like inside the fused step), not
    # an eager per-variable dispatch loop: a per-feature config launches
    # 52 independent collective programs per eager call, and async
    # interleaving of that many programs starves the CPU backend's
    # device-thread pool into a rendezvous deadlock (observed wedging
    # this box at `coll.pull`; single-program dispatch cannot deadlock)
    stage = {}
    try:
        sb = trainer.shard_batch(batches[0])
        inputs = sb["sparse"] if isinstance(sb, dict) and "sparse" in sb \
            else sb
        if isinstance(inputs, dict):
            inputs = {k: v for k, v in inputs.items() if k in coll.specs}
        if inputs:
            pull_fn = jax.jit(lambda st, inp: coll.pull(st, inp))
            rows = pull_fn(state.emb, inputs)
            jax.block_until_ready(jax.tree.leaves(rows))
            t0 = time.perf_counter()
            for _ in range(steps):
                rows = pull_fn(state.emb, inputs)
            jax.block_until_ready(jax.tree.leaves(rows))
            stage["pull_ms"] = round(1000 * (time.perf_counter() - t0)
                                     / steps, 3)
            grads = {k: v for k, v in rows.items()}
            upd_fn = jax.jit(
                lambda st, inp, g: coll.apply_gradients(st, inp, g))
            emb = upd_fn(state.emb, inputs, grads)
            jax.block_until_ready(jax.tree.leaves(emb))
            t0 = time.perf_counter()
            for _ in range(steps):
                emb = upd_fn(state.emb, inputs, grads)
            jax.block_until_ready(jax.tree.leaves(emb))
            stage["update_ms"] = round(1000 * (time.perf_counter() - t0)
                                       / steps, 3)
            # the isolated-update result is a full second copy of every
            # table — release it before the next timed block/config
            del emb, rows, grads
    except Exception as e:  # noqa: BLE001 — breakdown is best-effort
        stage["stage_error"] = f"{type(e).__name__}: {e}"

    result = {
        "metric": f"{name}_examples_per_sec_{platform}{n_dev}",
        "value": round(eps, 1),
        "unit": "examples/s",
        "vs_baseline": round(eps / n_dev / REF_PER_CHIP, 3),
        "per_chip": round(eps / n_dev, 1),
        "step_ms": round(1000 * dt_step, 3),
        "eps_min": round(min(block_eps), 1),
        "eps_max": round(max(block_eps), 1),
        "emb_gbps": round(emb_bytes_per_step(config, batch)
                          / dt_step / 1e9, 2),
        **stage,
        **_hbm_stats(),
        "config": dict(config),
    }
    if config.get("checkpoint"):
        result.update(run_checkpoint(coll, state))
    del state
    return result


def run_checkpoint(coll, state):
    """Save+load wall time for this config's tables (reference: 78GB/869s)."""
    import shutil
    import tempfile
    import jax
    from openembedding_tpu import checkpoint as ckpt

    nbytes = sum(x.nbytes for x in jax.tree.leaves(state.emb))
    d = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        t0 = time.perf_counter()
        ckpt.save_checkpoint(d, coll, state.emb)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        loaded = ckpt.load_checkpoint(d, coll)
        jax.block_until_ready(jax.tree.leaves(loaded))
        load_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(d, ignore_errors=True)
    gb = nbytes / 1e9
    return {
        "ckpt_gb": round(gb, 3),
        "ckpt_save_s": round(save_s, 2),
        "ckpt_load_s": round(load_s, 2),
        "ckpt_gbps_vs_ref": round(gb / max(save_s, 1e-9) / REF_CKPT_GBPS, 2),
    }


def _zipf_uid_batch_maker(rng, batch, vocab, zipf_a):
    """Shared synthetic stream for the offload benches: zipf-skewed uid over
    the full store (hot head caches, long tail streams through host) + a
    bounded ctx feature."""
    def make_batch():
        z = rng.zipf(zipf_a, size=batch)
        uid = ((z * 2654435761) % vocab).astype(np.int32)
        ctx = rng.randint(0, 100_000, batch).astype(np.int32)
        return {"label": (rng.rand(batch) > 0.75).astype(np.float32),
                "dense": rng.randn(batch, 13).astype(np.float32),
                "sparse": {"uid": uid, "uid:linear": uid,
                           "ctx": ctx, "ctx:linear": ctx}}
    return make_batch


def run_offload(name, config, *, steps, warmup):
    """North-star-scale offload config: host store >> HBM through the
    Trainer (the reference's PMem bar: DRAM-like throughput on a 500 GB
    model, documents/en/pmem.md:1-7). Reports examples/s, cache-hit rate,
    eviction and persist cost. The host store is a disk memmap
    (``backing_dir``) so the bench is bounded by neither HBM nor host RAM.
    """
    import shutil
    import tempfile
    import jax
    import optax
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec, Trainer
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu.parallel.mesh import create_mesh

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    mesh = create_mesh(1, n_dev)
    batch = config["batch"]
    dim = config["dim"]
    vocab = config["vocab"]
    cache = config["cache"]
    backing = tempfile.mkdtemp(prefix="bench_offload_")
    try:
        from openembedding_tpu import EmbeddingVariableMeta
        t0 = time.perf_counter()
        opt = {"category": "adagrad", "learning_rate": 0.01}
        init = {"category": "constant", "value": 0.01}
        table = ShardedOffloadedTable(
            "uid", EmbeddingVariableMeta(embedding_dim=dim,
                                         vocabulary_size=vocab),
            opt, init, vocab=vocab, cache_capacity=cache, mesh=mesh,
            backing_dir=backing)
        # the model's first-order term: a dim-1 companion, offloaded too
        # (the reference keeps linear weights on the PS as well)
        lin = ShardedOffloadedTable(
            "uid:linear", EmbeddingVariableMeta(embedding_dim=1,
                                                vocabulary_size=vocab),
            opt, init, vocab=vocab, cache_capacity=cache, mesh=mesh,
            backing_dir=backing)
        alloc_s = time.perf_counter() - t0
        specs = (table.embedding_spec(), lin.embedding_spec(),
                 EmbeddingSpec(name="ctx", input_dim=100_000, output_dim=dim,
                               optimizer=opt),
                 EmbeddingSpec(name="ctx:linear", input_dim=100_000,
                               output_dim=1, optimizer=opt))
        coll = EmbeddingCollection(specs, mesh)
        serial = bool(config.get("serial"))
        # explicit "depth" pins the A/B points; absent, the config
        # measures the FRAMEWORK default (Trainer.pipeline_depth)
        kw = {"pipeline_depth": int(config["depth"])} \
            if "depth" in config else {}
        trainer = Trainer(deepctr.build_model("deepfm", ("uid", "ctx")),
                          coll, optax.adagrad(0.01),
                          offload={"uid": table, "uid:linear": lin},
                          **kw)
        depth = trainer.pipeline_depth

        rng = np.random.RandomState(0)
        make_batch = _zipf_uid_batch_maker(rng, batch, vocab,
                                           config.get("zipf_a", 1.08))
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(make_batch()))
        # instrument the host half of prepare: with the lookahead pipeline
        # step time should approach max(host prepare, device step), not
        # their sum — prepare_ms vs step_ms in the result shows which
        prep_times = []
        for t in (table, lin):
            def timed_hp(ids, _orig=t.host_prepare):
                t0 = time.perf_counter()
                out = _orig(ids)
                prep_times.append(time.perf_counter() - t0)
                return out
            t.host_prepare = timed_hp
        hits = misses = 0
        for i in range(warmup):
            state, m = trainer.train_step(state, make_batch())
        jax.block_until_ready(m["loss"])
        prep_times.clear()
        # fresh zipf batches every step: the long tail keeps missing, the
        # hot head keeps hitting — the steady-state cache economics.
        # Pre-generate so batch synthesis is outside the timed loop, and
        # PIPELINE depth-K via prefetch (serial=True skips it entirely —
        # the A/B that isolates what the overlap buys)
        timed = [make_batch() for _ in range(steps)]
        uniqs = [np.unique(b["sparse"]["uid"]) for b in timed]
        t0 = time.perf_counter()
        for i in range(steps):
            # residency must be read in sequence (prepare mutates it), but
            # the uniq sets were precomputed outside the timed loop
            was_resident = int(table._resident[uniqs[i]].sum())
            hits += was_resident
            misses += uniqs[i].size - was_resident
            if not serial:
                trainer.prefetch(timed[i:i + 1 + depth])
            state, m = trainer.train_step(state, timed[i])
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        pdir = tempfile.mkdtemp(prefix="bench_offpersist_")
        try:
            info = table.persist(state.emb["uid"], pdir)
            persist_s = time.perf_counter() - t0
            persist_rows = info["rows"]
        finally:
            shutil.rmtree(pdir, ignore_errors=True)
        prep_sum = sum(prep_times)   # snapshot BEFORE the profile block
        if PROFILE_DIR:
            # traced block OUTSIDE the timed/persist measurements
            extra = [make_batch() for _ in range(10)]
            with _maybe_profile(name):
                for i, b in enumerate(extra):
                    if not serial:
                        trainer.prefetch(extra[i:i + 1 + depth])
                    state, m = trainer.train_step(state, b)
                jax.block_until_ready(m["loss"])
        eps = steps * batch / dt
        store_gb = sum(
            t.host_weights.nbytes + sum(v.nbytes
                                        for v in t.host_slots.values())
            for t in (table, lin)) / 1e9
        return {
            "metric": f"{name}_examples_per_sec_{platform}{n_dev}",
            "value": round(eps, 1),
            "unit": "examples/s",
            "vs_baseline": round(eps / n_dev / REF_PER_CHIP, 3),
            "per_chip": round(eps / n_dev, 1),
            "step_ms": round(1000 * dt / steps, 3),
            # host-prepare wall time per step (both tables, runs on the
            # lookahead thread): overlapped when step_ms ~= max(this,
            # device time) rather than their sum
            "prepare_ms": round(1000 * prep_sum / max(steps, 1), 3),
            "mode": "serial" if serial else f"lookahead_k{depth}",
            "host_store_gb": round(store_gb, 2),
            "cache_rows": cache,
            "cache_hit_rate": round(hits / max(hits + misses, 1), 4),
            "alloc_s": round(alloc_s, 1),
            "persist_s": round(persist_s, 2),
            "persist_rows": persist_rows,
            **_hbm_stats(),
            "config": dict(config),
        }
    finally:
        shutil.rmtree(backing, ignore_errors=True)


def run_offload_sweep(name, config, *, steps, warmup):
    """Cache-size -> hit-rate/throughput sweep for the offload tier, plus
    an in-HBM array-table ROOFLINE of the same model/batch: the tier must
    approach the roofline as the working set fits the cache — the
    reference's PMem bar (PMem ~= DRAM once the cache holds the hot set,
    documents/en/pmem.md:1-7)."""
    import jax
    import optax
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec, Trainer
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh

    entries = []
    for cache in config["caches"]:
        sub = dict(config, cache=cache)
        r = run_offload(f"{name}_c{cache}", sub, steps=steps, warmup=warmup)
        entries.append({
            "cache_rows": cache,
            "examples_per_sec": r["value"],
            "hit_rate": r["cache_hit_rate"],
            "step_ms": r["step_ms"],
        })
        gc.collect()
        jax.clear_caches()

    # roofline: identical model/batch with plain in-HBM array tables
    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    mesh = create_mesh(1, n_dev)
    batch, dim = config["batch"], config["dim"]
    hbm_vocab = 1 << 22
    opt = {"category": "adagrad", "learning_rate": 0.01}
    specs = (EmbeddingSpec(name="uid", input_dim=hbm_vocab, output_dim=dim,
                           optimizer=opt),
             EmbeddingSpec(name="uid:linear", input_dim=hbm_vocab,
                           output_dim=1, optimizer=opt),
             EmbeddingSpec(name="ctx", input_dim=100_000, output_dim=dim,
                           optimizer=opt),
             EmbeddingSpec(name="ctx:linear", input_dim=100_000,
                           output_dim=1, optimizer=opt))
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", ("uid", "ctx")),
                      coll, optax.adagrad(0.01))
    rng = np.random.RandomState(0)
    make_batch = _zipf_uid_batch_maker(rng, batch, hbm_vocab,
                                       config.get("zipf_a", 1.08))
    batches = [make_batch() for _ in range(8)]
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    for i in range(warmup):
        state, m = trainer.train_step(state, batches[i % len(batches)])
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for i in range(steps):
        state, m = trainer.train_step(state, batches[i % len(batches)])
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    roofline_eps = steps * batch / dt
    del state

    best = max(e["examples_per_sec"] for e in entries)
    return {
        "metric": f"{name}_{platform}{n_dev}",
        "value": round(best / roofline_eps, 3),
        "unit": "fraction_of_array_roofline",
        "vs_baseline": round(best / roofline_eps, 3),
        "array_roofline_eps": round(roofline_eps, 1),
        "sweep": entries,
        "config": dict(config),
    }


def run_hash_probe(name, config, *, steps, warmup):
    """Hash pull path microbench: bucket-row XLA probe (default) vs the
    fused Pallas probe+gather kernel vs the raw array row-gather roofline.
    All three run K lookups inside one jitted loop (per-iteration query
    batches derived on device) so the per-dispatch host cost cancels."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax import lax
    from openembedding_tpu import EmbeddingVariableMeta, hash_table as hl
    from openembedding_tpu import make_optimizer
    from openembedding_tpu.ops import pallas_hash as ph

    platform = jax.devices()[0].platform
    cap, dim, B = config["capacity"], config["dim"], config["batch"]
    K = config.get("loops", 20)
    rng = np.random.RandomState(0)
    n_ins = cap // 2
    nk = jnp.asarray((rng.permutation(max(n_ins * 4, 1 << 20))[:n_ins])
                     .astype(np.int32) + 1)
    meta = EmbeddingVariableMeta(embedding_dim=dim, vocabulary_size=2**63)
    opt = make_optimizer({"category": "default"})
    table = hl.create_hash_table(meta, opt, capacity=cap)
    ins = jax.jit(hl.find_or_insert)
    tk = table.keys
    for lo in range(0, n_ins, 1 << 18):
        c = nk[lo:lo + (1 << 18)]
        tk, _s, _i, _f = ins(tk, c, c != hl.empty_key(jnp.int32))
    weights = jnp.asarray(rng.randn(cap, dim).astype(np.float32))
    bsz, _nb, chain = hl.table_layout(cap, hl.DEFAULT_MAX_PROBES)
    EMPTY = hl.empty_key(jnp.int32)

    @functools.partial(jax.jit, static_argnames=("mode",))
    def many(tk, weights, nk, seed, mode):
        def body(i, acc):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            q = jnp.take(nk, jax.random.randint(key, (B,), 0, n_ins), axis=0)
            if mode == "pallas":
                starts = hl.probe_starts(q, cap, hl.DEFAULT_MAX_PROBES)
                rows, _hit = ph.probe_gather(
                    tk, weights, starts, q, chain=chain, bucket=bsz,
                    empty=EMPTY)
            elif mode == "xla_probe":
                slots = hl.find_rows(tk, q)
                hit = slots >= 0
                rows = jnp.take(weights, jnp.where(hit, slots, 0), axis=0,
                                mode="clip")
                rows = jnp.where(hit[:, None], rows, 0.0)
            else:  # array_gather roofline
                rows = jnp.take(weights, q % cap, axis=0, mode="clip")
            return acc + rows.sum()
        return lax.fori_loop(0, K, body, jnp.float32(0))

    def timed(mode):
        float(many(tk, weights, nk, 1, mode))        # compile + warm
        t0 = time.perf_counter()
        float(many(tk, weights, nk, 2, mode))
        return (time.perf_counter() - t0) / K

    # every mode runs or the config fails: a Mosaic refusal of the Pallas
    # mode is a result the invocation must not exit 0 on
    out = {}
    gb = B * dim * 4 / 1e9
    for mode in ("xla_probe", "array_gather", "pallas"):
        per = timed(mode)
        out[f"{mode}_us"] = round(per * 1e6, 1)
        out[f"{mode}_gbps"] = round(gb / per, 1)
    return {
        "metric": f"{name}_{platform}",
        "value": out["xla_probe_us"],
        "unit": "us/lookup_batch",
        "vs_baseline": round(out["array_gather_us"]
                             / out["xla_probe_us"], 3),
        **out,
        "config": dict(config),
    }


def run_auc_criteo(name, config, *, steps, warmup):
    """HELD-OUT AUC on the preprocessed Criteo sample ``CRITEO_DATA`` names
    (a ``label,I1..I13,C1..C26`` csv; >=100k rows give >=30k eval rows and
    a confidence interval that means something) — proves the data path +
    optimizer semantics end-to-end. Reference flow:
    test/benchmark/criteo_deepctr.py AUC. Rows split 70/30 train/eval;
    ``value`` is the EVAL AUC, train AUC + gap alongside. The repo ships no
    sample (``data.preprocess`` derives one from a parent csv), so without
    the variable the config fails and says so."""
    import os
    import jax
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils.observability import StreamingAUC

    path = os.environ.get("CRITEO_DATA")
    if not path:
        raise RuntimeError(
            "auc_criteo needs CRITEO_DATA=<preprocessed label,I1..I13,"
            "C1..C26 csv>: no Criteo sample ships with the repo "
            "(python -m openembedding_tpu.data.preprocess makes one)")
    batch = config["batch"]
    rows = list(criteo.read_criteo_csv(path, batch_size=1))
    n_eval = max(1, int(len(rows) * config.get("eval_frac", 0.3)))
    train_rows, eval_rows = rows[:-n_eval], rows[-n_eval:]

    def rebatch(rws, bsz):
        out = []
        for lo in range(0, len(rws), bsz):
            sub = rws[lo:lo + bsz]
            out.append({
                "label": np.concatenate([r["label"] for r in sub]),
                "dense": np.concatenate([r["dense"] for r in sub]),
                "sparse": {k: np.concatenate([r["sparse"][k] for r in sub])
                           for k in sub[0]["sparse"]}})
        return out

    features = tuple(criteo.SPARSE_NAMES)
    specs, mapper = make_fused_specs(
        features, -1, config["dim"],
        optimizer={"category": "adagrad", "learning_rate": 0.05},
        hash_capacity=1 << 18)
    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    mesh = create_mesh(1, n_dev)
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adagrad(0.05))
    batches = [mapper.fuse_batch(b) for b in rebatch(train_rows, batch)]
    eval_batches = [mapper.fuse_batch(b)
                    for b in rebatch(eval_rows, batch)]
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    n_seen = 0
    t0 = time.perf_counter()
    for epoch in range(config.get("epochs", 30)):
        for b in batches:
            state, m = trainer.train_step(state, b)
            n_seen += int(np.asarray(b["label"]).shape[0])
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0

    def auc_over(bs):
        auc = StreamingAUC()
        for b in bs:
            scores = trainer.eval_step(state, b)
            auc.update(b["label"], np.asarray(scores))
        return float(auc.result())

    eval_auc = auc_over(eval_batches)
    train_auc = auc_over(batches)
    return {
        "metric": f"{name}_{platform}{n_dev}",
        "value": round(eval_auc, 4),
        "unit": "eval_auc",
        "vs_baseline": round(eval_auc / 0.5, 3),
        "train_auc": round(train_auc, 4),
        "train_eval_gap": round(train_auc - eval_auc, 4),
        "train_rows": len(train_rows),
        "eval_rows": len(eval_rows),
        "examples_per_sec": round(n_seen / dt, 1),
        "data": path,
        "config": dict(config),
    }


def run_cache_ab(name, config, *, steps, warmup):
    """Cached-vs-uncached A/B on one config: identical data + seeds,
    ``plane="a2a"`` vs ``plane="a2a+cache"`` (the hot-row replica cache,
    ``parallel/hot_cache.py``). Reports both planes' examples/s, the
    speedup, and the cache hit rate / ICI-bytes-saved counters sampled
    over a few instrumented steps. ``value`` is the CACHED plane's
    examples/s so ``vs_baseline`` stays comparable with the plain
    ``deepfm_dim9*`` entries.
    """
    import jax
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils import observability as obs

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    data_ax = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = create_mesh(data_ax, n_dev // data_ax)
    batch = config["batch"]
    refresh = int(config.get("cache_refresh_every", 32))
    planes = {}
    stats = {}
    for plane in ("a2a", "a2a+cache"):
        cfg = dict(config, plane=plane)
        features, coll, trainer, mapper = build(cfg, mesh)
        batches = make_batches(cfg, features, mapper)
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batches[0]))
        # warm long enough that at least one admission refresh has landed
        # and the post-refresh programs are compiled
        warm = max(warmup, refresh + 2)
        for i in range(warm):
            state, m = trainer.train_step(state, batches[i % len(batches)])
        jax.block_until_ready(m["loss"])
        block_eps = []
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(steps):
                state, m = trainer.train_step(state,
                                              batches[i % len(batches)])
            jax.block_until_ready(m["loss"])
            block_eps.append(steps * batch / (time.perf_counter() - t0))
        planes[plane] = _median(block_eps)
        if plane == "a2a+cache":
            # instrumented sample OUTSIDE the timed blocks, driven through
            # direct pull/apply calls (the stats gate is part of THOSE
            # programs' cache keys; the trainer's outer step jit was
            # compiled with the gate off and would stay silent — the same
            # contract as the a2a_extra_entries accumulators)
            obs.GLOBAL.reset()
            obs.set_evaluate_performance(True)
            try:
                sb = trainer.shard_batch(batches[0])
                inputs = {k2: v for k2, v in sb["sparse"].items()
                          if k2 in coll.specs}
                rows = coll.pull(state.emb, inputs)
                jax.block_until_ready(jax.tree.leaves(rows))
                emb2 = coll.apply_gradients(state.emb, inputs, rows)
                jax.block_until_ready(jax.tree.leaves(emb2))
                jax.effects_barrier()
                cs = obs.cache_stats()
                del rows, emb2
            finally:
                obs.set_evaluate_performance(False)
            stats = {
                "cache_hit_rate": round(cs["cache_hit_rate"], 4),
                "ici_bytes_saved_per_step":
                    round(cs["ici_bytes_saved"], 1),
            }
        del state
        gc.collect()
    eps = planes["a2a+cache"]
    return {
        "metric": f"{name}_examples_per_sec_{platform}{n_dev}",
        "value": round(eps, 1),
        "unit": "examples/s",
        "vs_baseline": round(eps / n_dev / REF_PER_CHIP, 3),
        "per_chip": round(eps / n_dev, 1),
        "uncached_eps": round(planes["a2a"], 1),
        "cache_speedup": round(eps / planes["a2a"], 3),
        **stats,
        **_hbm_stats(),
        "config": dict(config),
    }


def run_compressed_ab(name, config, *, steps, warmup):
    """Compressed-vs-f32 exchange A/B on one config: identical data +
    seeds on ``plane="a2a"`` vs ``"a2a+bf16"`` (bf16 wire rows both
    directions) vs ``"a2a+int8"`` (bf16 pull + per-row-scale int8
    error-feedback push) — ``parallel/precision.py``. Reports every
    plane's examples/s, the compressed/f32 speedups, the final-loss
    deviation on the shared step stream (quantization honesty), and the
    int8 plane's quantization counters sampled over instrumented steps.

    ``value`` is the fully-compressed (int8) plane's examples/s so
    ``vs_baseline`` stays comparable with the plain ``deepfm_dim9*``
    entries. NOTE the byte claim is NOT this wall-clock number: on the
    shared-memory cpu8 mesh exchange bytes are nearly free, so timing
    flattens or inverts exactly like the cache/grouped A/Bs —
    the halving itself is the compiled-HLO contract ``tools.graftcheck``
    asserts (exchange collective bytes <= 0.55x f32, pull and push
    separately).
    """
    import jax
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils import observability as obs

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    data_ax = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = create_mesh(data_ax, n_dev // data_ax)
    batch = config["batch"]
    planes = {}
    losses = {}
    quant = {}
    for plane in ("a2a", "a2a+bf16", "a2a+int8"):
        cfg = dict(config, plane=plane)
        features, coll, trainer, mapper = build(cfg, mesh)
        batches = make_batches(cfg, features, mapper)
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batches[0]))
        for i in range(max(warmup, 2)):
            state, m = trainer.train_step(state, batches[i % len(batches)])
        jax.block_until_ready(m["loss"])
        block_eps = []
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(steps):
                state, m = trainer.train_step(state,
                                              batches[i % len(batches)])
            jax.block_until_ready(m["loss"])
            block_eps.append(steps * batch / (time.perf_counter() - t0))
        planes[plane] = _median(block_eps)
        losses[plane] = float(m["loss"])
        if plane == "a2a+int8":
            # instrumented sample OUTSIDE the timed blocks (the record
            # gate keys the eager stage programs' jit cache, same
            # contract as the cache/grouped counters)
            obs.GLOBAL.reset()
            obs.set_evaluate_performance(True)
            try:
                sb = trainer.shard_batch(batches[0])
                inputs = {k2: v for k2, v in sb["sparse"].items()
                          if k2 in coll.specs}
                rows = coll.pull(state.emb, inputs)
                jax.block_until_ready(jax.tree.leaves(rows))
                emb2 = coll.apply_gradients(state.emb, inputs, rows)
                jax.block_until_ready(jax.tree.leaves(emb2))
                jax.effects_barrier()
                snap = obs.GLOBAL.snapshot()
                quant = {
                    "quant_error_max": round(
                        snap.get("quant_error_max",
                                 {}).get("count", 0.0), 6),
                    "quant_residual_norm": round(
                        snap.get("quant_residual_norm",
                                 {}).get("count", 0.0), 4),
                }
                del rows, emb2
            finally:
                obs.set_evaluate_performance(False)
                obs.GLOBAL.reset()
        del state
        gc.collect()
    eps = planes["a2a+int8"]
    return {
        "metric": f"{name}_examples_per_sec_{platform}{n_dev}",
        "value": round(eps, 1),
        "unit": "examples/s",
        "vs_baseline": round(eps / n_dev / REF_PER_CHIP, 3),
        "per_chip": round(eps / n_dev, 1),
        "f32_eps": round(planes["a2a"], 1),
        "bf16_eps": round(planes["a2a+bf16"], 1),
        "bf16_speedup": round(planes["a2a+bf16"] / planes["a2a"], 3),
        "int8_speedup": round(eps / planes["a2a"], 3),
        "loss_f32": round(losses["a2a"], 6),
        "loss_drift_bf16": round(abs(losses["a2a+bf16"]
                                     - losses["a2a"]), 6),
        "loss_drift_int8": round(abs(losses["a2a+int8"]
                                     - losses["a2a"]), 6),
        **quant,
        **_hbm_stats(),
        "config": dict(config),
    }


def run_ingest_ab(name, config, *, steps, warmup):
    """Streaming-ingest A/B: the SAME shard data trained from on-disk
    shards through the parallel reader pool (``data/stream.py``) vs
    pre-materialized in-memory batch dicts, both on the a2a plane with
    the fit-style lookahead. This is the first bench where
    the input pipeline is on the critical path (ROADMAP item 5: every
    prior eps number fed synthetic in-memory batches). ``value`` is the
    STREAMED eps; ``stream_vs_mem`` is the honest cost of ingest
    (>= 0.9x is the lane's acceptance bar), and the ``ingest`` section
    carries the stall evidence — ``stall_p95_ms`` must be exactly 0.0
    post-warmup for the "the step never blocks on data" claim (the
    stream records a literal 0.0 for every pop that found data ready).
    Shards regenerate deterministically per seed, so the arms consume
    identical rows; the streamed arm re-walks the shard files each
    epoch (fresh parse + hash every time — the cost under test), the
    in-memory arm cycles the parsed dicts.
    """
    import shutil
    import tempfile
    import jax
    from openembedding_tpu.data import stream as stream_lib
    from openembedding_tpu.parallel.mesh import create_mesh

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    data_ax = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    mesh = create_mesh(data_ax, n_dev // data_ax)
    batch = config["batch"]
    cfg = dict(config, plane=config.get("plane", "a2a"))
    readers = int(config.get("readers", 2))
    ring = int(config.get("ring_batches", 8))
    num_shards = int(config.get("shards", 8))
    shard_rows = int(config.get("shard_rows", 12288))
    shard_dir = tempfile.mkdtemp(prefix="bench_ingest_")
    warm = max(warmup, 3)
    blocks = 3
    try:
        stream_lib.write_synthetic_shards(
            shard_dir, num_shards=num_shards, rows_per_shard=shard_rows,
            fmt="tsv", seed=config.get("seed", 0))
        features, coll, trainer, mapper = build(cfg, mesh)

        def make_stream(epochs):
            return stream_lib.ShardStream(
                shard_dir, batch_size=batch, readers=readers,
                ring_batches=ring, epochs=epochs,
                num_buckets=cfg["vocab"],
                transform=(mapper.fuse_batch if mapper is not None
                           else None),
                add_linear=mapper is None, name="bench_ingest")

        def drive(state, nxt_fn, cur, n):
            """n lookahead-fed steps from ``cur``; returns (state, last
            batch) — the cur/next identity pattern fit would use."""
            for _ in range(n):
                nxt = nxt_fn()
                state, m = trainer.train_step(state, cur,
                                              next_batch=nxt)
                cur = nxt
            jax.block_until_ready(m["loss"])
            return state, cur

        # -- arm A: in-memory (one epoch materialized through the SAME
        # parse path, then cycled as ready dicts)
        s0 = make_stream(epochs=1)
        try:
            mem = list(s0)
        finally:
            s0.close()
        if len(mem) < 2:
            raise RuntimeError(
                f"ingest bench needs >= 2 batches/epoch, got {len(mem)} "
                f"({num_shards}x{shard_rows} rows at batch {batch})")
        mi = {"i": 0}

        def next_mem():
            mi["i"] += 1
            return mem[mi["i"] % len(mem)]

        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(mem[0]))
        state, cur = drive(state, next_mem, mem[0], warm)
        mem_eps = []
        for _ in range(blocks):
            t0 = time.perf_counter()
            state, cur = drive(state, next_mem, cur, steps)
            mem_eps.append(steps * batch / (time.perf_counter() - t0))
        del state
        gc.collect()

        # -- arm B: streamed live from disk (infinite epochs; every
        # batch re-parsed + re-hashed on the reader pool)
        features, coll, trainer, mapper = build(cfg, mesh)
        live = make_stream(epochs=None)
        try:
            it = iter(live)
            first = next(it)
            state = trainer.init(jax.random.PRNGKey(0),
                                 trainer.shard_batch(first))
            state, cur = drive(state, lambda: next(it), first, warm)
            live.reset_stall_stats()   # measured window excludes warmup
            stream_eps = []
            for _ in range(blocks):
                t0 = time.perf_counter()
                state, cur = drive(state, lambda: next(it), cur, steps)
                stream_eps.append(steps * batch
                                  / (time.perf_counter() - t0))
            stalls = live.stall_summary()
            bad = live.bad_rows()
            ring_stats = live.memory_stats()
        finally:
            live.close()
        del state
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)
    eps = _median(stream_eps)
    eps_mem = _median(mem_eps)
    return {
        "metric": f"{name}_examples_per_sec_{platform}{n_dev}",
        "value": round(eps, 1),
        "unit": "examples/s",
        "vs_baseline": round(eps / n_dev / REF_PER_CHIP, 3),
        "per_chip": round(eps / n_dev, 1),
        "eps_min": round(min(stream_eps), 1),
        "eps_max": round(max(stream_eps), 1),
        "mem_eps": round(eps_mem, 1),
        "stream_vs_mem": round(eps / eps_mem, 3),
        "ingest": {
            "stall_p95_ms": round(stalls["p95_ms"], 4),
            "stall_p99_ms": round(stalls["p99_ms"], 4),
            "stall_max_ms": round(stalls["max_ms"], 4),
            "stalled_pops": int(stalls["stalled"]),
            "pops": int(stalls["pops"]),
            "bad_rows": int(bad),
            "readers": readers,
            "ring_batches": int(ring_stats["ring_capacity_batches"]),
            "rows_read": int(ring_stats["rows_read"]),
        },
        **_hbm_stats(),
        "config": dict(config),
    }


def run_plane_parity(name, config, *, steps, warmup):
    """Cross-plane AUC/loss parity: a2a, psum, hybrid (sparse_as_dense),
    and offload planes trained on IDENTICAL data + seeds must agree — the
    strongest correctness statement this single-chip environment can make
    (the reference's analogue: its one-node vs N-node AUC agreement,
    documents/en/benchmark.md). SGD + constant init end-to-end, so the
    planes are exactly comparable (random init folds PRNGs per shard and
    would differ across layouts by construction). ``value`` is the max
    pairwise held-out-AUC spread (0 = exact)."""
    import jax
    import optax
    from openembedding_tpu import (EmbeddingCollection, EmbeddingSpec,
                                   Trainer)
    from openembedding_tpu.hybrid import split_sparse_dense
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu import EmbeddingVariableMeta
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils.observability import StreamingAUC

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    batch, dim, vocab = config["batch"], config["dim"], config["vocab"]
    n_steps = config.get("train_steps", 200)
    feats = ("uid", "item")
    # a real DeepFM head (dim-8 rows + linear columns + MLP) over a 64k
    # zipf id space — round 3's toy (vocab 200, dim 1, LR, cache 80x the
    # vocab) could only prove wiring; at this scale the offload plane's
    # cache is SMALLER than the working set, so eviction + writeback are
    # inside the parity statement
    names = feats + tuple(f + ":linear" for f in feats)
    dims = {n: (1 if n.endswith(":linear") else dim) for n in names}
    rng = np.random.RandomState(0)
    zipf = config.get("zipf_a", 1.05)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = ranks ** -zipf
    probs /= probs.sum()

    def draw():
        return rng.choice(vocab, batch, p=probs).astype(np.int32)

    def make_batch():
        uid, item = draw(), draw()
        # learnable structure with MAIN effects (zero-init embeddings sit
        # on the symmetric saddle of pure-interaction labels)
        label = (((uid % 3 == 0) | (item % 2 == 0))
                 .astype(np.float32))
        return {"label": label, "dense": None,
                "sparse": {n: (uid if n.startswith("uid") else item)
                           for n in names}}

    train = [make_batch() for _ in range(n_steps)]
    held = [make_batch() for _ in range(8)]
    # ONE sgd lr for every parameter — the hybrid plane's embeddings live
    # inside the dense optimizer, so identical dynamics require identical
    # update rules across dense params and sparse rows
    lr = config.get("lr", 0.5)
    opt = {"category": "sgd", "learning_rate": lr}
    init = {"category": "constant", "value": 0.0}

    def eval_auc(trainer, state):
        auc = StreamingAUC()
        for b in held:
            state = trainer.prepare_offload(state, b)
            auc.update(b["label"],
                       np.asarray(trainer.eval_step(state, b)))
        return float(auc.result())

    def bounded_specs(plane):
        return tuple(
            EmbeddingSpec(name=n, input_dim=vocab, output_dim=dims[n],
                          optimizer=opt, initializer=init, plane=plane)
            for n in names)

    cache = config.get("cache", 1 << 13)
    results = {}
    for plane_name in config.get("planes",
                                 ("a2a", "a2a+grouped", "psum", "hybrid",
                                  "offload")):
        mesh = create_mesh(1, n_dev)
        offload = None
        sparse_as_dense = None
        if plane_name in ("a2a", "a2a+grouped", "psum"):
            coll = EmbeddingCollection(bounded_specs(plane_name), mesh)
        elif plane_name == "hybrid":
            sharded, dense_kept = split_sparse_dense(
                bounded_specs("a2a"), sparse_as_dense_size=vocab + 1)
            assert not sharded  # everything small enough to keep dense
            coll = EmbeddingCollection((), mesh)
            sparse_as_dense = dense_kept
        else:  # offload tier over the same bounded id space
            offload = {}
            spec_list = []
            for n in names:
                t = ShardedOffloadedTable(
                    n, EmbeddingVariableMeta(embedding_dim=dims[n],
                                             vocabulary_size=vocab),
                    opt, init, vocab=vocab,
                    cache_capacity=cache, mesh=mesh)
                offload[n] = t
                spec_list.append(t.embedding_spec())
            coll = EmbeddingCollection(tuple(spec_list), mesh)
        trainer = Trainer(deepctr.DeepFM(feature_names=feats),
                          coll, optax.sgd(lr),
                          sparse_as_dense=sparse_as_dense,
                          offload=offload)
        state = trainer.init(jax.random.PRNGKey(7),
                             trainer.shard_batch(train[0]))
        losses = []
        for b in train:
            state, m = trainer.train_step(state, b)
            losses.append(float(m["loss"]))
        entry = {
            "final_loss": round(losses[-1], 6),
            "eval_auc": round(eval_auc(trainer, state), 5),
        }
        if offload:
            for t in offload.values():
                t.finish()
            # the statement must include the eviction/writeback path —
            # a cache bigger than the working set would only prove wiring
            entry["evictions"] = sum(t.evictions for t in offload.values())
        results[plane_name] = entry
        del state
        gc.collect()
        jax.clear_caches()

    aucs = [r["eval_auc"] for r in results.values()]
    losses = [r["final_loss"] for r in results.values()]
    spread = max(aucs) - min(aucs)
    evictions = results.get("offload", {}).get("evictions", 0)
    ok = spread < config.get("tol", 0.01) and (
        "offload" not in results or evictions > 0)
    return {
        "metric": f"{name}_{platform}{n_dev}",
        "value": round(spread, 5),
        "unit": "max_auc_spread",
        "vs_baseline": 1.0 if ok else 0.0,
        "loss_spread": round(max(losses) - min(losses), 6),
        "offload_evictions": evictions,
        "per_plane": results,
        "config": dict(config),
    }


def run_serving_lookup(name, config, *, steps, warmup):
    """Serving data-plane latency: binary (the default) vs JSON lookup on a
    live replica daemon — quantifies why the routed plane is packed bytes
    (the reference's zero-copy RpcView, server/RpcView.h:63-105). The
    replica is a CPU child process (no device involvement)."""
    import shutil
    import socket
    import tempfile
    import jax
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
    from openembedding_tpu import checkpoint as ckpt
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.serving import ha

    mesh = create_mesh(1, 1, jax.devices()[:1])
    dim, batch = config["dim"], config["batch"]
    specs = (EmbeddingSpec(name="emb", input_dim=config["vocab"],
                           output_dim=dim,
                           initializer={"category": "normal",
                                        "stddev": 1.0}),)
    coll = EmbeddingCollection(specs, mesh)
    states = coll.init(jax.random.PRNGKey(0))
    d = tempfile.mkdtemp(prefix="bench_serving_")
    proc = None
    try:
        ckpt.save_checkpoint(d, coll, states, model_sign="bench-serve-1")
        del states
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        # the replica is a CPU child (ha.spawn_replica): this process has
        # touched JAX above and keeps the chip.
        # message_compress=zlib server-side: raw clients still get raw
        # bytes (codec only applies when advertised), so one daemon
        # serves all three modes
        proc = ha.spawn_replica(port, load=[f"bench-serve-1={d}"],
                                compress="zlib")
        ep = f"127.0.0.1:{port}"
        if not ha.wait_ready(ep, sign="bench-serve-1", timeout=300.0):
            raise RuntimeError("bench replica failed to become ready")
        router = ha.RoutingClient([ep], timeout=60.0)
        zrouter = ha.RoutingClient([ep], timeout=60.0, compress="zlib")
        rng = np.random.RandomState(0)
        idx = rng.randint(0, config["vocab"], batch).astype(np.int32)
        out = {}
        for mode, fn in (("bin", router.lookup_bin),
                         ("bin_zlib", zrouter.lookup_bin),
                         ("json", router.lookup_json)):
            fn("bench-serve-1", "emb", idx)  # warm (compile + route)
            times = []
            for _ in range(max(5, min(steps, 30))):
                t0 = time.perf_counter()
                fn("bench-serve-1", "emb", idx)
                times.append(time.perf_counter() - t0)
            out[f"{mode}_ms"] = round(_median(times) * 1e3, 2)
        # bytes on the wire per response (localhost hides the bandwidth
        # win; the ratio is the WAN story — reference RpcView.h:63-105)
        from openembedding_tpu.utils import compress as compress_lib
        rows = np.asarray(router.lookup_bin("bench-serve-1", "emb", idx))
        out["resp_bytes_raw"] = int(rows.nbytes)
        out["resp_bytes_zlib"] = len(
            compress_lib.compress("zlib", rows.tobytes()))
        return {
            "metric": f"{name}",
            "value": out["bin_ms"],
            "unit": "ms/lookup_batch",
            "vs_baseline": round(out["json_ms"]
                                 / max(out["bin_ms"], 1e-9), 2),
            **out,
            "batch": batch,
            "dim": dim,
            "config": dict(config),
        }
    finally:
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                proc.kill()
                proc.wait()
        shutil.rmtree(d, ignore_errors=True)


def run_ckpt_local(name, config, *, steps, warmup):
    """Checkpoint disk throughput: a CPU-backend subprocess on THIS host
    writes/reads a local dump, so no device->host copy is in the number.
    The reference bar is 78 GB / 869 s = 0.09 GB/s
    (documents/en/benchmark.md:52-55)."""
    import os
    import subprocess
    import sys as _sys
    import tempfile
    root = os.path.dirname(os.path.abspath(__file__))
    code = f"""
import sys
sys.path.insert(0, {root!r})
import jax
import json, shutil, tempfile, time
import numpy as np
from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu import checkpoint as ckpt
from openembedding_tpu.parallel.mesh import create_mesh
mesh = create_mesh(1, {config.get("devices", 4)})
specs = (EmbeddingSpec(name="big", input_dim={config["vocab"]},
                       output_dim={config["dim"]},
                       optimizer={{"category": "adagrad",
                                   "learning_rate": 0.01}}),)
coll = EmbeddingCollection(specs, mesh)
states = coll.init(jax.random.PRNGKey(0))
nbytes = sum(x.nbytes for x in jax.tree.leaves(states))
d = tempfile.mkdtemp(prefix="bench_ckpt_local_")
try:
    # two passes, best-of: the first pays compile + cold page cache, and
    # the parent bench process's device client adds host noise
    save_s = load_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ckpt.save_checkpoint(d, coll, states)
        save_s = min(save_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        loaded = ckpt.load_checkpoint(d, coll)
        jax.block_until_ready(jax.tree.leaves(loaded))
        load_s = min(load_s, time.perf_counter() - t0)
        del loaded
finally:
    shutil.rmtree(d, ignore_errors=True)
print(json.dumps({{"gb": nbytes / 1e9, "save_s": save_s,
                   "load_s": load_s}}))
"""
    # a child of a process that may hold the chip is CPU, said here: the
    # chip and the libtpu lockfile belong to one process at a time
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_NUM_CPU_DEVICES=str(config.get("devices", 4)))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([_sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(out.stdout[-500:] + out.stderr[-500:])
    r = json.loads(out.stdout.strip().splitlines()[-1])
    gbps = r["gb"] / max(r["save_s"], 1e-9)
    return {
        "metric": f"{name}_local_disk",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / REF_CKPT_GBPS, 2),
        "ckpt_gb": round(r["gb"], 3),
        "ckpt_save_s": round(r["save_s"], 2),
        "ckpt_load_s": round(r["load_s"], 2),
        "config": dict(config),
    }


def run_ckpt_delta_ab(name, config, *, steps, warmup):
    """Delta-checkpoint A/B on the dim9 table: parallel-writer FULL save
    (vs the serialized writer path on the same window) vs dirty-chunk
    DELTA save (~``dirty_frac`` of rows touched) vs base+chain
    load-replay. A DEVICELESS config: delta bytes and the writer speedup
    are the claims, not a device->host rate."""
    import os
    import shutil
    import tempfile
    import jax
    import jax.numpy as jnp
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
    from openembedding_tpu import checkpoint as ckpt
    from openembedding_tpu import checkpoint_delta as cdel
    from openembedding_tpu.parallel.mesh import create_mesh

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    mesh = create_mesh(1, n_dev)
    vocab, dim = config["vocab"], config["dim"]
    repeats = config.get("repeats", 3)
    dirty_frac = config.get("dirty_frac", 0.05)
    chunks = config.get("chunks", 1024)
    coll = EmbeddingCollection(
        (EmbeddingSpec(name="big", input_dim=vocab, output_dim=dim,
                       optimizer={"category": "adagrad",
                                  "learning_rate": 0.01}),), mesh)
    states = coll.init(jax.random.PRNGKey(0))
    jax.block_until_ready(jax.tree.leaves(states))
    base = tempfile.mkdtemp(prefix="bench_ckpt_delta_")
    try:
        # -- full save: serialized writer baseline, then the parallel pool
        d = os.path.join(base, "serial")
        t0 = time.perf_counter()
        info = ckpt.save_checkpoint(d, coll, states, max_workers=1)
        serial_s = time.perf_counter() - t0
        full_bytes = info["bytes"]
        shutil.rmtree(d)
        full_times = []
        for r in range(repeats):
            d = os.path.join(base, f"full{r}")
            t0 = time.perf_counter()
            ckpt.save_checkpoint(d, coll, states)
            full_times.append(time.perf_counter() - t0)
            shutil.rmtree(d)
        gbps = [full_bytes / t / 1e9 for t in full_times]

        # -- delta save: dirty ~dirty_frac of rows, write only their chunks
        coll.enable_dirty_tracking(target_chunks=chunks)
        ddir = os.path.join(base, "delta")
        ckpt.save_checkpoint(ddir, coll, states, mode="delta", step=0)
        n_dirty = max(1, int(vocab * dirty_frac))
        ids = jnp.arange(n_dirty, dtype=jnp.int32)
        rows = coll.pull(states, {"big": ids}, batch_sharded=False)
        states = coll.apply_gradients(
            states, {"big": ids}, {"big": jnp.ones_like(rows["big"])},
            batch_sharded=False)
        jax.block_until_ready(jax.tree.leaves(states))
        delta_times = []
        delta_bytes = 0
        for r in range(repeats):
            if r:
                # re-mark the same rows: each repeat writes a real delta
                coll.mark_dirty({"big": np.arange(n_dirty)})
            info = cdel.save_delta(
                ddir, coll, states, step=r + 1,
                compact_chain_len=10**6, compact_bytes_ratio=1e18,
                background_compact=False)
            delta_times.append(info["seconds"])
            delta_bytes = info["bytes"]

        # -- load-replay: base + the chain written above
        t0 = time.perf_counter()
        loaded = ckpt.load_checkpoint(ddir, coll)
        jax.block_until_ready(jax.tree.leaves(loaded))
        load_s = time.perf_counter() - t0
        probe = jnp.arange(min(vocab, 4096), dtype=jnp.int32)
        exact = bool((np.asarray(
            coll.pull(states, {"big": probe}, batch_sharded=False)["big"])
            == np.asarray(coll.pull(loaded, {"big": probe},
                                    batch_sharded=False)["big"])).all())
        del loaded
    finally:
        shutil.rmtree(base, ignore_errors=True)
    best = max(gbps)
    return {
        "metric": f"{name}_full_gbps_{platform}{n_dev}",
        "value": round(best, 3),
        "unit": "GB/s",
        "vs_baseline": round(best / REF_CKPT_GBPS, 2),
        "gbps_min": round(min(gbps), 3),
        "gbps_max": round(max(gbps), 3),
        "ckpt_gb": round(full_bytes / 1e9, 3),
        "full_save_s": round(min(full_times), 3),
        "serial_save_s": round(serial_s, 3),
        "parallel_speedup": round(serial_s / min(full_times), 2),
        "delta_save_s": round(min(delta_times), 4),
        "delta_bytes": int(delta_bytes),
        "full_bytes": int(full_bytes),
        "delta_vs_full_bytes": round(full_bytes / max(1, delta_bytes), 1),
        "dirty_frac": dirty_frac,
        "ckpt_delta_gbps": round(delta_bytes / max(min(delta_times), 1e-9)
                                 / 1e9, 3),
        "load_replay_s": round(load_s, 2),
        "replay_exact": exact,
        "config": dict(config),
    }


# The matrix: the reference benchmarks WDL/DeepFM/xDeepFM at dims 9 and 64
# over hashed Criteo ids (benchmark.md). "vocab" is PER FEATURE (26 features
# -> total rows = 26 * vocab): bigvocab lands at 26 * 2^22 ~= 2^26.7 total
# rows (dim 9 + linear + adagrad slots ~= 9 GB HBM) — a non-toy table; the
# OOM guard skips configs the local chip cannot hold.
CONFIGS = {
    "deepfm_dim9": {"model": "deepfm", "dim": 9, "vocab": 1 << 20,
                    "batch": 4096},
    "deepfm_dim9_zipf_bigvocab": {
        "model": "deepfm", "dim": 9, "vocab": 1 << 22, "batch": 4096,
        "zipf": True},
    # cached-vs-uncached A/B: the hot-row replica cache on the zipf
    # headline shape — same data/seeds on plane="a2a" vs "a2a+cache"
    # (parallel/hot_cache.py); value = cached eps, plus speedup + hit rate
    "deepfm_dim9_zipf": {"kind": "cache_ab", "model": "deepfm", "dim": 9,
                         "vocab": 1 << 20, "batch": 4096, "zipf": True,
                         "cache_k": 4096, "cache_refresh_every": 16},
    "deepfm_dim64": {"model": "deepfm", "dim": 64, "vocab": 1 << 18,
                     "batch": 4096, "zipf": True},
    # compressed-vs-f32 exchange A/B (parallel/precision.py): f32 vs
    # bf16-wire vs int8-error-feedback push on the headline shape and on
    # dim64 (where the wire bytes — and so the device-side win — are
    # largest; the halving itself is graftcheck's compiled-HLO contract)
    "deepfm_dim9_compressed_ab": {"kind": "compressed_ab",
                                  "model": "deepfm", "dim": 9,
                                  "vocab": 1 << 20, "batch": 4096,
                                  "zipf": True},
    "deepfm_dim64_compressed_ab": {"kind": "compressed_ab",
                                   "model": "deepfm", "dim": 64,
                                   "vocab": 1 << 18, "batch": 4096,
                                   "zipf": True},
    # streaming-ingest A/B (data/stream.py): the headline shape trained
    # from generated on-disk TSV shards through the parallel reader
    # pool vs the same rows pre-materialized in memory, a2a plane +
    # lookahead both arms; value = streamed eps, plus the
    # stream_vs_mem ratio and post-warmup stall evidence (cpu-window
    # acceptance: >= 0.9x and stall p95 == 0)
    "deepfm_dim9_ingest_ab": {"kind": "ingest_ab", "model": "deepfm",
                              "dim": 9, "vocab": 1 << 20, "batch": 4096,
                              "readers": 2, "shards": 8,
                              "shard_rows": 12288},
    # checkpoint save+load timing on a deliberately small table
    "ckpt_dim9": {"model": "deepfm", "dim": 9, "vocab": 1 << 16,
                  "batch": 4096, "checkpoint": True},
    # hash variables at the DEFAULT (wide, 2^62-capable) key space ...
    "deepfm_dim9_hash": {"model": "deepfm", "dim": 9, "vocab": 1 << 22,
                         "batch": 4096, "zipf": True, "hash": True,
                         "hash_capacity": 1 << 23},
    # ... vs the int32 opt-in — quantifies what the wide default costs
    "deepfm_dim9_hash_int32": {"model": "deepfm", "dim": 9, "vocab": 1 << 22,
                               "batch": 4096, "zipf": True, "hash": True,
                               "hash_capacity": 1 << 23,
                               "key_dtype": "int32"},
    "deepfm_dim9_per_feature": {"model": "deepfm", "dim": 9,
                                "vocab": 1 << 18, "batch": 4096,
                                "fused": False},
    # grouped-exchange A/B against the entry above: IDENTICAL 52-variable
    # per-feature layout (26 dim-9 + 26 dim-1 linear), but the collection
    # batches each dim bucket into ONE routed exchange per step
    # (parallel/grouped.py) instead of one pipeline per table — the
    # heterogeneous-table counterpart of the fused single-table rescue
    "deepfm_dim9_per_feature_grouped": {"model": "deepfm", "dim": 9,
                                        "vocab": 1 << 18, "batch": 4096,
                                        "fused": False,
                                        "plane": "a2a+grouped"},
    "wdl_dim64": {"model": "wdl", "dim": 64, "vocab": 1 << 18,
                  "batch": 4096, "zipf": True},
    "xdeepfm_dim16": {"model": "xdeepfm", "dim": 16, "vocab": 1 << 20,
                      "batch": 2048, "zipf": True},
    # north-star scale: 4x10^8-row host store (~29 GB incl. slot, >> the
    # 16 GB HBM) on disk memmap, HBM cache 2^22 rows, zipf stream
    "offload_bigvocab": {"kind": "offload", "dim": 8, "vocab": 400_000_000,
                         "cache": 1 << 22, "batch": 4096, "zipf_a": 1.08},
    # cache-size -> hit-rate/throughput sweep vs an in-HBM array roofline
    # (moderate 5x10^7-row store so three sweep points stay tractable);
    # value = best sweep point as a fraction of the roofline
    "offload_sweep": {"kind": "offload_sweep", "dim": 8,
                      "vocab": 50_000_000, "batch": 4096, "zipf_a": 1.08,
                      "caches": [1 << 18, 1 << 20, 1 << 22]},
    # lookahead-vs-serial A/B at identical config + the depth curve: what
    # the prepare/step overlap buys, and whether K > 2 buys more when the
    # host half is the long pole (reference prefetch `steps` budget,
    # exb_ops.cpp:148-156)
    "offload_ab_serial": {"kind": "offload", "dim": 8,
                          "vocab": 50_000_000, "cache": 1 << 22,
                          "batch": 4096, "zipf_a": 1.08, "serial": True},
    "offload_ab_k1": {"kind": "offload", "dim": 8, "vocab": 50_000_000,
                      "cache": 1 << 22, "batch": 4096, "zipf_a": 1.08,
                      "depth": 1},
    "offload_ab_k4": {"kind": "offload", "dim": 8, "vocab": 50_000_000,
                      "cache": 1 << 22, "batch": 4096, "zipf_a": 1.08,
                      "depth": 4},
    # hash pull path: bucket-row XLA probe vs fused Pallas kernel vs the
    # array row-gather roofline (dim 128 so the kernel's lane constraint
    # holds); value = XLA probe us, vs_baseline = roofline ratio
    "hash_probe_dim128": {"kind": "hash_probe", "capacity": 1 << 22,
                          "dim": 128, "batch": 32768},
    # held-out AUC on the preprocessed Criteo sample $CRITEO_DATA names
    "auc_criteo": {"kind": "auc", "dim": 9, "batch": 512, "epochs": 3},
    # cross-plane AUC/loss agreement on identical data+seeds (a2a vs psum
    # vs hybrid vs offload): DeepFM head, 64k zipf ids, 200 steps, and an
    # offload cache SMALLER than the working set so eviction/writeback are
    # inside the statement; value = max pairwise eval-AUC spread
    "plane_parity": {"kind": "plane_parity", "dim": 8, "vocab": 1 << 16,
                     "batch": 512, "train_steps": 200, "cache": 1 << 13,
                     "zipf_a": 1.05},
    # checkpoint IO measured on local disk via a CPU subprocess
    "ckpt_local_2gb": {"kind": "ckpt_local", "vocab": 1 << 25, "dim": 8,
                       "devices": 4},
    # delta-checkpoint A/B (checkpoint_delta.py): parallel-writer full
    # save vs serialized writer vs ~5%-dirty delta save vs base+chain
    # load-replay, on the dim9 table shape
    "ckpt_delta_ab": {"kind": "ckpt_delta_ab", "dim": 9, "vocab": 1 << 22,
                      "dirty_frac": 0.05, "chunks": 1024, "repeats": 3},
    # serving data plane: binary (default) vs JSON lookup latency against a
    # live replica daemon; value = binary ms, vs_baseline = json/bin ratio
    "serving_lookup": {"kind": "serving_lookup", "vocab": 1 << 16,
                       "dim": 64, "batch": 4096},
}
HEADLINE = "deepfm_dim9"
RUNNERS = {"offload": run_offload, "offload_sweep": run_offload_sweep,
           "cache_ab": run_cache_ab,
           "compressed_ab": run_compressed_ab,
           "ingest_ab": run_ingest_ab,
           "hash_probe": run_hash_probe,
           "auc": run_auc_criteo, "ckpt_local": run_ckpt_local,
           "ckpt_delta_ab": run_ckpt_delta_ab,
           "serving_lookup": run_serving_lookup,
           "plane_parity": run_plane_parity}


def _utcnow():
    import datetime
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


# configs whose VALUE is not a device number (an AUC, a parity spread, a
# CPU-daemon latency, local-disk GB/s, the ingest A/B's stream/mem ratio):
# the only ones that may run on the CPU backend, and the suite runs them
# there (their metric name records the platform). Every other config
# measures the TPU and fails without one.
DEVICELESS = frozenset({"serving_lookup", "ckpt_local_2gb", "auc_criteo",
                        "plane_parity", "ckpt_delta_ab",
                        "deepfm_dim9_ingest_ab"})


def run_suite_isolated(names, steps, timeout_s=3600, profile=""):
    """Run every config in its OWN child process (``bench.py --configs
    <name>``), one at a time. The parent never imports JAX: a process that
    has touched it holds the chip (and the libtpu lockfile), and a child
    that needs either then fails or hangs. A child per config also gives
    every measurement a fresh backend and a fresh HBM arena.

    A child past ``timeout_s`` is killed and reported as an error: left
    running it would keep the chip, and neither the next config nor the
    command that started the suite would ever get it back.
    """
    import os
    import subprocess
    import sys
    results = []
    for name in names:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--configs", name]
        if steps:
            cmd += ["--steps", str(steps)]
        if profile:
            cmd += ["--profile", profile]
        env = dict(os.environ)
        if name in DEVICELESS:
            env["JAX_PLATFORMS"] = "cpu"
            env["JAX_NUM_CPU_DEVICES"] = "8"
            env.pop("XLA_FLAGS", None)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                env=env)
        try:
            out, err = proc.communicate(timeout=timeout_s)
            line = next((ln for ln in reversed(out.strip().splitlines())
                         if ln.startswith("{")), None)
            if line is not None:
                r = json.loads(line)
            else:
                r = {"metric": name,
                     "error": f"no JSON output (rc={proc.returncode}): "
                              f"{err[-300:]}"}
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            r = {"metric": name,
                 "error": f"config exceeded {timeout_s}s; child killed"}
        except json.JSONDecodeError as e:
            r = {"metric": name, "error": f"unparseable child output: {e}"}
        r.setdefault("ts", _utcnow())
        results.append(r)
        print(json.dumps(r), flush=True)
    return results


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--suite", action="store_true",
                   help="run every config, each in its own subprocess "
                        "(one JSON line each); default runs the headline "
                        "only")
    p.add_argument("--configs", default="",
                   help="comma-separated subset of configs to run "
                        "IN-PROCESS (the per-config child entry point)")
    p.add_argument("--steps", type=int, default=0, help="0 = auto")
    p.add_argument("--timeout", type=int, default=3600,
                   help="per-config wall clock in --suite mode")
    p.add_argument("--profile", default="",
                   help="directory for jax.profiler traces (one block per "
                        "train/offload-throughput config; TensorBoard/"
                        "Perfetto viewable) — the reference benchmark's "
                        "--profile flag")
    p.add_argument("--trace", default="",
                   help="write a graftscope Chrome-trace/Perfetto JSON of "
                        "this invocation's host spans (step/pull/push/"
                        "offload/checkpoint) to this path. Full traces "
                        "come from the in-process modes (--configs / "
                        "headline); --suite children run in subprocesses "
                        "and do not inherit it (the parent's few spans "
                        "are still written). Every bench entry can ship "
                        "its trace.")
    p.add_argument("--trajectory", default="",
                   help="append this invocation's throughput results as "
                        "schema-versioned graftwatch records (git sha + "
                        "hardware fingerprint + eps band) to this JSONL "
                        "path — the same trajectory `python -m "
                        "tools.graftwatch --gate` reads. In-process "
                        "modes only, like --trace.")
    args = p.parse_args(argv)
    if args.profile:
        global PROFILE_DIR
        PROFILE_DIR = args.profile
    if args.trace:
        from openembedding_tpu.analysis import scope as _scope
        _scope.set_tracing(True)

    def _export_trace():
        # every exit path writes the file when --trace was given — a
        # silent no-op (suite mode) would read as "no spans"
        if args.trace:
            from openembedding_tpu.analysis import scope as _scope
            _scope.export_chrome_trace(args.trace)

    if args.suite:
        # the parent stays OFF JAX entirely — only children claim the chip
        # (run_suite_isolated). Device configs first, the CPU tail after.
        ordered = [n for n in CONFIGS if n not in DEVICELESS] \
            + [n for n in CONFIGS if n in DEVICELESS]
        results = run_suite_isolated(ordered, args.steps,
                                     args.timeout, profile=args.profile)
        # parent-process spans only: --suite children are subprocesses
        # and write no trace (documented in --help)
        _export_trace()
        return 1 if any("error" in r for r in results) else 0

    names = ([n.strip() for n in args.configs.split(",") if n.strip()]
             or [HEADLINE])
    import jax
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    platform = jax.devices()[0].platform
    on_device = [n for n in names if n not in DEVICELESS]
    if on_device and platform != "tpu":
        # no CPU stand-in for a device number: fail, naming what was found
        print(f"bench: {on_device} measure the TPU and JAX found platform "
              f"{platform!r}; only {sorted(DEVICELESS)} run on the CPU "
              "backend", file=sys.stderr)
        return 2
    steps = args.steps or (60 if platform == "tpu" else 5)
    warmup = 35 if platform == "tpu" else 1

    def _append_trajectory(results):
        # graftwatch bench trajectory: best-effort conversion — only
        # throughput entries carry the eps band the gate's noise model
        # needs; a conversion failure must not fail the measurement
        if not args.trajectory:
            return
        try:
            from tools import graftwatch
            fp, device = graftwatch.device_fingerprint()
            n = 0
            for r in results:
                rec = graftwatch.record_from_bench(r, fingerprint=fp,
                                                   device=device)
                if rec is not None:
                    graftwatch.append_record(args.trajectory, rec)
                    n += 1
            print(json.dumps({"trajectory": args.trajectory,
                              "records_appended": n}), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"trajectory_error":
                              f"{type(e).__name__}: {e}"}), flush=True)

    results = []
    for name in names:
        try:
            cfg = CONFIGS[name]
            runner = RUNNERS.get(cfg.get("kind"), run_config)
            r = runner(name, cfg, steps=steps, warmup=warmup)
        except Exception as e:  # noqa: BLE001 — a config too big for this
            # chip (OOM) must not kill the rest of the suite
            r = {"metric": name, "error": f"{type(e).__name__}: {e}"}
        finally:
            # drop every compiled program + cached table reference between
            # configs (multi-config in-process runs only)
            gc.collect()
            jax.clear_caches()
            gc.collect()
        results.append(r)
        if args.configs:
            print(json.dumps(r), flush=True)
    if not args.configs:
        print(json.dumps(results[0]))
    _append_trajectory(results)
    _export_trace()
    # a failed config must fail the invocation — a driver/CI gating on the
    # exit status should not see a silent benchmark regression
    return 1 if any("error" in r for r in results) else 0


if __name__ == "__main__":
    sys.exit(main())
