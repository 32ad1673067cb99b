"""Offload-tier diagnosis suite: one harness, one subcommand per probe.

Consolidates the seven-stage diagnosis trail (offload_diag.py +
offload_diag2-7.py) behind a single CLI; each subcommand reproduces one
stage's measurement on the live backend:

    python -m tools.offload_diag transfers   # h2d bandwidth + tiny-d2h RTT
    python -m tools.offload_diag steps       # all-hit step: fresh vs reused batches
    python -m tools.offload_diag inserts     # insert program cost, per-iter + resubmit
    python -m tools.offload_diag phases      # device-blocked per-piece timings
    python -m tools.offload_diag serial      # serial path: apply/h2d/step/note per iter
    python -m tools.offload_diag isolate     # A/B/C loops: h2d-only / step-only / insert+put
    python -m tools.offload_diag puts        # N-small-puts vs one-big-put fixed overhead
    python -m tools.offload_diag pipeline    # steady-state host-call stalls + breakdown

What the stages exist to separate: payload bytes from per-call fixed
overhead. Every HOST-BLOCKING device call has a fixed cost regardless of
size (``puts``), and a per-step deferred-overflow read makes that cost the
tier's per-step blocker (hence join-point-only overflow reads +
``overflow_check_every_n_batches``). A 16-batch warmup covers only ~28% of
the 200k-id hot set, so ``steps`` after it still carries insert traffic.

Every subcommand also runs on CPU for plumbing checks (numbers are then
about the CPU backend, not the tier).
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

VOCAB, CACHE_CAP, DIM, BATCH = 2_000_000, 1 << 22, 8, 4096
MISS = 1700   # the bench's steady-state per-batch miss count


def timeit(fn, n=20, warmup=3):
    for _ in range(warmup):
        r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


class Harness:
    """The shared fixture: 2M-row offloaded uid (+:linear) tables plus an
    in-HBM ctx pair under a deepfm Trainer — the bench's offload shape."""

    def __init__(self, pipeline_depth=2):
        import optax
        from openembedding_tpu import (EmbeddingCollection, EmbeddingSpec,
                                       EmbeddingVariableMeta, Trainer)
        from openembedding_tpu.models import deepctr
        from openembedding_tpu.offload import ShardedOffloadedTable
        from openembedding_tpu.parallel.mesh import create_mesh

        self.mesh = create_mesh(1, len(jax.devices()))
        opt = {"category": "adagrad", "learning_rate": 0.01}
        init = {"category": "constant", "value": 0.01}
        self.table = ShardedOffloadedTable(
            "uid", EmbeddingVariableMeta(embedding_dim=DIM,
                                         vocabulary_size=VOCAB),
            opt, init, vocab=VOCAB, cache_capacity=CACHE_CAP,
            mesh=self.mesh)
        self.lin = ShardedOffloadedTable(
            "uid:linear", EmbeddingVariableMeta(embedding_dim=1,
                                                vocabulary_size=VOCAB),
            opt, init, vocab=VOCAB, cache_capacity=CACHE_CAP,
            mesh=self.mesh)
        specs = (self.table.embedding_spec(), self.lin.embedding_spec(),
                 EmbeddingSpec(name="ctx", input_dim=100_000,
                               output_dim=DIM, optimizer=opt),
                 EmbeddingSpec(name="ctx:linear", input_dim=100_000,
                               output_dim=1, optimizer=opt))
        coll = EmbeddingCollection(specs, self.mesh)
        self.trainer = Trainer(
            deepctr.build_model("deepfm", ("uid", "ctx")), coll,
            optax.adagrad(0.01),
            offload={"uid": self.table, "uid:linear": self.lin},
            pipeline_depth=pipeline_depth)
        self.rng = np.random.RandomState(0)

    def batch_from(self, uid):
        ctx = (uid * 7 % 100_000).astype(np.int32)
        return {"label": (uid % 4 == 0).astype(np.float32),
                "dense": np.tile((uid % 13).astype(np.float32)[:, None],
                                 (1, 13)),
                "sparse": {"uid": uid, "uid:linear": uid,
                           "ctx": ctx, "ctx:linear": ctx}}

    def hot_batch(self, hi=30_000):
        return self.batch_from(
            self.rng.randint(0, hi, BATCH).astype(np.int32))

    def miss_batch(self, i, hot_hi=30_000, cold_base=40_000):
        """~MISS new ids per batch on top of a resident hot head."""
        hot = self.rng.randint(0, hot_hi, BATCH - MISS).astype(np.int32)
        new = np.arange(cold_base + i * MISS, cold_base + (i + 1) * MISS,
                        dtype=np.int32)
        return self.batch_from(np.concatenate([hot, new]))

    def warm(self, steps=3, mk=None):
        mk = mk or self.hot_batch
        state = self.trainer.init(jax.random.PRNGKey(0),
                                  self.trainer.shard_batch(mk()))
        m = None
        for _ in range(steps):
            state, m = self.trainer.train_step(state, mk())
        if m is not None:
            jax.block_until_ready(m["loss"])
        self.table.check_overflow()
        self.lin.check_overflow()
        return state


# --- subcommands -------------------------------------------------------------

def cmd_transfers(_args):
    """Stage 1-2: raw h2d bandwidth (fresh buffers) + tiny-d2h latency."""
    dev = jax.devices()[0]
    print(f"platform={dev.platform}")
    for mb in (0.0625, 0.5, 4.0):
        nbytes = int(mb * (1 << 20))
        bufs = [np.random.rand(nbytes // 8).astype(np.float64)
                for _ in range(8)]
        i = [0]

        def put():
            i[0] += 1
            return jax.device_put(bufs[i[0] % len(bufs)], dev)
        dt = timeit(put)
        print(f"h2d {mb:7.4f} MB: {dt*1e3:8.2f} ms  "
              f"{mb/1024/dt:8.3f} GB/s")
    c = jnp.int32(7) + 1

    def get():
        return int(jax.device_get(c))
    dt = timeit(lambda: jnp.asarray(get()))
    print(f"d2h tiny round trip: {dt*1e3:.2f} ms")


def cmd_steps(_args):
    """Stage 3-4: train step over a resident working set, fresh batches
    vs reused np arrays (isolates fresh-h2d cost). NOTE the all-hit
    label is approximate: warmup covers ~28% of the 200k hot set."""
    h = Harness()
    hot = h.rng.randint(0, 200_000, size=(64, BATCH)).astype(np.int32)

    def mk(i):
        return h.batch_from(hot[i % len(hot)])
    state = h.trainer.init(jax.random.PRNGKey(0),
                           h.trainer.shard_batch(mk(0)))
    m = None
    for i in range(16):
        state, m = h.trainer.train_step(state, mk(i))
    jax.block_until_ready(m["loss"])

    fresh = [mk(i) for i in range(16, 48)]
    t0 = time.perf_counter()
    for b in fresh:
        state, m = h.trainer.train_step(state, b)
    jax.block_until_ready(m["loss"])
    per = (time.perf_counter() - t0) / len(fresh)
    print(f"all-hit step, fresh batches:  {per*1e3:8.2f} ms "
          f"({BATCH/per:,.0f} ex/s)")

    reuse = fresh[:4]
    t0 = time.perf_counter()
    for i in range(32):
        state, m = h.trainer.train_step(state, reuse[i % 4])
    jax.block_until_ready(m["loss"])
    per = (time.perf_counter() - t0) / 32
    print(f"all-hit step, reused batches: {per*1e3:8.2f} ms "
          f"({BATCH/per:,.0f} ex/s)")


def cmd_inserts(_args):
    """Stage 5 + diag3: the device insert program alone — batch cost at
    the steady-state miss count, per-iteration trace (recompile check),
    and an all-present resubmit (pure probe, no insert)."""
    h = Harness()
    cache = h.table.create_cache()
    jax.block_until_ready(cache.keys)
    for i in range(12):
        ids = np.arange(1000 + i * MISS, 1000 + (i + 1) * MISS,
                        dtype=np.int32)
        t0 = time.perf_counter()
        cache = h.table._insert_from_host(cache, ids)
        jax.block_until_ready(cache.keys)
        print(f"iter {i:2d}: {1e3*(time.perf_counter()-t0):8.2f} ms")
    ids = np.arange(1000, 1000 + MISS, dtype=np.int32)
    t0 = time.perf_counter()
    cache = h.table._insert_from_host(cache, ids)
    jax.block_until_ready(cache.keys)
    print(f"resubmit (all present): "
          f"{1e3*(time.perf_counter()-t0):8.2f} ms")
    h.table._overflow_latest = None

    # prepared-batch path through both tables (host_prepare + apply)
    state = h.warm(steps=3)
    emb = dict(state.emb)
    cold = np.arange(1_000_000, 1_000_000 + 64 * MISS,
                     dtype=np.int32).reshape(64, MISS)
    t0 = time.perf_counter()
    n = 16
    for i in range(n):
        ids = cold[i % 64]
        for t in (h.table, h.lin):
            prep = t.host_prepare(ids)
            emb[t.name] = t.apply_prepared(emb[t.name], prep)
    jax.block_until_ready(emb["uid"].keys)
    per = (time.perf_counter() - t0) / n
    print(f"host_prepare+apply both tables ({MISS} misses): "
          f"{per*1e3:8.2f} ms")
    h.table.check_overflow()
    h.lin.check_overflow()


def cmd_phases(_args):
    """Stage diag2: every piece device-blocked per call — insert program,
    jitted step (blocked + async), shard_batch h2d, zero-miss apply."""
    h = Harness()

    def mk():
        return h.batch_from(
            h.rng.randint(0, 50_000, BATCH).astype(np.int32))
    state = h.trainer.init(jax.random.PRNGKey(0),
                           h.trainer.shard_batch(mk()))
    m = None
    for _ in range(14):   # make [0, 50k) resident
        state, m = h.trainer.train_step(state, mk())
    jax.block_until_ready(m["loss"])
    h.table.check_overflow()
    h.lin.check_overflow()

    emb = dict(state.emb)
    n = 16
    t0 = time.perf_counter()
    for i in range(n):
        ids = np.arange(100_000 + i * MISS, 100_000 + (i + 1) * MISS,
                        dtype=np.int32)
        emb["uid"] = h.table._insert_from_host(emb["uid"], ids)
        jax.block_until_ready(emb["uid"].keys)
    per = (time.perf_counter() - t0) / n
    print(f"a) insert {MISS} rows, device-blocked:    {per*1e3:8.2f} ms")
    h.table._overflow_latest = None

    bt = [mk() for _ in range(8)]
    sb = [h.trainer.shard_batch(b) for b in bt]
    t0 = time.perf_counter()
    for i in range(16):
        state, m = h.trainer._train_step(state, sb[i % 8])
        jax.block_until_ready(m["loss"])
    per = (time.perf_counter() - t0) / 16
    print(f"b) jitted step, presharded, blocked:    {per*1e3:8.2f} ms")
    t0 = time.perf_counter()
    for i in range(16):
        state, m = h.trainer._train_step(state, sb[i % 8])
    jax.block_until_ready(m["loss"])
    per = (time.perf_counter() - t0) / 16
    print(f"b2) jitted step, presharded, async:     {per*1e3:8.2f} ms")

    t0 = time.perf_counter()
    for i in range(16):
        out = h.trainer.shard_batch(bt[i % 8])
        jax.block_until_ready(jax.tree.leaves(out))
    per = (time.perf_counter() - t0) / 16
    print(f"c) shard_batch h2d, blocked:            {per*1e3:8.2f} ms")

    t0 = time.perf_counter()
    for i in range(16):
        prep = h.table.host_prepare(bt[i % 8]["sparse"]["uid"])
        emb2 = h.table.apply_prepared(state.emb["uid"], prep)
        jax.block_until_ready(jax.tree.leaves(emb2))
    per = (time.perf_counter() - t0) / 16
    print(f"d) prepare+apply, zero misses, blocked: {per*1e3:8.2f} ms")


def cmd_serial(_args):
    """Stage diag4: the serial path per-phase — apply_prepared /
    shard_batch / jitted step / note_update, per iteration (run with
    jax_log_compiles to spot recompiles)."""
    h = Harness()
    state = h.trainer.init(jax.random.PRNGKey(0),
                           h.trainer.shard_batch(h.miss_batch(0)))
    m = None
    for i in range(6):
        state, m = h.trainer.train_step(state, h.miss_batch(i + 1))
    jax.block_until_ready(m["loss"])
    print("--- warmup done; per-phase timing (serial path) ---",
          flush=True)
    for i in range(8):
        b = h.miss_batch(100 + i)
        t0 = time.perf_counter()
        state2, uniqs = h.trainer._apply_prepared_offload(state, b)
        jax.block_until_ready(jax.tree.leaves(state2.emb["uid"].keys))
        t1 = time.perf_counter()
        sb = h.trainer.shard_batch(b)
        jax.block_until_ready(jax.tree.leaves(sb))
        t2 = time.perf_counter()
        state3, m = h.trainer._train_step(state2, sb)
        jax.block_until_ready(m["loss"])
        t3 = time.perf_counter()
        for name, t in h.trainer.offload.items():
            t.note_update(b["sparse"][name], uniq=uniqs.get(name))
        t4 = time.perf_counter()
        state = state3
        print(f"iter {i}: apply={1e3*(t1-t0):7.2f}  h2d={1e3*(t2-t1):6.2f}"
              f"  step={1e3*(t3-t2):7.2f}  note={1e3*(t4-t3):6.2f} ms",
              flush=True)


def cmd_isolate(_args):
    """Stage diag5: three loops isolating the ~105 ms per-device-call
    collapse — fresh-batch h2d only, step only (reused presharded),
    insert only alternating with a 500 KB put."""
    h = Harness()
    state = h.warm(steps=3)
    print("A) fresh-batch h2d only:", flush=True)
    for i in range(20):
        b = h.hot_batch()
        t0 = time.perf_counter()
        sb = h.trainer.shard_batch(b)
        jax.block_until_ready(jax.tree.leaves(sb))
        print(f"  {i:2d}: {1e3*(time.perf_counter()-t0):7.2f} ms",
              flush=True)
    print("B) step only, reused presharded batch:", flush=True)
    sb = h.trainer.shard_batch(h.hot_batch())
    for i in range(20):
        t0 = time.perf_counter()
        state, m = h.trainer._train_step(state, sb)
        jax.block_until_ready(m["loss"])
        print(f"  {i:2d}: {1e3*(time.perf_counter()-t0):7.2f} ms",
              flush=True)
    print("C) insert only, fresh keys + fresh 500KB h2d:", flush=True)
    emb = dict(state.emb)
    for i in range(20):
        ids = np.arange(50_000 + i * MISS, 50_000 + (i + 1) * MISS,
                        dtype=np.int32)
        filler = np.random.rand(4096, 32).astype(np.float32)
        t0 = time.perf_counter()
        d = jax.device_put(filler)
        emb["uid"] = h.table._insert_from_host(emb["uid"], ids)
        jax.block_until_ready([d, emb["uid"].keys])
        print(f"  {i:2d}: {1e3*(time.perf_counter()-t0):7.2f} ms",
              flush=True)
    h.table._overflow_latest = None


def cmd_puts(_args):
    """Stage diag6: per-transfer fixed overhead — do N small puts cost
    ~N x one big put of the same total bytes? (Enter the trainer's
    degraded mode first, then measure.)"""
    h = Harness()
    h.warm(steps=3)
    print("degraded-mode entered (trainer warm)", flush=True)
    kb = 40  # ~12 arrays x 40 KB = the offload step's transfer profile
    for label, n_arrays in (("12 x 40KB", 12), ("1 x 480KB", 1),
                            ("3 x 160KB", 3)):
        per_bytes = kb * 1024 * 12 // n_arrays
        times = []
        for _it in range(8):
            bufs = [np.random.randint(0, 1 << 30, per_bytes // 4)
                    .astype(np.int32) for _ in range(n_arrays)]
            t0 = time.perf_counter()
            out = [jax.device_put(b) for b in bufs]
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        times.sort()
        print(f"{label}: median {1e3*times[len(times)//2]:7.2f} ms "
              f"(min {1e3*times[0]:.2f}, max {1e3*times[-1]:.2f})",
              flush=True)
    bufs = [np.random.randint(0, 1 << 30, kb * 256).astype(np.int32)
            for _ in range(24)]
    t0 = time.perf_counter()
    out = [jax.device_put(b) for b in bufs]
    jax.block_until_ready(out)
    print(f"24 x 40KB async batch: {1e3*(time.perf_counter()-t0):7.2f} ms "
          f"total", flush=True)


def cmd_pipeline(_args):
    """Stage diag7: the REAL loop with no explicit blocks — which host
    call stalls? Plus a per-call apply_prepared/check_overflow
    breakdown via monkeypatched timers."""
    h = Harness(pipeline_depth=1)
    state = h.trainer.init(jax.random.PRNGKey(0),
                           h.trainer.shard_batch(h.miss_batch(0)))
    m = None
    for i in range(12):  # past the overflow-check depth: steady state
        state, m = h.trainer.train_step(state, h.miss_batch(i + 1))
    jax.block_until_ready(m["loss"])
    print("steady state reached; timing host calls (NO explicit blocks)",
          flush=True)
    timed = [h.miss_batch(100 + i) for i in range(24)]
    t_total0 = time.perf_counter()
    rows = []
    for i, b in enumerate(timed):
        t0 = time.perf_counter()
        h.trainer.prefetch(timed[i:i + 2])
        t1 = time.perf_counter()
        state, uniqs = h.trainer._apply_prepared_offload(state, b)
        t2 = time.perf_counter()
        sb = h.trainer.shard_batch(b)
        t3 = time.perf_counter()
        state, m = h.trainer._train_step(state, sb)
        t4 = time.perf_counter()
        for name, t in h.trainer.offload.items():
            t.note_update(b["sparse"][name], uniq=uniqs.get(name))
        t5 = time.perf_counter()
        rows.append((t1 - t0, t2 - t1, t3 - t2, t4 - t3, t5 - t4))
    jax.block_until_ready(m["loss"])
    total = time.perf_counter() - t_total0
    print("  prefetch   apply    h2d   stepdisp  note  (ms)")
    for r in rows:
        print("  " + "  ".join(f"{1e3*x:7.2f}" for x in r))
    print(f"TOTAL {1e3*total/len(timed):.2f} ms/step", flush=True)

    import openembedding_tpu.offload as off
    orig_apply = off.ShardedOffloadedTable.apply_prepared
    orig_co = off.ShardedOffloadedTable.check_overflow

    def timed_apply(self, cache, prep):
        t0 = time.perf_counter()
        out = orig_apply(self, cache, prep)
        print(f"    apply_prepared[{self.name}]: "
              f"{1e3*(time.perf_counter()-t0):.2f} ms", flush=True)
        return out

    def timed_co(self, cache=None):
        t0 = time.perf_counter()
        out = orig_co(self, cache)
        print(f"      check_overflow[{self.name}] live={cache is not None}"
              f": {1e3*(time.perf_counter()-t0):.2f} ms", flush=True)
        return out
    off.ShardedOffloadedTable.apply_prepared = timed_apply
    off.ShardedOffloadedTable.check_overflow = timed_co
    try:
        print("--- per-call breakdown, 4 steps ---", flush=True)
        extra = [h.miss_batch(200 + i) for i in range(4)]
        for i, b in enumerate(extra):
            h.trainer.prefetch(extra[i:i + 2])
            state, m = h.trainer.train_step(state, b)
        jax.block_until_ready(m["loss"])
    finally:
        off.ShardedOffloadedTable.apply_prepared = orig_apply
        off.ShardedOffloadedTable.check_overflow = orig_co


COMMANDS = {
    "transfers": cmd_transfers,
    "steps": cmd_steps,
    "inserts": cmd_inserts,
    "phases": cmd_phases,
    "serial": cmd_serial,
    "isolate": cmd_isolate,
    "puts": cmd_puts,
    "pipeline": cmd_pipeline,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="offload-tier per-step cost localization")
    ap.add_argument("command", choices=sorted(COMMANDS),
                    help="which probe to run (see module docstring)")
    ap.add_argument("--log_compiles", action="store_true",
                    help="enable jax_log_compiles during the probe")
    args = ap.parse_args(argv)
    if args.log_compiles:
        import logging
        jax.config.update("jax_log_compiles", True)
        logging.basicConfig(level=logging.WARNING)
    COMMANDS[args.command](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
