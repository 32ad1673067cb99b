"""Runs one training cell whose table of UNBOUNDED keys lives behind the
offload tier (a keyed host store larger than the chip's memory under a
wide-key HBM cache). Traffic files of ``"kind": "train_offload_keys"``
come here. ``train_offload_runner.py`` is its twin for bounded ids; what
differs is said here.

How a run goes:

1. The traffic generator draws the pool on a thread while JAX reaches the
   chip (``run.py``), as for any training cell.
2. ``offload_keys_system.initial_state`` puts the 1 KiB lead and then the
   two caches on the device before anything else, fills both host stores
   BY KEY from the seed (ranks 1..K of every feature, rows made on the
   device a feature at a time; then, once the pool is drawn, the share of
   its rarer keys the job has met) and warms the cache through the tier's
   own bulk call with ranks 1..``prefill_ranks_per_feature``.
3. The followed steps, with the misses AND the fresh keys they find. Each
   lookup's starting row is the reference's (``reference_offload_keys.
   start_rows``: the seeded row of a stored key, a fresh key's own first
   row); the store's rows of the stored keys among them are read straight
   from the host store and have to be those, bit for bit. Three batches
   of the pool's end are read through the tier before any step: a stored
   key's row from the store, bit for bit, a key no store has seen as its
   initial row (to 4 units in the last place: the reference draws it in
   a program of its own).
   The three steps go through ``Trainer.fit`` one at a time with the
   window's own feed; then the rows are read through the tier, then
   ``flush``, and the store is read again BY KEY. The plain reference
   (``reference_offload_keys.follow``: ``reference.py``'s step over the
   same stored / fresh split) follows the same batches. Beside its three
   gaps the comparison holds to nought ``store_mismatch`` (lookups whose
   store row and cache row differ in any bit, or whose key the store does
   not hold after a flush: a fresh key's trained row has to be there
   under its key) and ``store_keys_shared`` (keys that share a store
   row), in the same three places as the bounded cell.
4. Warm-up, ``gc.freeze()``, then the window: ``Trainer.fit`` over
   ``train_runner.Feed``. The tier's counters and the seconds of its
   spans are read at the window's start and end, its store gauges at the
   end (``context["offload"]``, ``context["offload_store"]``). With the
   clock stopped: ``flush``, then the last three batches of the window
   read through the tier and from the store by key, bit against bit.
5. Peak memory, the step's HLO, the reference, the comparison.
"""

import gc
import json
import os
import resource
import shutil
import time

import jax
import numpy as np

from . import correct, offload_keys_system as system_lib, \
    reference_offload_keys as keyed
from .train_offload_runner import differing
from .train_runner import (CompileCounter, Feed, FOLLOWED_STEPS, OUT_DIR,
                           _norm)


def unheld(cache, store, held):
    """Lookups whose store row is not the cache's row in every bit, or
    whose key the store does not hold."""
    bad = ~held
    for t in cache:
        bad = bad | (cache[t].view(np.uint32)
                     != store[t].view(np.uint32)).any(axis=-1)
    return int(bad.sum())


def far(rows, want, ulps=4):
    """Lookups whose row is further than ``ulps`` units in the last place
    from ``want`` in some element: a fresh key's first row, which the
    reference draws in a program of its own (the same draw, scaled in
    another fusion)."""
    return (np.abs(rows - want) > ulps * np.spacing(
        np.maximum(np.abs(rows), np.abs(want)))).any(axis=-1)


def followed(system, trainer, state, feed_of, raw, prog_batches,
             raw_probe, probe_batches, seed):
    """Drive the first steps through the window's own call and feed, each
    with the misses and fresh keys it finds, and read the program's side
    of the comparison; returns (state, prog, store_mismatch by place,
    store_keys_shared). ``probe_batches`` are read through the tier
    before any step: batches the followed steps and the window do not
    reach."""
    from . import reference
    config = system.config
    first = reference.compact_ids(raw)[4]
    dense0 = jax.device_get(system_lib.dense_leaves(state.params))
    # where every lookup starts: the store's row if the store holds the
    # key (then the store must say so too, bit for bit), else the key's
    # own first row
    start, stored = keyed.start_rows(seed, config, raw)
    store0, held0 = system_lib.store_rows(system, prog_batches)
    mismatch = {"store_at_start": int((held0 != stored).sum()) + sum(
        int((store0[t].view(np.uint32) != start[t].view(np.uint32))
            .any(axis=-1)[stored].sum()) for t in start)}
    # through the tier before any step: a stored key reads its store
    # row, a key no store has seen its initial row
    want, there = keyed.start_rows(seed, config, raw_probe)
    state, probed = system_lib.pull_rows(system, state, probe_batches)
    mismatch["probed_before_steps"] = sum(
        int(np.where(there, (probed[t].view(np.uint32)
                             != want[t].view(np.uint32)).any(axis=-1),
                     far(probed[t], want[t])).sum()) for t in want)
    prog = {"loss": []}
    for t in range(FOLLOWED_STEPS):
        state, last = trainer.fit(state, feed_of(prog_batches[t:t + 1], 1))
        prog["loss"].append(float(last["loss"]))
        if t == 0:
            prog["grad"] = system_lib.first_grad_norms(system, state)
    dense3 = jax.device_get(system_lib.dense_leaves(state.params))
    state, rows3 = system_lib.pull_rows(system, state, prog_batches)
    system_lib.flush(system, state)
    store3, held3 = system_lib.store_rows(system, prog_batches)
    mismatch["after_followed_flush"] = unheld(rows3, store3, held3)
    shared = system_lib.store_keys_shared(system, prog_batches)
    prog["delta"] = {k: _norm(dense3[k] - dense0[k]) for k in dense0}
    prog["store_delta"] = {}
    for table in rows3:
        dim = rows3[table].shape[-1]
        at_first = lambda rows: rows.reshape(-1, dim)[first]
        prog["delta"][table] = _norm(at_first(rows3[table])
                                     - at_first(start[table]))
        prog["store_delta"][table] = _norm(at_first(store3[table])
                                           - at_first(start[table]))
    return state, prog, mismatch, shared


def set_up(config, traffic, inputs, seed, on_device, mark):
    """The system, its first state, the pool as the program takes it and
    the feed's maker: what a run and ``offload_controls`` share."""
    system = system_lib.build(config)
    system.inputs = inputs      # the store's tail waits for the pool
    state = system_lib.initial_state(system, seed, on_device)
    mark("tables")
    raw_pool = inputs.result()
    pool = [system_lib.program_batch(system, b) for b in raw_pool]
    mark("pool")
    probe = jax.jit(lambda x: x + 1)
    lag = system.trainer.pipeline_depth + 1

    def feed_of(batches, steps=None, **kw):
        return Feed(batches, probe, lag=lag,
                    in_flight=traffic["steps_in_flight"], steps=steps, **kw)

    return system, state, raw_pool, pool, feed_of


def compare(config, seed, raw_first, prog, mismatch, shared, failures,
            **kw):
    """(correct, compared, where): the reference follows the same batches."""
    ref = keyed.follow(seed, config, raw_first, **kw)
    values, where = correct.numbers(prog, ref)
    ok, compared = correct.decide(
        values, config["limits"],
        extra=[("insert_failures", failures, 0),
               ("store_mismatch", sum(mismatch.values()), 0),
               ("store_keys_shared", shared, 0)])
    print(json.dumps({"compared_at": where, "program": prog,
                      "reference": ref}), flush=True)
    return ok, compared


def run(cell, config, traffic, inputs, *, seed, seconds, trace, t_process,
        on_device):
    """One run of an offload training cell; returns the result line as a
    dict (and a ``context`` for the per-layer readers under
    ``"_context"``), as ``train_runner.run`` does."""
    counter = CompileCounter()

    def mark(phase):
        print(json.dumps({"set_up": phase, "at_s": round(
            time.perf_counter() - t_process, 2),
            "programs": counter.count, "host_peak_gib": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 2 ** 20, 2)}), flush=True)

    mark("imports")
    system, state, raw_pool, pool, feed_of = set_up(
        config, traffic, inputs, seed, on_device, mark)
    trainer = system.trainer
    raw_first = raw_pool[:FOLLOWED_STEPS]
    state, prog, mismatch, shared = followed(
        system, trainer, state, feed_of, raw_first, pool[:FOLLOWED_STEPS],
        raw_pool[-FOLLOWED_STEPS:], pool[-FOLLOWED_STEPS:], seed)

    def to_the_end(state, last):
        jax.block_until_ready(state)
        return float(last["loss"])

    mark("followed")
    state, last = trainer.fit(state, feed_of(pool[FOLLOWED_STEPS:],
                                             traffic["warmup_steps"]))
    to_the_end(state, last)
    mark("warm")

    from openembedding_tpu.utils import observability
    trace_dir = os.path.join(OUT_DIR, f"{cell}.{seed}.trace")
    at_seconds = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        at_seconds = (
            max(seconds - traffic["trace_seconds"], 0.0),
            lambda: jax.profiler.start_trace(trace_dir,
                                             profiler_options=options))

    gc.collect()
    gc.freeze()
    at_start = {}

    def on_start():
        at_start["stall"] = observability.GLOBAL.snapshot().get(
            "ingest_stall", {})
        at_start["compiles"] = counter.count
        at_start["tier"] = system_lib.tier_counts(system)

    feed = feed_of(pool, seconds=seconds, lead_in=traffic["lead_in_steps"],
                   on_start=on_start, at_seconds=at_seconds)
    with jax.profiler.TraceAnnotation("benchmark.fit"):
        state, last = trainer.fit(state, feed)
    with jax.profiler.TraceAnnotation("benchmark.drain"):
        to_the_end(state, last)
    t_end = time.perf_counter()
    gc.unfreeze()
    trace = trace and feed.called_at is not None
    if trace:
        jax.profiler.stop_trace()
    t_start = feed.started
    compiles = counter.count - at_start["compiles"]
    stall0 = at_start["stall"]
    stall1 = observability.GLOBAL.snapshot().get("ingest_stall", {})
    tier0, tier1 = at_start["tier"], system_lib.tier_counts(system)
    tier = {k: tier1[k] - tier0[k] for k in system_lib.COUNTERS}
    tier.update({k: {f: tier1[k][f] - tier0[k][f] for f in tier1[k]}
                 for k in system_lib.SPANS})
    window_s = t_end - t_start
    steps = feed.handed - traffic["lead_in_steps"]
    done_s = [t - t_start for t in feed.done[traffic["lead_in_steps"]:]]
    store = system_lib.store_gauges(system)
    print(json.dumps({"window_s": window_s, "steps": steps,
                      "train_compiles_in_window": compiles,
                      "offload": tier, "store": store,
                      "filled": system.filled}), flush=True)

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell}.{seed}.steps.json"), "w") as f:
        json.dump({"cell": cell, "seed": seed, "window_s": window_s,
                   "steps": steps, "waited_s": feed.waited_s,
                   "step_done_s": done_s}, f)

    peaks = [d.memory_stats() for d in system.mesh.devices.flat]
    memory_peak = max((p or {}).get("peak_bytes_in_use", 0) for p in peaks)
    # the tier as the window drove it, held to its guarantees
    tail = [pool[i % len(pool)]
            for i in range(feed.handed - FOLLOWED_STEPS, feed.handed)]
    system_lib.flush(system, state)
    state, cached = system_lib.pull_rows(system, state, tail)
    mismatch["after_window_flush"] = unheld(
        cached, *system_lib.store_rows(system, tail))
    print(json.dumps({"store_mismatch_at": mismatch}), flush=True)
    shared += system_lib.store_keys_shared(system, tail)
    mark("after_window")
    failures = system_lib.insert_failures(system, state.emb)
    step_hlo = None
    if trace:       # kept beside the trace: it names the trace's operations
        step_hlo = system_lib.step_hlo(system, state, pool[0])
        with open(os.path.join(OUT_DIR, f"{cell}.{seed}.step.hlo.txt"),
                  "w") as f:
            f.write(step_hlo)
    del state, last, pool                      # the caches leave the device
    ok, compared = compare(config, seed, raw_first, prog, mismatch, shared,
                           failures)

    end_to_end = {
        "examples_per_s": {
            "value": steps * config["batch"] / window_s if on_device
            else None, "unit": "examples/s"},
        "setup_s": {"value": t_start - t_process if on_device else None,
                    "unit": "s"},
    }
    context = {
        "cell": cell, "config": config, "traffic": traffic,
        "on_device": on_device, "window_s": window_s, "steps": steps,
        "waited_s": feed.waited_s,
        "device_kind": jax.devices()[0].device_kind,
        "step_done_s": done_s,
        "compiles_in_window": compiles,
        "ingest_stall_s": stall1.get("seconds", 0.0)
        - stall0.get("seconds", 0.0) - feed.waited_s,
        "memory_peak_bytes": memory_peak,
        "raw_window_batches": [raw_pool[i % len(raw_pool)]
                               for i in range(min(steps, len(raw_pool)))],
        "trace_dir": trace_dir if trace else None, "step_hlo": step_hlo,
        "feed_call_at_s": feed.called_at,
        "offload": tier, "offload_store": store,
    }
    return {"correct": ok, "attempted": steps, "failed": failures,
            "metrics": end_to_end, "memory_peak_bytes": memory_peak,
            "compared": compared, "_context": context}
