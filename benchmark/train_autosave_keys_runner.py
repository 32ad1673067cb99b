"""Runs one training cell that saves hash tables while it trains: the hash
cell's step through ``Trainer.fit(autosave_every=, autosave_dir=)`` over a
delta chain that is exact to the key. Traffic files of ``"kind":
"train_autosave_keys"`` come here.

What differs from ``train_autosave_runner.py``, whose steps these are:

1. ``autosave_keys_system.arm`` also refuses a program whose hash tracker
   is not exact to the key, before anything is built on the device.
2. The warm save marks the keys of the first period's batches. Some of
   them the table does not hold yet (the pool's first pass brings fresh
   keys, and marking ahead of the push inserts nothing): the save has to
   leave exactly those out (``counts_chain_keys.held_after`` says which,
   from the ranks of the raw batches) and count them.
3. The window's ``lead_in_steps`` + ``window_periods`` periods lie in the
   pool's first pass but for their last steps (the traffic file says how
   many), so every in-window save carries keys that training inserted
   since the save before.
4. In the array runner's order (the compactor joined, the window's
   entries counted, then the runner's own save of the last steps, which
   asks for no fold) the chain is replayed BY KEY (``reference_chain_keys.py``) and
   held to the live table, read back slot by slot: ``chain_mismatch_rows``
   (keys whose weights or any accumulator differ in any bit),
   ``chain_missing_keys`` (live keys the replay lacks),
   ``chain_extra_keys`` (replayed keys the table lacks) and
   ``chain_rows_off`` (entries whose key count is not the distinct keys
   the feed handed out since the entry before) all have to be nought. The
   keys each entry brought to the chain are printed, not compared. A
   rehearsal (``tiny_hash_ckpt``) also holds an entry to the table as it
   stood at the entry's own step (``chain_late_rows``).
"""

import gc
import json
import os
import shutil
import time

import jax

from . import (autosave_keys_system as keys_system, correct,
               counts_chain_keys, reference, reference_chain_keys,
               system as system_lib)
from .train_autosave_runner import (_to_the_end, entries_off, fed,
                                    save_the_tail, settle, window_batches)
from .train_runner import (CompileCounter, Feed, FOLLOWED_STEPS, OUT_DIR,
                           _followed)


def chain_faults(path, system, emb, entries=None):
    """``reference_chain_keys.compare`` of the chain against ``emb``."""
    return reference_chain_keys.compare(
        path, keys_system.live_reader(system, emb),
        keys_system.slots(system), entries=entries)


def at_step(config, seed, on_device, raw_pool, traffic, steps, path, entry):
    """Keys in which the chain's first ``entry`` entries differ from the
    table as it stood after ``steps`` steps of the window's call (rows
    that differ, keys missing, keys extra): a second system follows the
    run's batches without a save and stops there."""
    system = system_lib.build(config)
    state = system_lib.initial_state(system, seed, on_device)
    pool = [system_lib.program_batch(system, b) for b in raw_pool]
    probe = jax.jit(lambda x: x + 1)

    def feed_of(batches, n):
        return Feed(batches, probe, lag=system.trainer.pipeline_depth + 1,
                    in_flight=traffic["steps_in_flight"], steps=n)

    for t in range(FOLLOWED_STEPS):
        state, _ = system.trainer.fit(state, feed_of(pool[t:t + 1], 1))
    state, _ = system.trainer.fit(
        state, feed_of(pool[FOLLOWED_STEPS:], traffic["warmup_steps"]))
    state, _ = system.trainer.fit(state, feed_of(pool, steps))
    found = chain_faults(path, system, state.emb, entries=entry)
    return found["mismatch_rows"] + found["missing_keys"] \
        + found["extra_keys"]


def run(cell, config, traffic, inputs, *, seed, seconds, trace, t_process,
        on_device, plant=None):
    """One run of a hash autosave cell; returns the result line as a dict
    (and a ``context`` for the per-layer readers under ``"_context"``), as
    ``train_runner.run`` does. ``seconds`` is not read: the window's
    length is the configuration's ``window_periods``. ``plant(system)``
    plants a fault before anything trains (``autosave_keys_controls``)."""
    counter = CompileCounter()

    def mark(phase):
        print(json.dumps({"set_up": phase, "at_s": round(
            time.perf_counter() - t_process, 2),
            "programs": counter.count}), flush=True)

    mark("imports")
    system = system_lib.build(config)
    trainer = system.trainer
    keys_system.arm(system)
    if plant:
        plant(system)
    state = system_lib.initial_state(system, seed, on_device)
    mark("tables")
    raw_pool = inputs.result()
    pool = [system_lib.program_batch(system, b) for b in raw_pool]
    mark("pool")
    probe = jax.jit(lambda x: x + 1)
    lag = trainer.pipeline_depth + 1

    def feed_of(batches, steps=None, **kw):
        return Feed(batches, probe, lag=lag,
                    in_flight=traffic["steps_in_flight"], steps=steps, **kw)

    raw_first = raw_pool[:FOLLOWED_STEPS]
    state, prog = _followed(system, trainer, state, feed_of, raw_first,
                            pool[:FOLLOWED_STEPS])
    mark("followed")
    state, last = trainer.fit(state, feed_of(pool[FOLLOWED_STEPS:],
                                             traffic["warmup_steps"]))
    _to_the_end(state, last)
    mark("warm")
    ckpt_dir = keys_system.save_dir(config, OUT_DIR)
    try:
        return _window(cell, config, traffic, seed, trace, t_process,
                       on_device, counter, mark, system, state, raw_pool,
                       pool, feed_of, prog, ckpt_dir)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _window(cell, config, traffic, seed, trace, t_process, on_device,
            counter, mark, system, state, raw_pool, pool, feed_of, prog,
            ckpt_dir):
    """The base and the warm save, the window, the chain's comparison."""
    every = config["checkpoint"]["autosave_every"]
    lead_in = traffic["lead_in_steps"]
    to_hand, traced_from = window_batches(config, traffic)
    trainer = system.trainer
    trained = FOLLOWED_STEPS + traffic["warmup_steps"]
    base = keys_system.save(system, state, ckpt_dir, trained)
    mark("base")
    for batch in pool[:every]:
        keys_system.mark(system, batch)
    before = keys_system.counts()
    warm = keys_system.save(system, state, ckpt_dir, trained)
    marked = counts_chain_keys.distinct_keys(raw_pool[:every])
    expected = [counts_chain_keys.distinct_keys(
        raw_pool[:every],
        held=counts_chain_keys.held_after(config, raw_pool[:trained]))]
    absent = keys_system.delta(before, keys_system.counts())[
        keys_system.KEYS_ABSENT]
    tables = len(system.coll.specs)
    if warm.get("rows") != expected[0] * tables \
            or absent != (marked - expected[0]) * tables:
        raise SystemExit(       # before a window of such saves
            f"benchmark: the warm save carried {warm.get('rows')} keys and "
            f"left out {absent}; of the {marked} marked a table the table "
            f"holds {expected[0]}")
    print(json.dumps({"ckpt_dir": ckpt_dir, "base": base, "warm": dict(
        {k: warm[k] for k in ("mode", "seq", "rows", "bytes", "seconds")},
        keys_absent=absent)}), flush=True)
    mark("saves_warm")

    from openembedding_tpu.utils import observability
    trace_dir = os.path.join(OUT_DIR, f"{cell}.{seed}.trace")
    trace_at = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        trace_at = (
            traced_from,
            lambda: jax.profiler.start_trace(trace_dir,
                                             profiler_options=options))

    gc.collect()
    gc.freeze()
    at_start = {}

    def on_start():
        at_start["stall"] = observability.GLOBAL.snapshot().get(
            "ingest_stall", {})
        at_start["compiles"] = counter.count
        at_start["saves"] = keys_system.counts()

    feed = feed_of(pool, to_hand, lead_in=lead_in, on_start=on_start,
                   at_step=trace_at)
    with jax.profiler.TraceAnnotation("benchmark.fit"):
        state, last = trainer.fit(state, feed, autosave_every=every,
                                  autosave_dir=ckpt_dir)
    with jax.profiler.TraceAnnotation("benchmark.drain"):
        _to_the_end(state, last)
    t_end = time.perf_counter()
    gc.unfreeze()
    trace = trace and feed.called_at is not None
    if trace:
        jax.profiler.stop_trace()
    t_start = feed.started
    compiles = counter.count - at_start["compiles"]
    stall0 = at_start["stall"]
    stall1 = observability.GLOBAL.snapshot().get("ingest_stall", {})
    saves = keys_system.delta(at_start["saves"], keys_system.counts())
    window_s = t_end - t_start
    steps = feed.handed - lead_in
    done_s = [t - t_start for t in feed.done[lead_in:]]
    print(json.dumps({"window_s": window_s, "steps": steps,
                      "train_compiles_in_window": compiles,
                      "autosave": saves}), flush=True)
    if feed.handed > len(raw_pool):
        print(json.dumps({"pool_wrapped_at": len(raw_pool),
                          "handed": feed.handed}), flush=True)

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell}.{seed}.steps.json"), "w") as f:
        json.dump({"cell": cell, "seed": seed, "window_s": window_s,
                   "steps": steps, "waited_s": feed.waited_s,
                   "step_done_s": done_s}, f)

    peaks = [d.memory_stats() for d in system.mesh.devices.flat]
    memory_peak = max((p or {}).get("peak_bytes_in_use", 0) for p in peaks)
    # the chain as the window left it, held to the guarantees: no fold
    # runs under a reader, and none ran under the window
    saved_at = list(range(every, feed.handed + 1, every))
    expected += [counts_chain_keys.distinct_keys(
        fed(raw_pool, at - every, at)) for at in saved_at]
    settle(keys_system, ckpt_dir, made=len(expected),
           made_bytes=int(warm["bytes"] + saves["ckpt_delta_bytes"]))
    off = entries_off(reference_chain_keys.entry_keys(ckpt_dir), expected)
    extra = [("insert_failures",
              system_lib.insert_failures(system, state.emb), 0)]
    late = None
    if config.get("rehearsal"):
        # the first in-window entry (the chain's second) against the
        # table at its own step, while the chain still lists it
        late = at_step(config, seed, on_device, raw_pool, traffic,
                       saved_at[0], ckpt_dir, entry=2)
    expected.append(counts_chain_keys.distinct_keys(
        fed(raw_pool, saved_at[-1], feed.handed)))
    off += save_the_tail(keys_system, reference_chain_keys.entry_keys,
                         system, state, ckpt_dir, trained + feed.handed,
                         len(expected) - 1, expected[-1])
    mark("window")
    found = chain_faults(ckpt_dir, system, state.emb)
    mark("chain_compared")
    extra += [("chain_mismatch_rows", found["mismatch_rows"], 0),
              ("chain_missing_keys", found["missing_keys"], 0),
              ("chain_extra_keys", found["extra_keys"], 0),
              ("chain_rows_off", off, 0)]
    if late is not None:
        extra.append(("chain_late_rows", late, 0))
    step_hlo = snapshot_hlo = None
    if trace:       # kept beside the trace: they name its operations
        step_hlo = system_lib.step_hlo(system, state, pool[0])
        with open(os.path.join(OUT_DIR, f"{cell}.{seed}.step.hlo.txt"),
                  "w") as f:
            f.write(step_hlo)
        snapshot_hlo = keys_system.snapshot_hlo(system, state.emb,
                                                expected[1])
    del state, last, pool                      # the tables leave the device
    ref = reference.follow(seed, config, raw_pool[:FOLLOWED_STEPS])
    values, where = correct.numbers(prog, ref)
    ok, compared = correct.decide(values, config["limits"], extra=extra)
    print(json.dumps({"compared_at": where, "program": prog,
                      "reference": ref, "entry_keys": expected,
                      "entry_new_keys": found["new_keys"]}), flush=True)

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
        "snapshot_hlo": snapshot_hlo,
        "feed_call_at_s": feed.called_at,
        "autosave": saves,
    }
    return {"correct": ok, "attempted": steps,
            "failed": extra[0][1], "metrics": end_to_end,
            "memory_peak_bytes": memory_peak, "compared": compared,
            "_context": context}
