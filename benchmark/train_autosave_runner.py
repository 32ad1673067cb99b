"""Runs one training cell that saves while it trains: the array cell's
step through ``Trainer.fit(autosave_every=, autosave_dir=)`` over a delta
chain. Traffic files of ``"kind": "train_autosave"`` come here.

How an autosave run goes (what differs from ``train_runner.py``):

1. ``autosave_system.arm``: ``collection.enable_dirty_tracking()`` before
   anything trains, as a user does. Then the tables, the pool, the three
   followed steps and the warm-up, as in any training cell.
2. The base, in set-up: one delta save into the empty directory, which is
   the full save. Then every shape a window save will use is warmed: the
   rows of the batches the first period will hand out are marked (marking
   more than was pushed is always allowed) and one more delta save writes
   them, through the snapshot's gather program at the window's staging
   length, the copy to the host, the writer and the commit.
3. The window is a stated amount of work: ``Trainer.fit(feed,
   autosave_every=N, autosave_dir=dir)`` over a ``Feed`` that hands out
   ``lead_in_steps`` + ``checkpoint.window_periods`` x N batches, whatever
   ``--seconds`` says and however fast the program is, so that both sides
   of a pair make the same steps, the same saves and the same entries.
   ``fit`` counts its saves from its own first step, the lead-in included,
   so a window of k periods holds k saves, each of one period's rows; the
   first falls ``N - lead_in_steps`` steps after the window opens, the
   last ``lead_in_steps`` before it closes. The clock stops when the last
   state is ready and ``fit`` has returned, which it does not before the
   last save is committed. A traced run starts the profiler by step count
   too: before the window's last ``trace_periods`` periods are handed out
   (the traffic file), so the traced tail holds the last in-window save
   and most of a period before it at any step time.
4. With the clock stopped the chain is held to its guarantees, in an
   order in which no compactor can write under a reader (the program
   folds a chain into a new base on a background thread once a save meets
   its budget of entries or bytes). First ``join_compactor``; the
   manifest has to list the warm save's entry and the window's, else a
   budget was met inside the window and the run ends in one line that
   says so. ``chain_rows_off`` counts the entries whose row counts are
   not the distinct ids the feed handed out since the entry before
   (``counts_chain.distinct_rows``). A rehearsal (``tiny_array_ckpt``)
   also checks an entry against the table as it stood at the entry's own
   step (``chain_late_rows``): a second system follows the same batches
   without saving and stops there. At the cell's size that copy is a
   second table, which the chip does not hold. Then one more save carries
   the ``lead_in_steps`` steps after the last in-window save (until it is
   made the live table is that far ahead of the chain). It is the
   runner's own and asks for no fold (``autosave_system.save_last``: the
   array cell's would be the chain's eighth entry and start one of the
   6.54 GB base). ``join_compactor`` again, its entry is counted like the
   others, and the checkpoint's plain reference (``reference_chain.py``)
   replays what the manifest holds on the host while the live table is
   read back block by block: ``chain_mismatch_rows`` counts the rows of
   weights and accumulators, both tables, that differ in any bit. Both
   have to be nought.
5. The training reference follows the first three batches
   (``reference.py``), as in the array cell, under the same limits.
"""

import gc
import json
import os
import shutil
import time

import jax

from . import (autosave_system, correct, counts_chain, reference,
               reference_chain, system as system_lib)
from .train_runner import (CompileCounter, Feed, FOLLOWED_STEPS, OUT_DIR,
                           _followed)


def window_batches(config, traffic):
    """(batches the window's feed hands out, batches handed out before a
    traced run starts the profiler): the lead-in and ``window_periods``
    whole save periods; the traced tail is the last ``trace_periods``."""
    every = config["checkpoint"]["autosave_every"]
    periods = config["checkpoint"]["window_periods"]
    lead_in = traffic["lead_in_steps"]
    return lead_in + periods * every, \
        lead_in + (periods - traffic["trace_periods"]) * every


def settle(adapter, path, made=None, made_bytes=None):
    """Wait for any fold of ``path`` to end, then the manifest's chain.
    Where ``made`` is given the chain has to list that many entries (the
    warm save's and the window's): fewer, and a save inside the window
    met the compactor's budget, which the window is sized to stay under;
    the run ends there, in one line."""
    adapter.join_compactor(path)
    chain = reference_chain.manifest(path)["chain"]
    if made is not None and len(chain) != made:
        entries, ratio = adapter.compaction_budget()
        base = reference_chain.base_bytes(path)
        raise SystemExit(
            f"benchmark: the manifest lists {len(chain)} entries where "
            f"the warm save and the window made {made} ({made_bytes} "
            f"bytes): a save inside the window met the compactor's budget "
            f"(a chain of {entries} entries, or {ratio} of the base's "
            f"{base} bytes) and the chain was folded under the window; "
            "checkpoint.window_periods has to stay under that budget")
    return chain


def _to_the_end(state, last):
    jax.block_until_ready(state)
    return float(last["loss"])


def save_the_tail(adapter, entry_counts, system, state, path, step, made,
                  want):
    """The runner's own save, with the clock stopped, of the steps after
    the last in-window save (``adapter.save_last``, which starts no fold):
    until it is made the live table is that far ahead of the chain.
    Returns 0 where the chain then lists the ``made`` entries it listed
    and one more that holds ``want`` rows or keys a variable
    (``entry_counts`` reads the files), else how many it is off by."""
    tail = adapter.save_last(system, state, path, step)
    chain = settle(adapter, path)
    print(json.dumps({"tail_save": {k: tail.get(k) for k in (
        "seq", "rows", "bytes", "compaction")}, "chain_after": len(chain)}),
        flush=True)
    return entries_off(entry_counts(path, first=made), [want])


def fed(raw_pool, start, stop):
    """The raw batches a feed cycling the pool hands out at ``[start,
    stop)``."""
    return [raw_pool[i % len(raw_pool)] for i in range(start, stop)]


def entries_off(held, expected):
    """Entries of ``held`` ([{variable id: count}] as an ``entry_rows``
    reads the files) that do not hold, for every variable, the count
    ``expected`` names for them: a whole-table delta, or a save that
    missed or kept rows, shows here."""
    if len(held) != len(expected):
        return max(len(held), len(expected))
    return sum(1 for counts, want in zip(held, expected)
               if not counts or any(n != want for n in counts.values()))


def at_step(config, seed, on_device, raw_pool, traffic, steps, path, entry):
    """Rows in which the chain's first ``entry`` entries differ from the
    table as it stood after ``steps`` steps of the window's call: a second
    system follows the run's batches without a save and stops there (the
    same programs on the same inputs: the same bits)."""
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
    return reference_chain.mismatch_rows(
        path, autosave_system.live_reader(system, state.emb), entries=entry)


def run(cell, config, traffic, inputs, *, seed, seconds, trace, t_process,
        on_device, plant=None):
    """One run of an autosave training cell; returns the result line as a
    dict (and a ``context`` for the per-layer readers under
    ``"_context"``), as ``train_runner.run`` does. ``seconds`` is not
    read: the window's length is the configuration's ``window_periods``.
    ``plant(system)`` plants a fault before anything trains
    (``autosave_controls``)."""
    counter = CompileCounter()

    def mark(phase):
        print(json.dumps({"set_up": phase, "at_s": round(
            time.perf_counter() - t_process, 2),
            "programs": counter.count}), flush=True)

    mark("imports")
    system = system_lib.build(config)
    trainer = system.trainer
    autosave_system.arm(system)
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
    ckpt_dir = autosave_system.save_dir(config, OUT_DIR)
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
    base = autosave_system.save(system, state, ckpt_dir, trained)
    mark("base")
    for batch in pool[:every]:
        autosave_system.mark(system, batch)
    warm = autosave_system.save(system, state, ckpt_dir, trained)
    expected = [counts_chain.distinct_rows(raw_pool[:every])]
    if warm.get("rows") != expected[0] * len(system.coll.specs):
        raise SystemExit(       # before a window of such saves
            f"benchmark: the warm save carried {warm.get('rows')} rows, "
            f"the batches marked hold {expected[0]} a table")
    print(json.dumps({"ckpt_dir": ckpt_dir, "base": base, "warm": {
        k: warm[k] for k in ("mode", "seq", "rows", "bytes", "seconds")}}),
        flush=True)
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
        at_start["saves"] = autosave_system.counts()

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
    saves = autosave_system.delta(at_start["saves"],
                                  autosave_system.counts())
    window_s = t_end - t_start
    steps = feed.handed - lead_in
    done_s = [t - t_start for t in feed.done[lead_in:]]
    print(json.dumps({"window_s": window_s, "steps": steps,
                      "train_compiles_in_window": compiles,
                      "autosave": saves}), flush=True)

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
    expected += [counts_chain.distinct_rows(fed(raw_pool, at - every, at))
                 for at in saved_at]
    settle(autosave_system, ckpt_dir, made=len(expected),
           made_bytes=int(warm["bytes"] + saves["ckpt_delta_bytes"]))
    off = entries_off(reference_chain.entry_rows(ckpt_dir), expected)
    extra = [("insert_failures",
              system_lib.insert_failures(system, state.emb), 0)]
    late = None
    if config.get("rehearsal"):
        # the first in-window entry (the chain's second) against the
        # table at its own step, while the chain still lists it
        late = at_step(config, seed, on_device, raw_pool, traffic,
                       saved_at[0], ckpt_dir, entry=2)
    expected.append(counts_chain.distinct_rows(
        fed(raw_pool, saved_at[-1], feed.handed)))
    off += save_the_tail(autosave_system, reference_chain.entry_rows,
                         system, state, ckpt_dir, trained + feed.handed,
                         len(expected) - 1, expected[-1])
    mark("window")
    mismatch = reference_chain.mismatch_rows(
        ckpt_dir, autosave_system.live_reader(system, state.emb))
    mark("chain_compared")
    extra += [("chain_mismatch_rows", mismatch, 0),
              ("chain_rows_off", off, 0)]
    if late is not None:
        extra.append(("chain_late_rows", late, 0))
    step_hlo = None
    if trace:       # kept beside the trace: it names the trace's operations
        step_hlo = system_lib.step_hlo(system, state, pool[0])
        with open(os.path.join(OUT_DIR, f"{cell}.{seed}.step.hlo.txt"),
                  "w") as f:
            f.write(step_hlo)
    del state, last, pool                      # the tables leave the device
    ref = reference.follow(seed, config, raw_pool[:FOLLOWED_STEPS])
    values, where = correct.numbers(prog, ref)
    ok, compared = correct.decide(values, config["limits"], extra=extra)
    print(json.dumps({"compared_at": where, "program": prog,
                      "reference": ref, "entry_rows": expected}),
          flush=True)

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
        "autosave": saves,
    }
    return {"correct": ok, "attempted": steps,
            "failed": extra[0][1], "metrics": end_to_end,
            "memory_peak_bytes": memory_peak, "compared": compared,
            "_context": context}
