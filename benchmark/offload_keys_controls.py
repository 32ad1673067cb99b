"""Read the keyed offload tier's planted faults, its control and one
eviction, at a cell's own size: ``offload_controls.py``'s twin for a tier
of unbounded keys.

    python3 -m benchmark.offload_keys_controls <config> <what> <seed> \
        [<slots> [<steps>]]

``<what>`` is one of ``offload_keys_system.FAULTS`` (a miss served from
the initializer and not from the store; a writeback that drops its rows;
a fresh key whose trained row never reaches the store; two keys that
share a store row), ``bfloat16`` (the control of the arithmetic: the
reference in bfloat16 stands in the program's place, no table is built),
``none`` (the program as it is), or ``evict``. The run builds the cell's
system as the runner does (``<slots>``: another ``cache_capacity``, for a
rehearsal), drives the followed steps and prints the comparison as one
JSON line. A fault and the control have to read ``"correct": false``;
``none`` and ``evict`` true.

``evict`` goes on after the comparison: ``<steps>`` further steps through
``Trainer.fit`` (default 400), the rows of the followed batches read
through the tier, the cache warmed with stored keys to 64 rows under its
budget and one more batch prepared, so that the tier has to evict (write
the dirty rows back under their keys, the fresh keys' among them, empty
the cache, re-insert the survivors); then the same rows read through the
tier again, and from the store after a flush. ``evict_mismatch`` counts
the lookups that differ in any bit between the readings, or whose key the
store does not hold, and has to be nought; the stall is the
``offload.evict`` span.

The benchmark's runs never call this.
"""

import concurrent.futures
import json
import resource
import sys
import time

T_PROCESS = time.perf_counter()


def evict(system, state, feed_of, batches, steps, last):
    """Steps that leave their rows dirty, then one eviction between two
    readings of ``batches`` through the tier; (state, what was read)."""
    import jax
    from . import offload_keys_system as offload_system, \
        train_offload_keys_runner as runner
    if steps:
        state, _ = system.trainer.fit(state, feed_of(steps, len(steps)))
        jax.block_until_ready(state)
    dirty = {name: int(t._dirty.dirty_count)
             for name, t in system.tiers.items()}
    state, before = offload_system.pull_rows(system, state, batches)
    state = offload_system.fill_to_budget(system, state)
    began = time.perf_counter()
    state = system.trainer.prepare_offload(state, last)
    jax.block_until_ready(state.emb)
    stall_s = time.perf_counter() - began
    state, after = offload_system.pull_rows(system, state, batches)
    offload_system.flush(system, state)
    stored, held = offload_system.store_rows(system, batches)
    return state, {
        "steps": len(steps), "dirty_rows": dirty, "stall_s": stall_s,
        "evict_mismatch": runner.differing(before, after)
        + runner.unheld(after, stored, held)}


def main(argv):
    config_name, what, seed = argv[0], argv[1], int(argv[2])
    from . import run as run_lib
    config = run_lib.load("configs", config_name)
    if len(argv) > 3:
        config["cache_capacity"] = int(argv[3])
    evict_steps = (int(argv[4]) if len(argv) > 4 else 400) \
        if what == "evict" else 0
    rehearsal = bool(config.get("rehearsal"))
    if rehearsal:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    from . import offload_keys_system as offload_system, \
        train_offload_keys_runner as runner
    from .traffic_gen import zipf_train
    on_device = offload_system.found_devices()["platform"] == "tpu"
    if not rehearsal and not on_device:
        print("offload_keys_controls: needs the TPU chip", file=sys.stderr)
        return 2
    if on_device:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    n = runner.FOLLOWED_STEPS
    # the followed batches, their probes, the steps before an eviction
    # and the batch that brings it
    traffic = dict(run_lib.load("traffic", "train_zipf_offload_keys"),
                   pool_batches=2 * n + evict_steps + 1)
    inputs = concurrent.futures.Future()
    inputs.set_result(zipf_train.make(traffic, config, seed))
    if what == "bfloat16":
        # the reference in bfloat16 in the program's place: no table
        import jax.numpy as jnp
        from . import reference_offload_keys as keyed
        raw = inputs.result()[:n]
        prog = keyed.follow(seed, config, raw, dtype=jnp.bfloat16)
        ok, compared = runner.compare(config, seed, raw, prog, {}, 0, 0)
        print(json.dumps({"config": config_name, "what": what,
                          "seed": seed, "correct": ok,
                          "compared": compared}), flush=True)
        return 0
    system, state, raw, pool, feed_of = runner.set_up(
        config, traffic, inputs, seed, on_device, lambda phase: None)
    set_up_s = time.perf_counter() - T_PROCESS

    def evictions():    # the registry is the process's: read what is added
        spans = {name: offload_system.span_read("offload.evict", [name])
                 for name in system.tiers}
        return offload_system.tier_counts(system)["offload_evictions"], spans

    evictions0, spans0 = evictions()
    if what not in ("none", "evict"):
        offload_system.plant(system, what)
    began = time.perf_counter()
    state, prog, mismatch, shared = runner.followed(
        system, system.trainer, state, feed_of, raw[:n], pool[:n],
        raw[n:2 * n], pool[n:2 * n], seed)
    followed_s = time.perf_counter() - began
    evicted = None
    if what == "evict":
        state, evicted = evict(system, state, feed_of, pool[:n],
                               pool[2 * n:-1], pool[-1])
    failures = offload_system.insert_failures(system, state.emb)
    del state
    print(json.dumps({"store_mismatch_at": mismatch}), flush=True)
    ok, compared = runner.compare(config, seed, raw[:n], prog, mismatch,
                                  shared, failures)
    if evicted is not None:
        ok = ok and evicted["evict_mismatch"] == 0
    evictions1, spans1 = evictions()
    print(json.dumps({
        "config": config_name, "what": what, "seed": seed, "correct": ok,
        "compared": compared, "set_up_s": set_up_s,
        "followed_s": followed_s, "evicted": evicted,
        "evictions": evictions1 - evictions0,
        "evict_span": {name: {k: v - spans0[name][k] for k, v in read.items()}
                       for name, read in spans1.items()},
        "store": offload_system.store_gauges(system),
        "host_peak_gib": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 2 ** 20,
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in system.mesh.devices.flat)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
