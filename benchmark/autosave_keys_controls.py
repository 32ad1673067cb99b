"""Read the hash autosave cell's planted faults at a cell's own size.

    python3 -m benchmark.autosave_keys_controls <config> <what> <seed> \\
        [<seconds>]

``<what>`` is one of ``FAULTS`` or ``none`` (the program as it is). The
run is the cell's own (``train_autosave_keys_runner.run``) with the fault
planted in the built system before anything trains; the last line is the
comparison as JSON. A fault has to read ``"correct": false``, ``none``
true. The benchmark's runs never call this.

``marks_dropped``       one step's marks in every save period never reach
    the dirty set. The window stays in the pool's first pass, so the
    batch does not come back: the keys only it pushed are in no entry.
    ``chain_mismatch_rows`` counts those the base held (the chain has
    them as they stood before), ``chain_missing_keys`` those the step
    inserted, ``chain_rows_off`` every in-window entry.
``snapshot_late``       every save's snapshot is taken one step after the
    step it names. The chain still ends at the live table, so the chip run
    sees it only in ``chain_rows_off`` (an entry holds one batch's keys
    too many); the rehearsal's ``chain_late_rows`` sees the rows.
``stale_accumulator``   the snapshot stages every accumulator as it stood
    at the start of training: ``chain_mismatch_rows`` counts every key a
    save carried.
``fresh_keys_dropped``  a save leaves out the keys that were inserted
    since the snapshot before (it looks its dirty keys up in a copy of
    the key array as that snapshot saw it): ``chain_missing_keys`` counts
    them, ``chain_rows_off`` every entry that had any.
"""

import concurrent.futures
import json
import sys
import time

T_PROCESS = time.perf_counter()
FAULTS = ("marks_dropped", "snapshot_late", "stale_accumulator",
          "fresh_keys_dropped")
TRAFFIC = "train_zipf_autosave_keys"


def plant(system, fault, traffic):
    """Plant one of ``FAULTS`` in the built system. The two that replace
    a function of the program's module hand back what puts it back."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault in ("marks_dropped", "snapshot_late"):
        from . import autosave_controls     # the array cell's, as they are
        return autosave_controls.plant(system, fault, traffic)
    import jax.numpy as jnp
    from openembedding_tpu import checkpoint_delta
    stage = checkpoint_delta._stage_hash_keys

    def undo():
        checkpoint_delta._stage_hash_keys = stage
    if fault == "stale_accumulator":
        start = system.config["adagrad"]["initial_accumulator_value"]

        def stale(*args, **kw):
            staged = stage(*args, **kw)
            staged.arrays = [
                jnp.full_like(a, start) if f.startswith("slot_") else a
                for f, a in zip(staged.fields, staged.arrays)]
            return staged
        checkpoint_delta._stage_hash_keys = stale
        return undo
    import numpy as np
    from openembedding_tpu import hash_table
    from openembedding_tpu.parallel import sharded_hash
    seen = {}           # the key array as the snapshot before saw it

    def old_keys_only(collection, name, state, keys64, include_optimizer):
        if name in seen:
            wide = hash_table.is_wide(state.keys)
            query = hash_table.split64(keys64) if wide \
                else keys64.astype(state.keys.dtype)
            found, _ = sharded_hash.snapshot_keys_sharded(
                seen[name], [], jnp.asarray(query), keys64.size,
                mesh=collection.mesh, spec=collection.sharding_spec(name))
            keys64 = keys64[np.asarray(found)]
        seen[name] = jnp.copy(state.keys)
        return stage(collection, name, state, keys64, include_optimizer)
    checkpoint_delta._stage_hash_keys = old_keys_only
    return undo


def main(argv):
    config_name, what, seed = argv[0], argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 20.0
    from . import run
    config = run.load("configs", config_name)
    traffic = run.load("traffic", TRAFFIC)
    from .traffic_gen import zipf_train
    drawing = concurrent.futures.ThreadPoolExecutor(1)
    inputs = drawing.submit(zipf_train.make, traffic, config, seed)
    drawing.shutdown(wait=False)
    rehearsal = bool(config.get("rehearsal"))
    if rehearsal:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from . import system as system_lib, train_autosave_keys_runner as runner
    on_device = system_lib.found_devices()["platform"] == "tpu"
    if not rehearsal and not on_device:
        print(f"autosave_keys_controls: {config_name} needs a TPU chip",
              file=sys.stderr)
        return 2
    if on_device:
        from openembedding_tpu.utils.compile_cache import \
            enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    planted = []
    try:
        result = runner.run(
            f"{config_name}.{TRAFFIC}", config, traffic, inputs,
            seed=seed, seconds=seconds, trace=False, t_process=T_PROCESS,
            on_device=on_device,
            plant=None if what == "none" else lambda system:
            planted.append(plant(system, what, traffic)))
    finally:
        for undo in filter(None, planted):
            undo()
    print(json.dumps({"config": config_name, "fault": what, "seed": seed,
                      "correct": result["correct"],
                      "attempted": result["attempted"],
                      "examples_per_s":
                          result["metrics"]["examples_per_s"]["value"],
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
