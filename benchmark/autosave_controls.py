"""Read the autosave cell's planted faults at a cell's own size.

    python3 -m benchmark.autosave_controls <config> <what> <seed> [<seconds>]

``<what>`` is one of ``FAULTS`` or ``none`` (the program as it is). The
run is the cell's own (``train_autosave_runner.run``) with the fault
planted in the built system before anything trains; the last line is the
comparison as JSON. A fault has to read ``"correct": false``, ``none``
true. The benchmark's runs never call this.

``marks_dropped``       one step's marks in every save period never reach
    the dirty set. The pool is cycled, so a batch comes back 256 steps
    later and its rows are marked then: the drop that shows at the end is
    the last period's. ``chain_mismatch_rows`` counts the rows the chain
    holds as they stood a pool pass earlier; ``chain_rows_off`` every entry.
``snapshot_late``       every save's snapshot is taken one step after the
    step it names. The chain still ends at the live table, so the chip run
    sees it only in ``chain_rows_off`` (an entry holds one batch's rows too
    many); the rehearsal's ``chain_late_rows`` sees the rows themselves.
``stale_accumulator``   the snapshot stages every accumulator as it stood
    at the start of training: ``chain_mismatch_rows`` counts every row a
    save carried.
"""

import concurrent.futures
import json
import sys
import time

T_PROCESS = time.perf_counter()
FAULTS = ("marks_dropped", "snapshot_late", "stale_accumulator")
DROPPED_STEP = 7            # of every period, counted from its snapshot


def plant(system, fault, traffic):
    """Plant one of ``FAULTS`` in the built system."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    config, coll, trainer = system.config, system.coll, system.trainer
    every = config["checkpoint"]["autosave_every"]
    if fault == "marks_dropped":
        from .train_runner import FOLLOWED_STEPS
        # marks before the window's call: the followed steps, the
        # warm-up, and the runner's own for the warm save
        before = FOLLOWED_STEPS + traffic["warmup_steps"] + every
        mark, calls = coll.mark_dirty, [0]

        def some_dropped(sparse):
            calls[0] += 1
            if calls[0] <= before or \
                    (calls[0] - before) % every != DROPPED_STEP:
                mark(sparse)
        coll.mark_dirty = some_dropped
    elif fault == "snapshot_late":
        save, step, waiting = trainer._autosave_fit, trainer.train_step, []

        def late(state, path, cursor, step):
            waiting.append((path, cursor, step))

        def step_then_save(state, batch, **kw):
            state, metrics = step(state, batch, **kw)
            if waiting:     # the step after the one the save names
                save(state, *waiting.pop())
            return state, metrics
        trainer._autosave_fit, trainer.train_step = late, step_then_save
    else:
        import jax.numpy as jnp
        from openembedding_tpu import checkpoint_delta
        stage = checkpoint_delta._stage_array_rows
        start = config["adagrad"]["initial_accumulator_value"]

        def stale(*args, **kw):
            staged = stage(*args, **kw)
            staged.arrays = [
                jnp.full_like(a, start) if f.startswith("slot_") else a
                for f, a in zip(staged.fields, staged.arrays)]
            return staged
        checkpoint_delta._stage_array_rows = stale


def main(argv):
    config_name, what, seed = argv[0], argv[1], int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 20.0
    from . import run
    config = run.load("configs", config_name)
    traffic = run.load("traffic", "train_zipf_autosave")
    from .traffic_gen import zipf_train
    drawing = concurrent.futures.ThreadPoolExecutor(1)
    inputs = drawing.submit(zipf_train.make, traffic, config, seed)
    drawing.shutdown(wait=False)
    rehearsal = bool(config.get("rehearsal"))
    if rehearsal:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from . import system as system_lib, train_autosave_runner as runner
    on_device = system_lib.found_devices()["platform"] == "tpu"
    if not rehearsal and not on_device:
        print(f"autosave_controls: {config_name} needs a TPU chip",
              file=sys.stderr)
        return 2
    if on_device:
        from openembedding_tpu.utils.compile_cache import \
            enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = runner.run(
        f"{config_name}.train_zipf_autosave", config, traffic, inputs,
        seed=seed, seconds=seconds, trace=False, t_process=T_PROCESS,
        on_device=on_device,
        plant=None if what == "none"
        else lambda system: plant(system, what, traffic))
    print(json.dumps({"config": config_name, "fault": what, "seed": seed,
                      "correct": result["correct"],
                      "attempted": result["attempted"],
                      "examples_per_s":
                          result["metrics"]["examples_per_s"]["value"],
                      "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
