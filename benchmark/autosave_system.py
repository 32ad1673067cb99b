"""What the autosave cell touches of the system under test beyond
``system.py``: arming the dirty tracking, the directory a deployment saves
to, the program's save counters and spans, and the live table read back
block by block for the checkpoint's reference."""

import functools
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

COUNTERS = ("ckpt_delta_saves", "ckpt_delta_rows", "ckpt_delta_bytes",
            "ckpt_full_saves", "ckpt_full_bytes")
SPANS = ("trainer.autosave", "ckpt.d2h", "ckpt.checksum", "ckpt.write",
         "ckpt.commit")
COMMIT_LAG = "ckpt_commit_lag_s"
# base (80 B a row) + chain, with room: what the directory has to hold
NEED_BYTES_PER_ROW = 120


def arm(system):
    """As a user does before training with delta saves. The deployment
    saves the rows a period pushed, so a program that tracks an array
    table in chunks of contiguous rows cannot run it: hashed ids dirty
    every chunk, each of its saves is the whole table (6.5 GB, a minute
    and a half with training stopped, and tens of GiB of host memory to
    replay), and the run would end in ``chain_rows_off`` anyway. It is
    refused here, before the tables are made."""
    system.coll.enable_dirty_tracking()
    for name, tracker in system.coll.dirty_trackers.items():
        rows = getattr(tracker, "rows_per_chunk", 1)
        if rows > 1 and not system.coll.specs[name].use_hash:
            raise SystemExit(
                f"benchmark: the program tracks {name!r} in chunks of "
                f"{rows} rows, not to the row: every delta save of this "
                "table would be the whole table; the configuration "
                "cannot run")


def save_dir(config, out_dir):
    """A fresh directory where the configuration says a deployment saves
    (``assumed.autosave_dir``): the first of the process's temporary
    directory, ``benchmark/out`` and ``/dev/shm`` whose filesystem has room
    for the base and the chain. The caller removes it."""
    need = config["rows_per_feature"] * config["sparse_features"] \
        * NEED_BYTES_PER_ROW
    os.makedirs(out_dir, exist_ok=True)
    for parent in (tempfile.gettempdir(), out_dir, "/dev/shm"):
        if os.path.isdir(parent) and shutil.disk_usage(parent).free > need:
            return tempfile.mkdtemp(prefix="oe_autosave_", dir=parent)
    raise RuntimeError(f"no directory with {need / 1e9:.1f} GB free for "
                       "the checkpoint")


def counts():
    """The program's save counters, span seconds and calls, and commit
    lag as they stand now; what the program lacks reads nought."""
    from openembedding_tpu.analysis import scope
    from openembedding_tpu.utils import observability
    snap = observability.GLOBAL.snapshot()
    out = {c: snap.get(c, {}).get("count", 0.0) for c in COUNTERS}
    for span in SPANS:
        series = scope._hist_name(span)
        out[span] = {"s": scope.HISTOGRAMS.sum(series),
                     "calls": scope.HISTOGRAMS.count(series)}
    out[COMMIT_LAG] = {"s": scope.HISTOGRAMS.sum(COMMIT_LAG),
                       "calls": scope.HISTOGRAMS.count(COMMIT_LAG)}
    return out


def delta(before, after):
    """What the window added to ``counts``."""
    return {k: ({f: after[k][f] - before[k][f] for f in after[k]}
                if isinstance(after[k], dict) else after[k] - before[k])
            for k in after}


def save(system, state, path, step):
    """One delta save through the public call (the first into an empty
    directory is the full base)."""
    from openembedding_tpu import checkpoint
    return checkpoint.save_checkpoint(
        path, system.coll, state.emb,
        dense_state=(state.params, state.opt_state), mode="delta",
        step=step)


def save_last(system, state, path, step):
    """The runner's own save, with the clock stopped, of the steps after
    the last in-window save: the delta save's own call under a budget no
    chain meets, so that it starts no fold. It is no save of the
    deployment: it brings the chain level with the live table for the
    comparison, and a fold of the cell's 6.54 GB base would hold the
    machine 140 s with the chip idle and write 6.5 GB more to its disk in
    every run (my chip run, PR 43)."""
    import sys
    from openembedding_tpu import checkpoint_delta
    return checkpoint_delta.save_delta(
        path, system.coll, state.emb, step=step,
        dense_state=(state.params, state.opt_state),
        compact_chain_len=sys.maxsize, compact_bytes_ratio=float("inf"))


def join_compactor(path):
    """Wait until no fold of ``path`` runs (the program folds a chain into
    a new base on a background thread once a save meets its budget), and
    raise what a fold failed with: after this, and until the next save,
    nothing writes the directory."""
    from openembedding_tpu import checkpoint_delta
    checkpoint_delta.join_compactor(path)


def compaction_budget():
    """(entries, share of the base's bytes) at which a save of the program
    starts a fold, as its saves' defaults stand."""
    import inspect
    from openembedding_tpu import checkpoint_delta
    defaults = inspect.signature(checkpoint_delta.begin_delta).parameters
    return (defaults["compact_chain_len"].default,
            defaults["compact_bytes_ratio"].default)


def mark(system, batch):
    """Mark a program batch's rows dirty, as a custom loop does."""
    system.coll.mark_dirty(batch["sparse"])


@functools.lru_cache(maxsize=None)
def _reader():
    return jax.jit(lambda array, at: jnp.take(array, at, axis=0))


def live_reader(system, emb):
    """``live(vid, field, lo, hi)`` for ``reference_chain.mismatch_rows``:
    logical rows of the table on the device, through the layout's own
    row-to-position map."""
    names = {system.coll.variable_id(n): n for n in system.coll.specs}

    def live(vid, field, lo, hi):
        name = names[vid]
        state = emb[name]
        array = state.weights if field == "weights" \
            else state.slots[field[len("slot_"):]]
        spec = system.coll.sharding_spec(name)
        if spec.num_shards == 1:        # one shard: a row is where its id is
            return np.asarray(array[lo:hi])
        shard, local = spec.shard_and_local(np.arange(lo, hi))
        at = (shard * spec.rows_per_shard + local).astype(np.int32)
        return np.asarray(_reader()(array, at))

    return live
