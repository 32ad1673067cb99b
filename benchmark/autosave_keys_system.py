"""What the hash autosave cell touches of the system under test beyond
``system.py`` and ``autosave_system.py``: the refusal of a tracker that is
not exact to the key, the directory's size, the counter of marked keys a
save found no row for, the live table read back slot by slot for the
checkpoint's reference, and the snapshot programs' HLO."""

import os
import shutil
import tempfile

import jax
import numpy as np

from . import autosave_system
from .autosave_system import (  # noqa: F401
    compaction_budget, counts as _counts, delta, join_compactor, mark, save,
    save_last)

KEYS_ABSENT = "ckpt_delta_keys_absent"
SNAPSHOT_SPANS = ("ckpt.claim", "ckpt.stage")
PROBE_KEY = (1 << 40) + 12345       # a key no chunk id could stand for
# base (96 B a live key over both tables) + chain, with room
NEED_BYTES_PER_KEY = 240


def arm(system):
    """As a user does before training with delta saves. The deployment
    saves the keys a period pushed, so a program that tracks a hash table
    in ``key % n`` chunks cannot run it: 62-bit keys dirty every chunk,
    each of its saves is a scan of the whole table on the host with the
    chip idle, and the run would end in ``chain_rows_off`` anyway. It is
    refused here, before the tables are made: a mark of one key has to
    come back from the tracker as that key."""
    autosave_system.arm(system)
    for name, tracker in system.coll.dirty_trackers.items():
        if not system.coll.specs[name].use_hash:
            continue
        tracker.mark_keys(np.array([PROBE_KEY], np.int64))
        back = np.asarray(tracker.snapshot_clear())
        if back.shape != (1,) or int(back[0]) != PROBE_KEY:
            raise SystemExit(
                f"benchmark: the program tracks the hash table {name!r} in "
                f"chunks ({getattr(tracker, 'num_chunks', '?')}), not to "
                "the key: every delta save of this table would be a scan "
                "of the whole table; the configuration cannot run")


def save_dir(config, out_dir):
    """A fresh directory where the configuration says a deployment saves
    (``assumed.autosave_dir``): the first of the process's temporary
    directory, ``benchmark/out`` and ``/dev/shm`` whose filesystem has room
    for the base and the chain. The caller removes it."""
    need = int(config["hash_capacity"] * config["load_factor"]
               * NEED_BYTES_PER_KEY)
    os.makedirs(out_dir, exist_ok=True)
    for parent in (tempfile.gettempdir(), out_dir, "/dev/shm"):
        if os.path.isdir(parent) and shutil.disk_usage(parent).free > need:
            return tempfile.mkdtemp(prefix="oe_autosave_keys_", dir=parent)
    raise RuntimeError(f"no directory with {need / 1e9:.1f} GB free for "
                       "the checkpoint")


def counts():
    """``autosave_system.counts`` and the keys that saves found no row
    for; what the program lacks reads nought."""
    from openembedding_tpu.analysis import scope
    from openembedding_tpu.utils import observability
    out = _counts()
    out[KEYS_ABSENT] = observability.GLOBAL.snapshot().get(
        KEYS_ABSENT, {}).get("count", 0.0)
    for span in SNAPSHOT_SPANS:         # the halves of the step's stall
        series = scope._hist_name(span)
        out[span] = {"s": scope.HISTOGRAMS.sum(series),
                     "calls": scope.HISTOGRAMS.count(series)}
    return out


def slots(system):
    """Slots of one hash table (both have as many)."""
    return next(iter(system.coll.specs.values())).hash_capacity


def live_reader(system, emb):
    """``live(vid, field, lo, hi)`` for ``reference_chain_keys.compare``:
    slots ``[lo, hi)`` of the table on the device, ``keys`` among the
    fields."""
    names = {system.coll.variable_id(n): n for n in system.coll.specs}

    def live(vid, field, lo, hi):
        state = emb[names[vid]]
        array = state.keys if field == "keys" \
            else state.weights if field == "weights" \
            else state.slots[field[len("slot_"):]]
        return np.asarray(array[lo:hi])

    return live


def snapshot_hlo(system, emb, keys):
    """Optimized HLO text of each table's snapshot program at the staging
    length of a save of ``keys`` keys, in the order a save dispatches them
    (a compile-cache hit after the window): the device trace names
    operations by instruction, and these texts say which of them ran under
    ``ckpt_find``. None where the program has no such snapshot."""
    try:
        from openembedding_tpu import checkpoint_delta as cd
        from openembedding_tpu.parallel import sharded_hash as sh
        program, staging = sh._snapshot_keys_program, cd._staging_rows
    except (ImportError, AttributeError):
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    whole = NamedSharding(system.mesh, P())

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    texts = []
    for name in system.coll.dirty_trackers:
        state, spec = emb[name], system.coll.sharding_spec(name)
        arrays = [state.weights] + [state.slots[s]
                                    for s in sorted(state.slots)]
        query = jax.ShapeDtypeStruct(
            (staging(keys),) + state.keys.shape[1:], state.keys.dtype,
            sharding=whole)
        texts.append(program(system.mesh, spec, len(arrays)).lower(
            abstract(state.keys), [abstract(a) for a in arrays], query,
            jax.ShapeDtypeStruct((), np.int32, sharding=whole))
            .compile().as_text())
    return texts
