"""What the hash autosave cell's readers share beyond ``_autosave.py``:
the device time of a snapshot's find. A snapshot program of a hash table
finds its dirty keys' slots (stage ``ckpt_find``) before it gathers their
rows, in one module whose name holds ``ckpt_gather``. The trace names a
device event by its instruction alone and the tables' programs number
their instructions each for itself, so an event is looked up in the HLO of
the program whose execution it lies in (``run["snapshot_hlo"]``, one text
a table in the order a save dispatches them; the copy of the dense state
closes a save). A program without the stage, or a run without the texts,
gives nothing to read: ``None``, never a raise."""

import bisect

from .. import trace_reduce
from . import _offload
from ._autosave import DENSE_COPY, GATHER_STAGE

FIND_STAGE = "ckpt_find"


def find_device_s(run):
    """(device seconds under ``ckpt_find``, whole saves they belong to)
    over the traced window, averaged over the device planes; None without
    a trace, the snapshot programs' HLO or the stage in it."""
    texts, lines = run.get("snapshot_hlo"), _offload._device_lines(run)
    if not run.get("trace") or not lines or not texts:
        return None
    finds = [{instruction for instruction, path
              in trace_reduce.scope_names(text).items()
              if FIND_STAGE in path.split("/")} for text in texts]
    if not any(finds):
        return None
    total_ns = saves = 0
    for ops, modules in lines:
        ops = sorted(ops, key=lambda e: e.start_ns)
        starts = [e.start_ns for e in ops]
        ran = sorted((e for e in modules if GATHER_STAGE in e.name),
                     key=lambda e: e.start_ns)
        for i, closing in enumerate(ran):
            mine = ran[max(i - len(texts), 0):i]
            if DENSE_COPY not in closing.name or len(mine) < len(texts) \
                    or any(DENSE_COPY in e.name for e in mine):
                continue
            saves += 1
            for table, module in zip(finds, mine):
                end = module.start_ns + module.duration_ns
                inside = ops[bisect.bisect_left(starts, module.start_ns):
                             bisect.bisect_left(starts, end)]
                total_ns += trace_reduce._union(
                    [(e.start_ns, e.start_ns + e.duration_ns)
                     for e in inside
                     if e.name.split(" = ", 1)[0].strip().lstrip("%")
                     in table])[0]
    if not saves:
        return None
    return total_ns * 1e-9 / len(lines), saves / len(lines)
