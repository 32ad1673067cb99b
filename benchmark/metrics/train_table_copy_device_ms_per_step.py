"""Device milliseconds per step, per chip, of the trace's events whose
instruction in the step's own HLO is a ``copy`` / ``copy-start`` /
``copy-done`` with a table operand's local shape
(``_hash_x4.table_copy_ms_per_step``): 0 where the compiler copies no
table. The routed hash push kept its find-or-insert inside a conditional
until PR 40, and the compiler copied each key array into the branch."""

from ._hash_x4 import table_copy_ms_per_step

TIMING = True


def read(run):
    return table_copy_ms_per_step(run)
