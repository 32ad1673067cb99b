"""Marked keys a save found no row for and left out, a save, both tables
together: counter ``ckpt_delta_keys_absent`` (a key marked ahead of its
push, or one whose insert no probe window held). Nought in a window whose
every marked key was pushed."""

from ._autosave import saves

TIMING = False
COUNTER = "ckpt_delta_keys_absent"


def read(run):
    read_, n = run.get("autosave") or {}, saves(run)
    if not n or COUNTER not in read_:
        return None
    return read_[COUNTER] / n
