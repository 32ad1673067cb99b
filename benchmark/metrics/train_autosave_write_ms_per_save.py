"""Milliseconds per save in ``ckpt.checksum`` + ``ckpt.write`` +
``ckpt.commit``: block checksums, the files (one a table, written side by
side: thread seconds) and the manifest rename."""

from ._autosave import span_ms_per_save

TIMING = True


def read(run):
    return span_ms_per_save(run, "ckpt.checksum", "ckpt.write",
                            "ckpt.commit")
