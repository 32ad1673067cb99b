"""What the readers of the sharded hash cell share: which instructions of
the step's optimized HLO copy a table. The trace names a device event by
its instruction, and the step's own text (``run["step_hlo"]``: the plan's
and the pull's programs are functions of that module) says what an
instruction is: a ``copy``, or the ``copy-start`` / ``copy-done`` halves of
an asynchronous one, whose result has the shape a chip holds of a table
operand (the key array, the weights, an optimizer slot). The v5e compiler
makes such a copy where a loop inside a branch of a conditional carries
the array (PERF.md section 6, PR 27): 512 MiB a table a step at this
cell's size. A run without a trace or without the text gives nothing to
read: ``None``, never a raise."""

import re

from . import _offload

COPIES = ("copy", "copy-start", "copy-done")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")


def table_shapes(config):
    """The shapes a chip holds of a hash configuration's table operands
    (the key array, the weights of both tables and their accumulators), as
    HLO writes them without a layout: ``s32[67108864,2]``."""
    rows = config["hash_capacity"] // config["chips"]
    return [f"s32[{rows},2]"] + [
        f"f32[{rows},{config[width]}]"
        for width in ("embedding_dim", "linear_dim")]


def table_copies(hlo_text, shapes):
    """{instruction: shape} of the copies of ``hlo_text`` whose result
    holds one of ``shapes``."""
    found = {}
    for text in (hlo_text or "").splitlines():
        parsed = _INSTRUCTION.match(text)
        if not parsed or parsed.group(3) not in COPIES:
            continue
        for shape in shapes:
            # the layout follows the shape: ``s32[8,2]{1,0:T(2,128)}``
            if re.search(re.escape(shape) + r"(?![\d,\]])", parsed.group(2)):
                found[parsed.group(1)] = shape
                break
    return found


def table_copy_ms_per_step(run):
    """Device milliseconds a step, per chip, in copies of a table: 0 where
    the step's text holds none."""
    trace, hlo = run.get("trace"), run.get("step_hlo")
    lines = _offload._device_lines(run)
    if not trace or not trace["steps"] or not hlo or not lines:
        return None
    copies = table_copies(hlo, table_shapes(run["config"]))
    if not copies:
        return 0.0
    total_ns = sum(
        e.duration_ns for ops, _ in lines for e in ops
        if e.name.split(" = ", 1)[0].strip().lstrip("%") in copies)
    return total_ns * 1e-6 / len(lines) / trace["steps"]
