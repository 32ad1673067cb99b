"""``tools.stage_ops``: the stage table an instruction a line.

On the small trace recorded on a TPU v5e that the benchmark's own reducer
is held to (``benchmark/testdata``: 21 steps of
``deepfm_dim9_hash.train_zipf``), the instructions listed under a stage add
up to what ``benchmark.stage_reduce`` gives the stage, and the printed
table names the costliest of each.
"""

import gzip
import os

import pytest

from benchmark import stage_reduce, trace_reduce
from tools import stage_ops

DATA = os.path.join(os.path.dirname(trace_reduce.__file__), "testdata")
TRACE = os.path.join(DATA, "hash_step_stages.xplane.pb.gz")
HLO = os.path.join(DATA, "hash_step_stages.hlo.txt.gz")


def test_instructions_add_up_to_their_stage(capsys):
    with gzip.open(HLO, "rt") as f:
        hlo = f.read()
    table = stage_reduce.reduce(TRACE, hlo)
    chips, steps, by_stage = stage_ops.instruction_ms(TRACE, hlo)
    assert (chips, steps) == (table["chips"], table["steps"])
    assert set(by_stage) == set(table["stage_s"])
    for stage, seconds in table["stage_s"].items():
        assert sum(ms for ms, _, _ in by_stage[stage]) == pytest.approx(
            seconds * 1e3 / steps, rel=1e-9, abs=1e-12), stage
    # the find and insert loops' own instructions lead the hash step
    ms, instruction, path = max(by_stage["probe"])
    assert ms > 1.0 and "probe" in path.split("/")

    stage_ops.main([TRACE, HLO, "3"])
    printed = capsys.readouterr().out.splitlines()
    assert printed[0] == f"{chips} chip(s), {steps} steps"
    heads = [line for line in printed if line.startswith("== ")]
    assert heads[0].startswith("== probe ")
    assert len(heads) == len(by_stage)
    assert instruction in "\n".join(printed)
    assert len(printed) <= 1 + 4 * len(heads)
