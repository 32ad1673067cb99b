"""graftwatch memory ledger: jaxcompat shim, peak-temp contract, rows.

Covers the ISSUE-7 tentpole surface: ``compiled_memory_stats`` yields
per-device numbers (None on a backend that reports no analysis), the peak-temp bound arithmetic (pull = batch
scratch only; push earns exactly one declined-donation state
materialization; honored donation collapses the allowance), a synthetic
shard-sized-materialization injection caught at the calibrated audit
sizes, and a real lowered plane program's ledger row enforced end to
end. The full plane matrix runs in ``tools/graftcheck`` (CI).
"""

import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu.analysis import contracts, memwatch
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.utils import jaxcompat


def test_compiled_memory_stats_shim():
    compiled = jax.jit(lambda x: x * 2 + 1).lower(
        jnp.zeros((256, 64), jnp.float32)).compile()
    mem = jaxcompat.compiled_memory_stats(compiled)
    assert mem is not None
    for key in ("argument_bytes", "output_bytes", "temp_bytes",
                "alias_bytes", "generated_code_bytes", "peak_bytes"):
        assert isinstance(mem[key], int) and mem[key] >= 0, key
    assert mem["argument_bytes"] == 256 * 64 * 4
    assert mem["peak_bytes"] == max(
        0, mem["argument_bytes"] + mem["output_bytes"]
        + mem["temp_bytes"] - mem["alias_bytes"])


def test_compiled_memory_stats_absent_analysis_is_none():
    """A backend that reports no analysis reads as absent data."""

    class ReturnsNone:
        def memory_analysis(self):
            return None

    assert jaxcompat.compiled_memory_stats(ReturnsNone()) is None


_AUDIT_PARAMS = {"global_batch": 512, "dim": 16, "itemsize": 4,
                 "num_shards": 8, "num_tables": 1,
                 "table_shard_bytes": 8 << 20,
                 "state_shard_bytes": 16 << 20}


def test_peak_temp_bound_arithmetic():
    pull = contracts.peak_temp_bound(_AUDIT_PARAMS, "pull")
    push = contracts.peak_temp_bound(_AUDIT_PARAMS, "push")
    batch_scratch = contracts.TEMP_BATCH_FACTOR * 512 * 18 * 4 * 8
    assert pull == contracts.TEMP_FLOOR_BYTES + batch_scratch
    # push earns exactly one (slack-padded) unaliased state copy on top
    assert push == pull + int(contracts.TEMP_STATE_SLACK * (16 << 20))
    # donation honored (alias covers the state) -> the allowance is gone
    assert contracts.peak_temp_bound(
        _AUDIT_PARAMS, "push", alias_bytes=16 << 20) == pull


def test_peak_temp_catches_shard_sized_materialization():
    """At the calibrated audit sizes an extra table-shard-sized buffer
    in temp busts the bound for both program kinds — the memory-level
    twin of the max_copy_bytes audit."""
    shard = _AUDIT_PARAMS["table_shard_bytes"]
    # pull: legit scratch passes, scratch + one shard fails
    ok_pull = {"temp_bytes": 64 << 10, "alias_bytes": 0}
    contracts.check_peak_temp_bytes(ok_pull, _AUDIT_PARAMS,
                                    program="pull")
    with pytest.raises(contracts.ContractViolation, match="peak-temp"):
        contracts.check_peak_temp_bytes(
            {"temp_bytes": (64 << 10) + shard, "alias_bytes": 0},
            _AUDIT_PARAMS, program="pull")
    # push: the one declined-donation state copy passes, a second
    # shard-sized materialization on top fails
    state = _AUDIT_PARAMS["state_shard_bytes"]
    contracts.check_peak_temp_bytes(
        {"temp_bytes": state + (64 << 10), "alias_bytes": 0},
        _AUDIT_PARAMS, program="push")
    with pytest.raises(contracts.ContractViolation, match="peak-temp"):
        contracts.check_peak_temp_bytes(
            {"temp_bytes": state + (64 << 10) + shard, "alias_bytes": 0},
            _AUDIT_PARAMS, program="push")


def test_registered_planes_cover_the_registry():
    planes = memwatch.registered_planes()
    assert {"psum", "a2a", "a2a+cache", "a2a+grouped"} <= set(planes)


def test_plane_memory_row_enforced(devices8):
    """One real lowering end to end: the a2a pull/push ledger rows carry
    per-device numbers and PASS the enforced peak-temp contract (the
    push row exercises the declined-donation state term — the CPU
    backend never aliases)."""
    mesh = create_mesh(2, 4, devices8)
    pull = memwatch.plane_memory(mesh, "a2a", "pull", batch=256, dim=8,
                                 vocab=1 << 16, check=True)
    assert pull.mem is not None and pull.temp_bound is not None
    assert pull.mem["argument_bytes"] > 0
    # read-only pull: temp is batch scratch, far under one weights shard
    assert pull.mem["temp_bytes"] < pull.params["table_shard_bytes"]
    push = memwatch.plane_memory(mesh, "a2a", "push", batch=256, dim=8,
                                 vocab=1 << 16, check=True)
    assert push.mem is not None
    assert push.mem["temp_bytes"] <= push.temp_bound
    # the params carry the audit inputs the bound consumed
    assert push.params["state_shard_bytes"] > 0
    table = memwatch.format_memory_table([pull, push])
    assert "a2a" in table and "temp_cap" in table


def test_memory_row_without_analysis_reports_absent():
    """A backend without memory analysis yields mem=None rows (absence
    reported, not punished) — graftcheck's CLI is what escalates a
    blind ledger to a failure."""
    row = memwatch.MemoryRow(plane="a2a", program="pull", kind="array",
                             mem=None, params={})
    out = memwatch.format_memory_table([row])
    assert "n/a" in out
    assert row.as_dict()["plane"] == "a2a"
