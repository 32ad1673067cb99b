"""Per-plane compiled-program contracts (analysis/contracts.py).

The registry must (a) PASS on every shipped plane's pull/push program on
an 8-device virtual mesh — the contracts describe reality — and (b)
CATCH a deliberately broken sharding annotation — the contracts have
teeth. The whole-train-step audit proves donation is honored and no
host transfer hides inside the jitted step.
"""

import pytest

from openembedding_tpu.analysis import contracts, programs
from openembedding_tpu.parallel.mesh import create_mesh

B, DIM = 1024, 16


@pytest.mark.parametrize(
    "plane",
    ["psum", "a2a", "a2a+cache"])
def test_pull_push_contracts_array(devices8, plane):
    mesh = create_mesh(2, 4, devices8)
    txt, params = programs.lower_pull(mesh, plane, batch=B, dim=DIM)
    summary = contracts.check_program(txt, plane, "pull", **params)
    if plane != "psum":
        assert summary["all-to-all"][0] >= 1
    else:
        assert "all-to-all" not in summary

    txt, params = programs.lower_push(mesh, plane, batch=B, dim=DIM)
    contracts.check_program(txt, plane, "push", **params)


@pytest.mark.slow
@pytest.mark.parametrize("plane", ["a2a", "a2a+cache"])
def test_pull_push_contracts_hash(devices8, plane):
    """Slow lane (tier-1 budget): the hash planes recompile everything
    from scratch (~25 s); tier-1 keeps the array matrix above and
    `tools/graftcheck` covers hash in CI."""
    mesh = create_mesh(2, 4, devices8)
    txt, params = programs.lower_pull(mesh, plane, batch=B, dim=DIM,
                                      use_hash=True)
    contracts.check_program(txt, plane, "pull", **params)
    txt, params = programs.lower_push(mesh, plane, batch=B, dim=DIM,
                                      use_hash=True)
    contracts.check_program(txt, plane, "push", **params)


def test_grouped_one_exchange_set_per_group(devices8):
    """THE grouped-plane claim: a 3-table collection compiles to exactly
    ONE exchange collective set (num_groups == 1), not one per table —
    the all-to-all inventory equals a single-table a2a program's, where
    the per-table loop would compile 3x that."""
    mesh = create_mesh(2, 4, devices8)
    a2a_ops = programs.count_exchange_a2a(mesh, "pull", batch=B, dim=DIM)
    txt, params = programs.lower_grouped_pull(mesh, tables=3, batch=B,
                                              dim=DIM, a2a_ops=a2a_ops)
    assert params["num_groups"] == 1 and params["num_tables"] == 3
    summary = contracts.check_program(txt, "a2a+grouped", "pull", **params)
    assert summary["all-to-all"][0] == a2a_ops
    assert summary["all-to-all"][0] < params["num_tables"] * a2a_ops


@pytest.mark.slow
def test_grouped_push_contract(devices8):
    """Push half of the launch-count claim (tier-1 keeps the pull half;
    `tools/graftcheck` audits both in CI)."""
    mesh = create_mesh(2, 4, devices8)
    push_ops = programs.count_exchange_a2a(mesh, "push", batch=B, dim=DIM)
    txt, params = programs.lower_grouped_push(mesh, tables=3, batch=B,
                                              dim=DIM, a2a_ops=push_ops)
    summary = contracts.check_program(txt, "a2a+grouped", "push", **params)
    assert summary["all-to-all"][0] == push_ops


def test_grouped_exchange_unit_counted_at_stream_size(devices8,
                                                      monkeypatch):
    """Calibration fix (ISSUE 7 satellite): the launch-count cap's
    per-exchange unit must be counted at the group's CONCATENATED
    stream size (num_tables * batch), not the per-table batch — XLA's
    all-to-all split count depends on the exchanged buffer size, so the
    per-table unit undercounts below the split threshold (batch 256
    compiled 8 grouped ops against a cap of 4, forcing CI to pin batch
    512). graftcheck/graftscope at batch 256 cover the compiled end;
    this pins the counting rule itself."""
    mesh = create_mesh(2, 4, devices8)
    asked = []

    def fake_count(mesh, program, **kw):
        asked.append(kw["batch"])
        return 8

    monkeypatch.setattr(programs, "count_exchange_a2a", fake_count)
    coll = programs._grouped_collection(mesh, tables=3, vocab=1 << 14,
                                        dim=16, use_hash=False)
    params = programs.grouped_params(mesh, coll, tuple(coll.specs),
                                     batch=256, dim=16, program="pull")
    assert asked == [3 * 256]
    assert params["a2a_ops_per_exchange"] == 8
    # an explicit a2a_ops skips the count entirely (test/CLI callers)
    asked.clear()
    programs.grouped_params(mesh, coll, tuple(coll.specs), batch=256,
                            dim=16, program="pull", a2a_ops=4)
    assert asked == []
    # MULTI-group plan: the unit counts at the WIDEST group's stream,
    # not the whole collection's — num_tables * batch would inflate the
    # unit past what any one group exchanges and slacken the cap
    from openembedding_tpu.embedding import (EmbeddingCollection,
                                             EmbeddingSpec)
    specs = tuple(
        EmbeddingSpec(name=f"m{i}", input_dim=(1 << 14) + 64 * i,
                      output_dim=dim, plane="a2a+grouped")
        for i, dim in enumerate((16, 16, 16, 64)))
    multi = EmbeddingCollection(specs, mesh)
    asked.clear()
    params = programs.grouped_params(mesh, multi, tuple(multi.specs),
                                     batch=256, dim=16, program="pull")
    assert params["num_groups"] == 2 and params["num_tables"] == 4
    assert asked == [3 * 256]           # widest group has 3 members


def test_grouped_broken_annotation_caught(devices8):
    """Replicating the grouped pull output re-gathers each table's rows
    in a separate buffer — each below the single-buffer bound, so the
    TOTAL-bytes budget is what must catch it."""
    mesh = create_mesh(2, 4, devices8)
    txt, params = programs.lower_grouped_pull(mesh, tables=3, batch=B,
                                              dim=DIM, a2a_ops=8,
                                              out_replicated=True)
    with pytest.raises(contracts.ContractViolation, match="total"):
        contracts.check_program(txt, "a2a+grouped", "pull", **params)


@pytest.mark.slow
def test_grouped_contracts_hash(devices8):
    """Hash groups carry an explicit (key..., tag) column stream; same
    launch-count contract. Slow lane like the other hash lowerings."""
    mesh = create_mesh(2, 4, devices8)
    a2a_ops = programs.count_exchange_a2a(mesh, "pull", batch=B, dim=DIM)
    txt, params = programs.lower_grouped_pull(mesh, tables=3, batch=B,
                                              dim=DIM, use_hash=True,
                                              a2a_ops=a2a_ops)
    contracts.check_program(txt, "a2a+grouped", "pull", **params)
    push_ops = programs.count_exchange_a2a(mesh, "push", batch=B, dim=DIM)
    txt, params = programs.lower_grouped_push(mesh, tables=3, batch=B,
                                              dim=DIM, use_hash=True,
                                              a2a_ops=push_ops)
    contracts.check_program(txt, "a2a+grouped", "push", **params)


def test_broken_sharding_annotation_caught(devices8):
    """Replicating the pull output (a one-line sharding regression)
    forces a global-batch gather — the contract must fail it."""
    mesh = create_mesh(2, 4, devices8)
    txt, params = programs.lower_pull(mesh, "a2a", batch=B, dim=DIM,
                                      out_replicated=True)
    with pytest.raises(contracts.ContractViolation, match="all-gather"):
        contracts.check_program(txt, "a2a", "pull", **params)


def test_train_step_contract(devices8):
    """The whole jitted step: donation honored (tables updated in
    place), no f64, no host transfer, and no table-sized copy.

    vocab/dim are sized so each table shard (vocab*dim*4/8 = 512 KiB)
    dwarfs every dense buffer — a copy at or above shard size can only
    be a table that lost its donation.
    """
    mesh = create_mesh(2, 4, devices8)
    vocab, dim = 1 << 16, 16
    txt, params = programs.lower_train_step(mesh, "a2a", vocab=vocab,
                                            dim=dim, batch=256)
    contracts.check_program(txt, "any", "step", **params)
    aliased = contracts.donated_params(txt)
    assert len(aliased) >= 4, aliased   # tables + slots + dense + opt
    table_shard_bytes = vocab * dim * 4 // mesh.size
    assert contracts.max_copy_bytes(txt) < table_shard_bytes


def test_step_with_record_stats_contains_callback(devices8):
    """Sanity for the host-transfer audit: when the observability gate
    is ON the pull program legitimately carries a host callback — the
    audit must SEE it (and the default program must not have one)."""
    from openembedding_tpu.utils import observability as obs
    mesh = create_mesh(2, 4, devices8)
    txt, _ = programs.lower_pull(mesh, "a2a", batch=B, dim=DIM)
    assert contracts.host_transfer_ops(txt) == []
    obs.set_evaluate_performance(True)
    try:
        txt_rec, _ = programs.lower_pull(mesh, "a2a", batch=B, dim=DIM)
    finally:
        obs.set_evaluate_performance(False)
    assert "host-callback" in contracts.host_transfer_ops(txt_rec)
    with pytest.raises(contracts.ContractViolation, match="host"):
        contracts.check_no_host_transfers(txt_rec)


def test_registry_unknown_key():
    with pytest.raises(KeyError, match="no contract registered"):
        contracts.check_program("", "nope", "pull", batch_slice=1, dim=1)
