"""Tables fed the same ids (a fused table and its ``:linear`` twin: the
same host array, or an equal copy) run ONE plan a step, which leaves what a
plan each leaves, bit for bit, on one device and routed over 2x2; routed,
also what the step without any plan leaves.
"""

import pytest

from openembedding_tpu.embedding import SameColumns

from plan_helpers import STEPS, _fused_steps, _no_plan, _same


def _a_plan_each(coll):
    """What the step ran before tables shared a plan: nothing is seen to
    be the same."""
    coll.same_columns = lambda inputs: SameColumns()


@pytest.mark.parametrize("twin,seen", [
    ("same", "plan_columns_same_object"),
    ("copy", "plan_columns_compared_equal")])
@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "a2a2x2"])
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_tables_fed_one_column_share_one_plan(kind, twin, seen, shape):
    """The same array or an equal copy: one plan a step for the two tables,
    and losses, dense state, tables and scores are what a plan each
    leaves, bit for bit; routed over a 2x2 mesh they are also what the
    step without any plan leaves (held for the mapper's own batch)."""
    want = _fused_steps(kind, twin, _a_plan_each, shape)
    assert want[2] == {"dedup_plans_built": 2 * STEPS,
                       "dedup_plan_tables": 2 * STEPS}
    got = _fused_steps(kind, twin, None, shape)
    assert got[2] == {seen: STEPS, "dedup_plans_built": STEPS,
                      "dedup_plan_tables": 2 * STEPS}
    assert got[0] == want[0]
    _same(got[1], want[1])
    if shape != (1, 1) and twin == "same":
        alone = _fused_steps(kind, twin, _no_plan, shape)
        assert alone[2] == {seen: STEPS}
        assert got[0] == alone[0]
        _same(got[1], alone[1])
