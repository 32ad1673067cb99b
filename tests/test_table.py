"""Core table semantics: pull/apply, dedup, initializer behavior."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import (EmbeddingVariableMeta, apply_gradients,
                               create_table, make_optimizer, pull)
from openembedding_tpu import table as table_lib
from openembedding_tpu.ops import dedup


def make(vocab=16, dim=4, opt="sgd", init=None):
    meta = EmbeddingVariableMeta(embedding_dim=dim, vocabulary_size=vocab)
    optimizer = make_optimizer(opt)
    return meta, optimizer, create_table(meta, optimizer, init,
                                         rng=jax.random.PRNGKey(0))


def test_pull_shapes():
    _, _, state = make()
    out = pull(state, jnp.array([[1, 2], [3, 3]]))
    assert out.shape == (2, 2, 4)
    np.testing.assert_array_equal(out[1, 0], out[1, 1])


def test_initializers_deterministic_and_ranged():
    meta = EmbeddingVariableMeta(embedding_dim=8, vocabulary_size=100)
    opt = make_optimizer("default")
    a = create_table(meta, opt, {"category": "uniform", "minval": -0.5, "maxval": 0.5},
                     rng=jax.random.PRNGKey(7))
    b = create_table(meta, opt, {"category": "uniform", "minval": -0.5, "maxval": 0.5},
                     rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a.weights), np.asarray(b.weights))
    assert float(a.weights.min()) >= -0.5 and float(a.weights.max()) <= 0.5
    c = create_table(meta, opt, {"category": "constant", "value": 2.5})
    assert float(c.weights.min()) == float(c.weights.max()) == 2.5
    n = create_table(meta, opt, {"category": "normal", "stddev": 0.1, "truncated": True})
    assert float(jnp.abs(n.weights).max()) <= 0.2 + 1e-6


def test_untouched_rows_unchanged():
    _, opt, state = make(opt={"category": "sgd", "learning_rate": 1.0})
    before = np.asarray(state.weights).copy()
    idx = jnp.array([2, 5])
    g = jnp.ones((2, 4))
    state2 = apply_gradients(state, opt, idx, g)
    after = np.asarray(state2.weights)
    touched = {2, 5}
    for r in range(16):
        if r in touched:
            assert not np.allclose(before[r], after[r])
        else:
            np.testing.assert_array_equal(before[r], after[r])


def test_duplicates_summed_once():
    # one update with summed grad, not N momentum updates
    _, opt, state = make(opt={"category": "sgd", "learning_rate": 0.1, "momentum": 0.9})
    idx = jnp.array([3, 3, 3])
    g = jnp.ones((3, 4))
    state2 = apply_gradients(state, opt, idx, g)
    # moment = 0*0.9 + 0.1*3 = 0.3 ; weight -= 0.3
    np.testing.assert_allclose(np.asarray(state2.slots["moment"])[3],
                               np.full(4, 0.3), rtol=1e-6)
    delta = np.asarray(state.weights - state2.weights)[3]
    np.testing.assert_allclose(delta, np.full(4, 0.3), rtol=1e-6)


def test_dedup_capacity_padding():
    idx = jnp.array([5, 1, 5, 9, 1, 1], dtype=jnp.int32)
    uniq, inverse, valid = dedup.unique_indices(idx, capacity=6)
    assert uniq.shape == (6,)
    assert int(valid.sum()) == 3
    np.testing.assert_array_equal(np.asarray(uniq)[np.asarray(inverse)],
                                  np.asarray(idx))
    g = jnp.ones((6, 2))
    summed, counts = dedup.combine_gradients(g, inverse, 6)
    got = {int(u): int(c) for u, c, v in
           zip(np.asarray(uniq), np.asarray(counts), np.asarray(valid)) if v}
    assert got == {1: 3, 5: 2, 9: 1}
    assert float(summed.sum()) == 12.0


def test_jit_apply_under_vocab_smaller_than_batch():
    _, opt, state = make(vocab=4, opt={"category": "adagrad", "learning_rate": 0.1})
    idx = jnp.array([0, 1, 2, 3, 0, 1, 2, 3, 0])
    g = jnp.ones((9, 4))
    step = jax.jit(lambda s: apply_gradients(s, opt, idx, g))
    state2 = step(state)
    assert np.isfinite(np.asarray(state2.weights)).all()


def test_negative_index_dropped_not_wrapped():
    _, opt, state = make(vocab=8, opt={"category": "sgd", "learning_rate": 1.0})
    before = np.asarray(state.weights).copy()
    state2 = apply_gradients(state, opt, jnp.array([-3]), jnp.ones((1, 4)))
    np.testing.assert_array_equal(before, np.asarray(state2.weights))


def test_bool_config_strings():
    from openembedding_tpu import make_initializer
    assert make_optimizer({"category": "sgd", "nesterov": "true"}).nesterov is True
    assert make_optimizer({"category": "sgd", "nesterov": "false"}).nesterov is False
    assert make_initializer({"category": "normal", "truncated": "false"}).truncated is False


def test_bfloat16_adam_beta_slots_float32():
    meta = EmbeddingVariableMeta(datatype="bfloat16", embedding_dim=4,
                                 vocabulary_size=8)
    opt = make_optimizer("adam")
    state = create_table(meta, opt)
    assert state.weights.dtype == jnp.bfloat16
    assert state.slots["beta_1_t"].dtype == jnp.float32
    state2 = apply_gradients(state, opt, jnp.array([1]), jnp.ones((1, 4), jnp.bfloat16))
    assert state2.weights.dtype == jnp.bfloat16
    assert state2.slots["beta_2_t"].dtype == jnp.float32
    np.testing.assert_allclose(float(state2.slots["beta_2_t"][1, 0]), 0.999)


def test_float64_requires_x64():
    import pytest as _pytest
    meta = EmbeddingVariableMeta(datatype="float64", embedding_dim=2,
                                 vocabulary_size=4)
    with _pytest.raises(ValueError, match="x64"):
        create_table(meta, make_optimizer("sgd"))


# --- the chunked sparse apply (table.apply_rows) ---------------------------

CHUNK = 8       # the module constant, set small for these tests
ADAGRAD = {"category": "adagrad", "learning_rate": 0.5}


def dyadic(rng, shape):
    """Multiples of 1/8 in [-2, 2]: sums of a few of them, their squares
    and their halves are exact in float32, so neither the order of a
    scatter-add nor a fused multiply-add can move a bit."""
    return (rng.integers(-16, 17, size=shape) / 8).astype(np.float32)


def numpy_adagrad(w, accum, g, lr=np.float32(0.5), eps=np.float32(1e-7)):
    accum = accum + g * g
    return w - lr * g / (np.sqrt(accum) + eps), accum


def numpy_apply(weights, accum, indices, grads, in_counts=None):
    """Plain apply of the array table's contract: every distinct id in
    ``[0, rows)`` is updated once, with the sum of its gradients."""
    weights, accum = weights.copy(), accum.copy()
    for row in sorted(set(int(i) for i in indices)):
        if 0 <= row < weights.shape[0]:
            g = grads[indices == row].sum(axis=0, dtype=np.float32)
            weights[row], accum[row] = numpy_adagrad(weights[row],
                                                     accum[row], g)
    return weights, accum


def apply_case(case, rows=64):
    """(indices, bound): ids of one push and one past the last live slot of
    its unique buffer (``jnp.unique`` sorts: negatives first and dead, ids
    past the table last and live, their writes dropped)."""
    rng = np.random.default_rng(29)
    distinct = rng.permutation(rows)
    if case == "no_live_row":
        return np.array([-1, -5, -1] * 7), 0
    if case == "one_live_row":
        return np.full(3 * CHUNK, 7), 1
    if case == "one_chunk":
        return np.resize(distinct[:CHUNK], 3 * CHUNK), CHUNK
    if case == "one_chunk_and_one":
        return np.resize(distinct[:CHUNK + 1], 3 * CHUNK), CHUNK + 1
    if case == "ragged_capacity":       # 21 slots in chunks of 8
        return np.resize(distinct[:19], 21), 19
    if case == "every_slot_live":
        return distinct[:3 * CHUNK], 3 * CHUNK
    if case == "negative_and_out_of_range":
        return np.concatenate([distinct[:10], [-3, -3, -1, rows, rows + 9],
                               distinct[:5]]), 3 + 10 + 2
    if case == "fits_one_chunk":        # no loop: the body once
        return np.resize(distinct[:3], CHUNK), None
    raise ValueError(case)


APPLY_CASES = ["no_live_row", "one_live_row", "one_chunk",
               "one_chunk_and_one", "ragged_capacity", "every_slot_live",
               "negative_and_out_of_range", "fits_one_chunk"]


def recorded(run):
    """{counter: sum} of what ``run`` records under the statistics gate."""
    from openembedding_tpu.utils import observability
    observability.GLOBAL.reset()
    observability.set_evaluate_performance(True)
    try:
        out = jax.block_until_ready(run())
        jax.effects_barrier()
    finally:
        observability.set_evaluate_performance(False)
    got = observability.GLOBAL.snapshot()
    observability.GLOBAL.reset()
    return out, {k: int(v["count"]) for k, v in got.items()}


@pytest.mark.parametrize("in_counts", [False, True], ids=["", "in_counts"])
@pytest.mark.parametrize("dim", [4, 1])     # one-element rows: written late
@pytest.mark.parametrize("case", APPLY_CASES)
def test_chunked_apply_is_the_plain_apply_bit_for_bit(monkeypatch, case, dim,
                                                      in_counts):
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", CHUNK)
    rng = np.random.default_rng(7)
    indices, bound = apply_case(case)
    n, rows = len(indices), 64
    _, opt, state = make(vocab=rows, dim=dim, opt=ADAGRAD)
    grads = dyadic(rng, (n, dim))
    counts = rng.integers(1, 4, size=n) if in_counts else None
    new, stats = recorded(lambda: jax.jit(
        lambda s, i, g, c: apply_gradients(
            s, opt, i, g, in_counts=c, record_stats=True))(
                state, jnp.asarray(indices, jnp.int32), grads, counts))
    want_w, want_a = numpy_apply(np.asarray(state.weights),
                                 np.asarray(state.slots["accum"]),
                                 indices, grads)
    np.testing.assert_array_equal(np.asarray(new.weights), want_w)
    np.testing.assert_array_equal(np.asarray(new.slots["accum"]), want_a)
    live = sum(1 for i in set(indices.tolist()) if i >= 0)
    walked = n if bound is None else -(-bound // CHUNK) * CHUNK
    assert stats == {"apply_slots_live": live, "apply_slots_walked": walked}


@pytest.mark.parametrize("optimizer", ["adam", "test"])
@pytest.mark.parametrize("case", APPLY_CASES)
def test_chunked_apply_writes_what_one_pass_writes(monkeypatch, case,
                                                   optimizer):
    """Every optimizer slot, against the same apply with the whole buffer
    as one chunk (the sequence before the loop): Adam's per-row beta
    powers are slots of width 1, and ``test`` divides by the counts."""
    rng = np.random.default_rng(11)
    indices, _ = apply_case(case)
    _, opt, state = make(vocab=64, dim=4, opt=optimizer)
    args = (state, jnp.asarray(indices, jnp.int32),
            rng.normal(size=(len(indices), 4)).astype(np.float32),
            jnp.asarray(rng.integers(1, 4, size=len(indices))))

    def run(chunk):
        monkeypatch.setattr(table_lib, "APPLY_CHUNK", chunk)
        return jax.jit(lambda s, i, g, c: apply_gradients(
            s, opt, i, g, in_counts=c))(*args)

    one_pass, chunked = run(1 << 20), run(CHUNK)
    for got, want in zip(jax.tree.leaves(chunked), jax.tree.leaves(one_pass)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_apply_loop_only_past_one_chunk(monkeypatch):
    monkeypatch.setattr(table_lib, "APPLY_CHUNK", CHUNK)
    _, opt, state = make(vocab=64, dim=4, opt=ADAGRAD)

    def whiles(n):
        return jax.jit(lambda s, i, g: apply_gradients(s, opt, i, g)).lower(
            state, jnp.zeros((n,), jnp.int32),
            jnp.zeros((n, 4))).compile().as_text().count(" while(")

    assert (whiles(CHUNK), whiles(CHUNK + 1)) == (0, 1)
