"""Compressed exchange (parallel/precision.py): parity matrix, int8_ef
error-feedback trajectory, byte-halving contracts (positive + negative),
at-rest bf16 HBM shrink, checkpoint precision migrations, quantization
observability, and the EnvConfig exchange section.

Tolerance derivations (documented, not guessed):

* bf16 wire rows: each pulled row crosses the wire through exactly ONE
  round-to-nearest bf16 cast (the residue accumulator fills every entry
  once — alltoall.exchange_pull), so |err| <= 2^-9 * |x| (8 explicit
  mantissa bits, RN). Asserted at 2^-8 relative for a 2x margin plus a
  tiny atol for subnormals.
* bf16 push: the pre-reduced gradient row is cast once before the
  owner's f32 optimizer math; adagrad's update is 1-Lipschitz in g up
  to the lr/sqrt(accum) factor, so one step's weight deviation is
  bounded by lr * 2^-8 * max|g| per element (same 2x margin).
* int8_ef: per-row max-abs/127 scale => one step's quantization error
  <= scale/2 per element. Error feedback recirculates it, so over a
  REPEATED batch the drift vs f32 stays O(one quantization step)
  instead of growing linearly — asserted empirically with margin, and
  asserted no worse than the feedback-free (fresh-residual) ablation.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402

from openembedding_tpu import checkpoint as ckpt              # noqa: E402
from openembedding_tpu.embedding import (EmbeddingCollection,  # noqa: E402
                                         EmbeddingSpec)
from openembedding_tpu.parallel import precision               # noqa: E402
from openembedding_tpu.parallel import sharded_table as st     # noqa: E402
from openembedding_tpu.parallel.mesh import create_mesh        # noqa: E402
from openembedding_tpu.utils import observability              # noqa: E402

VOCAB = 1024
DIM = 16
BATCH = 256

# |bf16(x) - x| <= 2^-9 |x| round-to-nearest; asserted with 2x margin
BF16_RTOL = 2.0 ** -8
BF16_ATOL = 1e-7


def _world(mesh, plane, *, dtype="float32", dim=DIM, vocab=VOCAB, **kw):
    spec = EmbeddingSpec(
        name="t", input_dim=vocab, output_dim=dim, dtype=dtype, plane=plane,
        optimizer={"category": "adagrad", "learning_rate": 0.1}, **kw)
    coll = EmbeddingCollection((spec,), mesh)
    return coll, coll.init(jax.random.PRNGKey(0))


def _hash_world(mesh, plane, *, dim=DIM, **kw):
    spec = EmbeddingSpec(
        name="t", input_dim=-1, output_dim=dim, hash_capacity=1 << 14,
        plane=plane,
        optimizer={"category": "adagrad", "learning_rate": 0.1}, **kw)
    coll = EmbeddingCollection((spec,), mesh)
    return coll, coll.init(jax.random.PRNGKey(0))


def _batch(rng, n=BATCH, vocab=VOCAB, dim=DIM, dtype=np.int32):
    idx = rng.randint(0, vocab, size=n).astype(dtype)
    g = rng.randn(n, dim).astype(np.float32)
    return idx, g


# --- plane-token grammar / spec validation -----------------------------------

def test_plane_token_parsing():
    assert precision.parse_plane("a2a+bf16") == ("a2a", "bf16", "bf16")
    assert precision.parse_plane("a2a+int8") == ("a2a", "bf16", "int8_ef")
    assert precision.parse_plane("a2a+grouped+bf16") == \
        ("a2a+grouped", "bf16", "bf16")
    assert precision.parse_plane("a2a") == ("a2a", "f32", "f32")
    assert precision.plane_label("a2a", "bf16", "f32") == "a2a+bf16"
    assert precision.plane_label("a2a", "bf16", "int8_ef") == "a2a+int8"
    assert precision.plane_label("psum", "f32", "f32") == "psum"


def test_spec_normalizes_plane_suffix():
    spec = EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                         plane="a2a+bf16")
    assert spec.plane == "a2a"
    assert spec.exchange_precision == "bf16"
    assert spec.push_precision == "bf16"


def test_illegal_precision_combos_raise():
    with pytest.raises(ValueError, match="psum"):
        EmbeddingSpec(name="x", input_dim=8, output_dim=2, plane="psum",
                      exchange_precision="bf16")
    with pytest.raises(ValueError, match="int8_ef"):
        EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                      plane="a2a+grouped", push_precision="int8_ef")
    with pytest.raises(ValueError, match="int8_ef"):
        EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                      plane="a2a+cache", push_precision="int8_ef")
    with pytest.raises(ValueError, match="explicitly"):
        # suffix vs explicit field conflict
        EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                      plane="a2a+int8", push_precision="bf16")
    with pytest.raises(ValueError, match="unknown exchange_precision"):
        EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                      exchange_precision="fp8")


# --- parity matrix -----------------------------------------------------------

def test_precision_f32_is_the_same_plane(devices8):
    """The f32 rung compiles the EXACT shipped program: same plane
    label (same lru-cached program object) and bitwise-equal results."""
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _world(mesh, "a2a")
    c1, s1 = _world(mesh, "a2a", exchange_precision="f32",
                    push_precision="f32")
    assert c1.sharding_spec("t").plane_label == "a2a"
    assert c0.sharding_spec("t") == c1.sharding_spec("t")
    rng = np.random.RandomState(0)
    idx, g = _batch(rng)
    r0 = c0.pull(s0, {"t": idx}, batch_sharded=False)["t"]
    r1 = c1.pull(s1, {"t": idx}, batch_sharded=False)["t"]
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    n0 = c0.apply_gradients(s0, {"t": idx}, {"t": g}, batch_sharded=False)
    n1 = c1.apply_gradients(s1, {"t": idx}, {"t": g}, batch_sharded=False)
    np.testing.assert_array_equal(np.asarray(n0["t"].weights),
                                  np.asarray(n1["t"].weights))


def test_bf16_pull_allclose_derived_tolerance(devices8):
    """bf16 wire rows: one RN cast per pulled row => |err| <= 2^-9|x|,
    asserted at 2^-8; and the wire is REALLY quantized (not f32)."""
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _world(mesh, "a2a")
    c1, s1 = _world(mesh, "a2a+bf16")
    rng = np.random.RandomState(1)
    idx, _ = _batch(rng)
    r0 = np.asarray(c0.pull(s0, {"t": idx}, batch_sharded=False)["t"])
    r1 = np.asarray(c1.pull(s1, {"t": idx}, batch_sharded=False)["t"])
    assert (np.abs(r1 - r0) <= np.abs(r0) * BF16_RTOL + BF16_ATOL).all()
    # exactly the bf16 rounding of the f32 rows — the wire carried bf16
    np.testing.assert_array_equal(
        r1, np.asarray(r0, dtype=jnp.bfloat16).astype(np.float32))
    assert not (r1 == r0).all()


def test_bf16_push_one_step_parity(devices8):
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _world(mesh, "a2a")
    c1, s1 = _world(mesh, "a2a+bf16")
    rng = np.random.RandomState(2)
    idx, g = _batch(rng)
    n0 = c0.apply_gradients(s0, {"t": idx}, {"t": g}, batch_sharded=False)
    n1 = c1.apply_gradients(s1, {"t": idx}, {"t": g}, batch_sharded=False)
    w0 = np.asarray(n0["t"].weights)
    w1 = np.asarray(n1["t"].weights)
    # adagrad: |dw| <= lr * |dg| / sqrt(accum0) with accum0 = 0.1 =>
    # bound = 0.1 * 2^-8 * max|g-sum| / sqrt(0.1) (2x-margined rtol)
    gmax = np.abs(g).max() * 4        # duplicate pre-reduce headroom
    bound = 0.1 * BF16_RTOL * gmax / np.sqrt(0.1)
    assert np.abs(w1 - w0).max() <= bound, (np.abs(w1 - w0).max(), bound)


@pytest.mark.slow
def test_bf16_parity_hash_wide(devices8):
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _hash_world(mesh, "a2a")
    c1, s1 = _hash_world(mesh, "a2a+bf16")
    rng = np.random.RandomState(3)
    idx = rng.randint(0, 1 << 40, size=BATCH).astype(np.int64)
    g = rng.randn(BATCH, DIM).astype(np.float32)
    r0 = np.asarray(c0.pull(s0, {"t": idx}, batch_sharded=False)["t"])
    r1 = np.asarray(c1.pull(s1, {"t": idx}, batch_sharded=False)["t"])
    assert (np.abs(r1 - r0) <= np.abs(r0) * BF16_RTOL + BF16_ATOL).all()
    n0 = c0.apply_gradients(s0, {"t": idx}, {"t": g}, batch_sharded=False)
    n1 = c1.apply_gradients(s1, {"t": idx}, {"t": g}, batch_sharded=False)
    w0 = np.asarray(n0["t"].weights, np.float32)
    w1 = np.asarray(n1["t"].weights, np.float32)
    bound = 0.1 * BF16_RTOL * np.abs(g).max() * 4 / np.sqrt(0.1)
    assert np.abs(w1 - w0).max() <= bound


@pytest.mark.slow
def test_bf16_parity_grouped(devices8):
    """The wire composes with the grouped plane: one bf16 round per
    GROUP, per-table rows still within the one-cast tolerance."""
    mesh = create_mesh(2, 4, devices8)

    def world(plane):
        specs = tuple(
            EmbeddingSpec(name=f"t{i}", input_dim=4096 + 64 * i,
                          output_dim=8, plane=plane,
                          optimizer={"category": "adagrad",
                                     "learning_rate": 0.1})
            for i in range(3))
        coll = EmbeddingCollection(specs, mesh)
        return coll, coll.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(4)
    idx = {f"t{i}": rng.randint(0, 4000, size=BATCH).astype(np.int32)
           for i in range(3)}
    g = {f"t{i}": rng.randn(BATCH, 8).astype(np.float32) for i in range(3)}
    c0, s0 = world("a2a+grouped")
    c1, s1 = world("a2a+grouped+bf16")
    r0 = c0.pull(s0, idx, batch_sharded=False)
    r1 = c1.pull(s1, idx, batch_sharded=False)
    for k in r0:
        a, b = np.asarray(r0[k]), np.asarray(r1[k])
        assert (np.abs(b - a) <= np.abs(a) * BF16_RTOL + BF16_ATOL).all()
    n1 = c1.apply_gradients(s1, idx, g, batch_sharded=False)
    assert set(n1) == set(s1)


# --- int8 error-feedback -----------------------------------------------------

def _drift_after(coll, states, idx, g, steps, *, reset_ef=False):
    for _ in range(steps):
        if reset_ef and isinstance(states["t"], precision.EFState):
            # feedback-free ablation: drop the residual every step
            states = dict(states)
            states["t"] = precision.unwrap(states["t"])
        states = coll.apply_gradients(states, {"t": idx}, {"t": g},
                                      batch_sharded=False)
    return states


def test_int8_ef_optimizer_trajectory_bound(devices8):
    """10-step fixed-batch trajectory: int8_ef drift vs f32 stays
    O(one quantization step) — and is never worse than the
    feedback-free ablation (the residual genuinely recirculates)."""
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _world(mesh, "a2a")
    c1, s1 = _world(mesh, "a2a+int8")
    rng = np.random.RandomState(5)
    idx, g = _batch(rng)
    steps = 10
    s0 = _drift_after(c0, s0, idx, g, steps)
    ef = _drift_after(c1, s1, idx, g, steps)
    c2, s2 = _world(mesh, "a2a+int8")
    noef = _drift_after(c2, s2, idx, g, steps, reset_ef=True)
    w0 = np.asarray(s0["t"].weights)
    wef = np.asarray(precision.unwrap(ef["t"]).weights)
    wno = np.asarray(precision.unwrap(noef["t"]).weights)
    d_ef = np.abs(wef - w0).max()
    d_no = np.abs(wno - w0).max()
    # one quantization step of the dequantized gradient reaching the
    # optimizer: scale/2 = max|g-row-sum|/254; through adagrad's
    # lr/sqrt(accum) that is at most lr * (4*gmax/254) / sqrt(0.1).
    # EF keeps the CUMULATIVE drift within a few such steps (errors
    # cancel instead of accumulating); 8x covers optimizer nonlinearity
    q = 0.1 * (4 * np.abs(g).max() / 254) / np.sqrt(0.1)
    assert d_ef <= 8 * q, (d_ef, q)
    # feedback must not hurt (equality possible on lucky seeds)
    assert d_ef <= d_no + 0.25 * q, (d_ef, d_no)
    # and the trajectory is meaningfully close to f32 overall
    assert d_ef <= 0.05 * max(1e-6, np.abs(w0).max())


def test_int8_ef_trajectory_hash_wide(devices8):
    """The wide-key (64-bit pair) residual matcher — mix/sort/verify in
    alltoall._match_prev_keys — driven over a repeated batch: drift vs
    f32 bounded AND no worse than the feedback-free ablation, so a
    matcher bug (wrong candidate, mix overflow) cannot ship silently as
    'int8 without feedback'."""
    mesh = create_mesh(2, 4, devices8)
    c0, s0 = _hash_world(mesh, "a2a", dim=8)
    c1, s1 = _hash_world(mesh, "a2a+int8", dim=8)
    c2, s2 = _hash_world(mesh, "a2a+int8", dim=8)
    rng = np.random.RandomState(13)
    idx = rng.randint(0, 1 << 40, size=128).astype(np.int64)
    g = rng.randn(128, 8).astype(np.float32)
    steps = 6

    def run(coll, states, reset_ef=False):
        for _ in range(steps):
            if reset_ef and isinstance(states["t"], precision.EFState):
                states = dict(states)
                states["t"] = precision.unwrap(states["t"])
            states = coll.apply_gradients(states, {"t": idx}, {"t": g},
                                          batch_sharded=False)
        return states

    s0 = run(c0, s0)
    ef = run(c1, s1)
    noef = run(c2, s2, reset_ef=True)
    assert isinstance(ef["t"], precision.EFState)
    assert ef["t"].keys.ndim == 2 and ef["t"].keys.shape[1] == 2
    assert float(jnp.abs(ef["t"].resid).max()) > 0
    w0 = np.asarray(s0["t"].weights, np.float32)
    wef = np.asarray(precision.unwrap(ef["t"]).weights, np.float32)
    wno = np.asarray(precision.unwrap(noef["t"]).weights, np.float32)
    d_ef = np.abs(wef - w0).max()
    d_no = np.abs(wno - w0).max()
    q = 0.1 * (4 * np.abs(g).max() / 254) / np.sqrt(0.1)
    assert d_ef <= 8 * q, (d_ef, q)
    assert d_ef <= d_no + 0.25 * q, (d_ef, d_no)


def test_int8_ef_state_threading(devices8):
    """EFState wraps the table after the first push, keeps a stable
    buffer across same-shape steps, and re-sizes on a batch change."""
    mesh = create_mesh(2, 4, devices8)
    coll, states = _world(mesh, "a2a+int8")
    assert isinstance(states["t"], precision.EFState)   # attached empty
    assert states["t"].keys.shape[0] == 0
    rng = np.random.RandomState(6)
    idx, g = _batch(rng)
    s1 = coll.apply_gradients(states, {"t": idx}, {"t": g},
                              batch_sharded=False)
    ef = s1["t"]
    assert isinstance(ef, precision.EFState)
    assert ef.keys.shape[0] > 0 and ef.resid.shape == \
        (ef.keys.shape[0], DIM)
    s2 = coll.apply_gradients(s1, {"t": idx}, {"t": g},
                              batch_sharded=False)
    assert s2["t"].keys.shape == ef.keys.shape
    # nonzero residual was actually stored (quantization is lossy)
    assert float(jnp.abs(s2["t"].resid).max()) > 0
    # batch-size change re-sizes the buffer instead of crashing
    idx2, g2 = _batch(rng, n=128)
    s3 = coll.apply_gradients(s2, {"t": idx2}, {"t": g2},
                              batch_sharded=False)
    assert s3["t"].keys.shape[0] != ef.keys.shape[0]
    # pulls read through the wrapper
    rows = coll.pull(s3, {"t": idx}, batch_sharded=False)["t"]
    assert rows.shape == (BATCH, DIM)


def test_quant_observability_counters(devices8):
    mesh = create_mesh(2, 4, devices8)
    observability.GLOBAL.reset()
    observability.set_evaluate_performance(True)
    try:
        coll, states = _world(mesh, "a2a+int8")
        rng = np.random.RandomState(7)
        idx, g = _batch(rng)
        for _ in range(2):
            states = coll.apply_gradients(states, {"t": idx}, {"t": g},
                                          batch_sharded=False)
        jax.block_until_ready(jax.tree.leaves(states))
        import time
        time.sleep(0.2)     # debug.callback drains asynchronously
        snap = observability.GLOBAL.snapshot()
        assert snap.get("quant_residual_norm", {}).get("count", 0) > 0
        assert snap.get("quant_error_max", {}).get("count", 0) > 0
        text = observability.prometheus_text()
        assert "oe_quant_residual_norm_total" in text
        assert "oe_quant_error_max_total" in text
    finally:
        observability.set_evaluate_performance(False)
        observability.GLOBAL.reset()


# --- byte-halving contracts --------------------------------------------------

def test_compressed_byte_contracts_array(devices8):
    """Compiled-HLO-measured: bf16/int8 exchange bytes <= 0.55x the f32
    plane's, pull and push separately (the acceptance-criteria audit,
    same code path tools.graftcheck runs in CI)."""
    from openembedding_tpu.analysis import contracts, programs
    mesh = create_mesh(2, 4, devices8)
    dim, batch = 64, 256      # the ratio binds at dim >= 32 (registry)
    base = {}
    for prog, lower in (("pull", programs.lower_pull),
                        ("push", programs.lower_push)):
        base[prog], _ = lower(mesh, "a2a", batch=batch, dim=dim)
    for plane in ("a2a+bf16", "a2a+int8"):
        for prog, lower in (("pull", programs.lower_pull),
                            ("push", programs.lower_push)):
            txt, params = lower(mesh, plane, batch=batch, dim=dim)
            res = contracts.check_compressed_program(
                txt, base[prog], plane, prog, **params)
            assert res["ratio"] <= 0.55
    # int8 push is far below even the halving bound
    txt, params = programs.lower_push(mesh, "a2a+int8", batch=batch,
                                      dim=dim)
    res = contracts.check_compressed_program(txt, base["push"],
                                             "a2a+int8", "push", **params)
    assert res["ratio"] <= 0.35


def test_f32_plane_under_compressed_bound_is_caught(devices8):
    """The negative the acceptance criteria demand: an f32 program
    registered under a compressed contract must FAIL — both via the
    wire-width inventory bound and via the byte-halving ratio."""
    from openembedding_tpu.analysis import contracts, programs
    mesh = create_mesh(2, 4, devices8)
    txt, params = programs.lower_pull(mesh, "a2a", batch=256, dim=64)
    params = dict(params)
    params["wire_itemsize"] = 2
    with pytest.raises(contracts.ContractViolation):
        contracts.check_program(txt, "a2a+bf16", "pull", **params)
    with pytest.raises(contracts.ContractViolation, match="NOT compress"):
        contracts.check_byte_halving(txt, txt, label="f32-as-bf16")


@pytest.mark.slow
def test_compressed_byte_contracts_hash(devices8):
    from openembedding_tpu.analysis import contracts, programs
    mesh = create_mesh(2, 4, devices8)
    dim, batch = 64, 256
    for prog, lower in (("pull", programs.lower_pull),
                        ("push", programs.lower_push)):
        base, _ = lower(mesh, "a2a", batch=batch, dim=dim, use_hash=True)
        for plane in ("a2a+bf16", "a2a+int8"):
            txt, params = lower(mesh, plane, batch=batch, dim=dim,
                                use_hash=True)
            res = contracts.check_compressed_program(
                txt, base, plane, prog, **params)
            assert res["ratio"] <= 0.55


# --- at-rest bf16 ------------------------------------------------------------

def test_at_rest_bf16_halves_weight_hbm(devices8):
    """bf16 tables + f32 slots: the memwatch-ledger shrink — weight
    bytes halve, slot bytes stay f32, and the exchange still runs."""
    mesh = create_mesh(2, 4, devices8)
    cf, sf = _world(mesh, "a2a", dtype="float32")
    cb, sb = _world(mesh, "a2a", dtype="bfloat16")
    wf, wb = sf["t"].weights, sb["t"].weights
    assert wb.dtype == jnp.bfloat16 and wb.nbytes * 2 == wf.nbytes
    for k in sf["t"].slots:
        assert sb["t"].slots[k].dtype == jnp.float32
        assert sb["t"].slots[k].nbytes == sf["t"].slots[k].nbytes
    rng = np.random.RandomState(8)
    idx, g = _batch(rng)
    rows = cb.pull(sb, {"t": idx}, batch_sharded=False)["t"]
    assert rows.dtype == jnp.bfloat16
    nb = cb.apply_gradients(sb, {"t": idx},
                            {"t": g.astype(jnp.bfloat16)},
                            batch_sharded=False)
    assert nb["t"].weights.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.float32 for v in nb["t"].slots.values())


def test_at_rest_bf16_memory_ledger_shrink(devices8):
    """The compiled-program argument bytes (memwatch ledger axis)
    shrink by the weights' half when the table goes bf16."""
    from openembedding_tpu.analysis import programs
    from openembedding_tpu.utils import jaxcompat
    mesh = create_mesh(2, 4, devices8)

    def arg_bytes(dtype):
        import jax as _jax
        coll = EmbeddingCollection(
            (EmbeddingSpec(name="t", input_dim=1 << 14, output_dim=16,
                           dtype=dtype,
                           optimizer={"category": "default"}),), mesh)
        states = coll.init(_jax.random.PRNGKey(0))
        return sum(x.nbytes for x in _jax.tree.leaves(states))

    f32 = arg_bytes("float32")
    bf16 = arg_bytes("bfloat16")
    # the stateless optimizer has no slots: state = weights -> exact half
    assert bf16 * 2 == f32


# --- checkpoint format (tpu-2) -----------------------------------------------

def _two_var_coll(mesh, dtype):
    specs = (EmbeddingSpec(name="arr", input_dim=512, output_dim=8,
                           dtype=dtype),
             EmbeddingSpec(name="hsh", input_dim=-1, output_dim=8,
                           dtype=dtype, hash_capacity=512))
    return EmbeddingCollection(
        specs, mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})


def _trained(coll):
    rng = np.random.RandomState(9)
    idx = {"arr": rng.randint(0, 512, size=64).astype(np.int32),
           "hsh": rng.randint(0, 10000, size=64).astype(np.int64)}
    g = {k: rng.randn(64, 8).astype(np.float32) for k in idx}
    states = coll.init(jax.random.PRNGKey(0))
    states = coll.apply_gradients(states, idx, g, batch_sharded=False)
    return states, idx


def test_bf16_checkpoint_local_roundtrip(devices8, tmp_path):
    """The LOCAL memmap dump of a bf16 table (numpy stores '<V2' void
    rows) round-trips bit-exactly — the storage_dtypes record added in
    meta format tpu-2."""
    mesh = create_mesh(2, 4, devices8)
    coll = _two_var_coll(mesh, "bfloat16")
    states, idx = _trained(coll)
    before = coll.pull(states, idx, batch_sharded=False)
    ckpt.save_checkpoint(str(tmp_path / "m"), coll, states)
    loaded = ckpt.load_checkpoint(str(tmp_path / "m"), coll)
    after = coll.pull(loaded, idx, batch_sharded=False)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k], np.float32),
                                      np.asarray(after[k], np.float32))
    assert loaded["arr"].weights.dtype == jnp.bfloat16
    assert all(v.dtype == jnp.float32
               for v in loaded["arr"].slots.values())


def test_bf16_dump_routes_through_compress(devices8, tmp_path):
    """compress='zlib' sends the bf16 rows through utils/compress.py's
    framed .npyz streams; the loader views the V2 frames back under the
    recorded true dtype."""
    mesh = create_mesh(2, 4, devices8)
    coll = _two_var_coll(mesh, "bfloat16")
    states, idx = _trained(coll)
    before = coll.pull(states, idx, batch_sharded=False)
    ckpt.save_checkpoint(str(tmp_path / "m"), coll, states,
                         compress="zlib")
    vdir = tmp_path / "m" / "var_0_arr.d"
    names = os.listdir(vdir)
    assert any(f.endswith(".npyz") for f in names), names
    loaded = ckpt.load_checkpoint(str(tmp_path / "m"), coll)
    after = coll.pull(loaded, idx, batch_sharded=False)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k], np.float32),
                                      np.asarray(after[k], np.float32))


def test_precision_migration_and_tpu1_compat(devices8, tmp_path):
    """(1) an OLD 'tpu-1' f32 checkpoint (no storage_dtypes) loads
    transparently; (2) f32 dump -> bf16 table downcasts; (3) bf16 dump
    -> f32 table upcasts exactly."""
    import json
    mesh = create_mesh(2, 4, devices8)
    cf = _two_var_coll(mesh, "float32")
    sf, idx = _trained(cf)
    before = cf.pull(sf, idx, batch_sharded=False)
    p = tmp_path / "old"
    ckpt.save_checkpoint(str(p), cf, sf)
    meta = json.loads((p / "model_meta").read_text())
    assert meta["version"] == "tpu-2"
    # rewrite as a legacy tpu-1 checkpoint: old version, no dtype record
    meta["version"] = "tpu-1"
    meta["extra"].pop("storage_dtypes")
    (p / "model_meta").write_text(json.dumps(meta))
    loaded = ckpt.load_checkpoint(str(p), cf)
    after = cf.pull(loaded, idx, batch_sharded=False)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k]),
                                      np.asarray(after[k]))
    # f32 (legacy) dump -> bf16 collection: transparent downcast
    cb = _two_var_coll(mesh, "bfloat16")
    down = ckpt.load_checkpoint(str(p), cb)
    assert down["arr"].weights.dtype == jnp.bfloat16
    # bf16 dump -> f32 collection: transparent upcast, exact values
    sb, idxb = _trained(cb)
    beforeb = cb.pull(sb, idxb, batch_sharded=False)
    p2 = tmp_path / "bf16"
    ckpt.save_checkpoint(str(p2), cb, sb)
    up = ckpt.load_checkpoint(str(p2), cf)
    assert up["arr"].weights.dtype == jnp.float32
    afterb = cf.pull(up, idxb, batch_sharded=False)
    for k in beforeb:
        np.testing.assert_array_equal(np.asarray(beforeb[k], np.float32),
                                      np.asarray(afterb[k]))


def test_int8_ef_state_never_checkpointed(devices8, tmp_path):
    """EFState is derived: the dump holds only the table; a restore
    re-attaches an empty residual (one step of feedback forfeited)."""
    mesh = create_mesh(2, 4, devices8)
    spec = EmbeddingSpec(name="t", input_dim=512, output_dim=8,
                         plane="a2a+int8",
                         optimizer={"category": "adagrad",
                                    "learning_rate": 0.1})
    coll = EmbeddingCollection((spec,), mesh)
    states = coll.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(10)
    idx = rng.randint(0, 512, size=64).astype(np.int32)
    g = rng.randn(64, 8).astype(np.float32)
    states = coll.apply_gradients(states, {"t": idx}, {"t": g},
                                  batch_sharded=False)
    assert float(jnp.abs(states["t"].resid).max()) > 0
    before = coll.pull(states, {"t": idx}, batch_sharded=False)["t"]
    ckpt.save_checkpoint(str(tmp_path / "m"), coll, states)
    loaded = ckpt.load_checkpoint(str(tmp_path / "m"), coll)
    assert isinstance(loaded["t"], precision.EFState)
    assert loaded["t"].keys.shape[0] == 0          # fresh residual
    after = coll.pull(loaded, {"t": idx}, batch_sharded=False)["t"]
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


def test_int8_ef_single_shard_structure_stable(devices8):
    """On a single-device mesh the push has no wire: int8_ef degrades
    to the exact masked-local program, and the state pytree STRUCTURE
    must not flip (EFState at init -> TableState after a push would
    force a retrace of a donated step jit every second step)."""
    mesh = create_mesh(1, 1, devices8[:1])
    spec = EmbeddingSpec(name="t", input_dim=64, output_dim=4,
                         plane="a2a+int8",
                         optimizer={"category": "adagrad",
                                    "learning_rate": 0.1})
    coll = EmbeddingCollection((spec,), mesh)
    states = coll.init(jax.random.PRNGKey(0))
    assert not isinstance(states["t"], precision.EFState)
    rng = np.random.RandomState(11)
    idx = rng.randint(0, 64, size=16).astype(np.int32)
    g = rng.randn(16, 4).astype(np.float32)
    new = coll.apply_gradients(states, {"t": idx}, {"t": g},
                               batch_sharded=False)
    assert type(new["t"]) is type(states["t"])


def test_legacy_tpu1_bf16_slot_dump_loads(devices8, tmp_path):
    """A PRE-ladder tpu-1 dump of a bf16 table stored its SLOTS at the
    table dtype (bf16, opaque '<V2') — today's slot target is f32, so
    the decoder must fall back to the dump's table dtype, not fail on
    the itemsize mismatch."""
    import glob
    import json
    import ml_dtypes
    mesh = create_mesh(2, 4, devices8)
    coll = EmbeddingCollection(
        (EmbeddingSpec(name="arr", input_dim=256, output_dim=8,
                       dtype="bfloat16"),), mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    states = coll.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(12)
    idx = rng.randint(0, 256, size=32).astype(np.int32)
    g = rng.randn(32, 8).astype(np.float32)
    states = coll.apply_gradients(states, {"arr": idx}, {"arr": g},
                                  batch_sharded=False)
    p = tmp_path / "m"
    ckpt.save_checkpoint(str(p), coll, states)
    for f in glob.glob(str(p / "var_0_arr.d" / "slot_*.npy")):
        np.save(f, np.load(f).astype(ml_dtypes.bfloat16))
    meta = json.loads((p / "model_meta").read_text())
    meta["version"] = "tpu-1"
    meta["extra"].pop("storage_dtypes")
    (p / "model_meta").write_text(json.dumps(meta))
    loaded = ckpt.load_checkpoint(str(p), coll)
    acc = loaded["arr"].slots["accum"]
    assert acc.dtype == jnp.float32
    # the values are the stored bf16 accum, upcast — not garbage bits
    want = np.asarray(jax.device_get(states["arr"].slots["accum"])
                      ).astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(jax.device_get(acc)), want)


# --- EnvConfig ---------------------------------------------------------------

def test_envconfig_exchange_section():
    from openembedding_tpu.utils.envconfig import EnvConfig
    cfg = EnvConfig.load(config={"exchange": {"precision": "bf16",
                                              "push_precision": "int8_ef"}})
    assert cfg.exchange.spec_kwargs() == {
        "exchange_precision": "bf16", "push_precision": "int8_ef"}
    spec = EmbeddingSpec(name="x", input_dim=8, output_dim=2,
                         **cfg.exchange.spec_kwargs())
    assert spec.push_precision == "int8_ef"
    with pytest.raises(ValueError, match="bf16"):
        EnvConfig.load(config={"exchange": {"precision": "fp8"}})
    env = {"OE_EXCHANGE_PRECISION": "bf16"}
    assert EnvConfig.load(env=env).exchange.precision == "bf16"


# --- model-zoo AUC parity (slow) ---------------------------------------------

@pytest.mark.slow
def test_auc_parity_compressed_zoo(devices8):
    """Compressed vs f32 on the learnable task: the fully-compressed
    plane (bf16 pull + int8_ef push) trains to the same AUC within
    0.002 absolute — the end-to-end quality gate of the ladder."""
    import optax
    from openembedding_tpu import Trainer
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.utils.observability import StreamingAUC

    mesh = create_mesh(2, 4, devices8)

    def run(plane):
        specs = deepctr.make_feature_specs(
            ("f",), 256, 8, plane=plane,
            optimizer={"category": "adagrad", "learning_rate": 0.5})
        coll = EmbeddingCollection(specs, mesh)
        trainer = Trainer(deepctr.build_model("deepfm", ("f",)), coll,
                          optax.adam(1e-2))
        rng = np.random.RandomState(0)

        def batch():
            ids = rng.randint(0, 256, 256).astype(np.int32)
            label = ((ids.astype(np.int64) * 2654435761) % 3
                     == 0).astype(np.float32)
            return {"label": label,
                    "dense": rng.randn(256, 4).astype(np.float32) * 0,
                    "sparse": {"f": ids, "f:linear": ids}}

        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batch()))
        state, _ = trainer.fit(state, (batch() for _ in range(60)))
        auc = StreamingAUC()
        rng2 = np.random.RandomState(42)
        for _ in range(4):
            ids = rng2.randint(0, 256, 256).astype(np.int32)
            label = ((ids.astype(np.int64) * 2654435761) % 3
                     == 0).astype(np.float32)
            b = {"label": label,
                 "dense": np.zeros((256, 4), np.float32),
                 "sparse": {"f": ids, "f:linear": ids}}
            auc.update(label, np.asarray(trainer.eval_step(state, b)))
        return auc.result()

    auc_f32 = run("a2a")
    auc_c = run("a2a+int8")
    assert auc_f32 > 0.9, f"f32 zoo run did not learn: {auc_f32:.4f}"
    assert abs(auc_c - auc_f32) <= 0.002, (auc_c, auc_f32)
