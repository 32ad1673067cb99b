"""Compressed exchange: the precision ladder of the sparse data planes.

The reference compresses its RPC payloads with byte codecs
(snappy/lz4/zlib, ``server.message_compress`` —
/root/reference/openembedding/client/EnvConfig.cpp:27-34, RpcView.h
compress path) to keep the pull/push exchange off the critical path. The
TPU-native analogue of wire compression is *precision*, not codecs:

* ``exchange.precision = "bf16"`` — pulled rows cross the all-to-all
  wire (and the row-assembly all-gather) as bfloat16 and are upcast
  after the row leg. Master weights and optimizer slots stay float32 in
  the shard; only the WIRE narrows, so the quantization is one
  round-to-nearest cast per pulled row (|err| <= 2^-9 · |x|).
* ``push.precision = "bf16"`` — the pre-reduced gradient rows ride the
  push exchange as bfloat16 (keys/counts stay int32), upcast before the
  owner's f32 optimizer math.
* ``push.precision = "int8_ef"`` — per-row max-abs scale int8
  quantization of the pre-reduced gradients with an **error-feedback
  residual**: the quantization error of each sent row is carried in
  :class:`EFState` (threaded through ``TrainState.emb``) and added back
  into the next gradient this sender pre-reduces for the same key, so
  the error is recirculated, not lost. Residuals are positional per
  (device, slice) — a key that hops to a different sender before
  recurring forfeits that one step's residual (bounded, never
  compounding: the residual is overwritten, not accumulated).

Plane token grammar: the ladder composes with the shipped planes as a
plane-string suffix — ``"a2a+bf16"`` = base ``"a2a"`` with bf16 wire
rows both directions; ``"a2a+int8"`` = bf16 pull + int8_ef push (the
fully-compressed plane). ``EmbeddingSpec.exchange_precision`` /
``push_precision`` select the rungs independently; the suffix is
shorthand for the canonical combinations (and the label the contract
registry, graftscope ledger and plane_timed spans all key on).

Where a program has no wire there is nothing to compress: single-shard
meshes and the ``psum`` ablation plane run at full precision regardless
(``psum`` + a compressed rung is rejected at spec construction), and
``precision = "f32"`` compiles byte-identical programs to the shipped
planes — the parity matrix asserts exact ``==`` there.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax.numpy as jnp
from flax import struct

# pull-wire rungs (exchange.precision) and push-wire rungs (push.precision)
EXCHANGE_PRECISIONS = ("f32", "bf16")
PUSH_PRECISIONS = ("f32", "bf16", "int8_ef")

# plane-token suffix -> (exchange_precision, push_precision)
PLANE_SUFFIXES = {
    "+bf16": ("bf16", "bf16"),
    "+int8": ("bf16", "int8_ef"),
}

# base planes int8_ef may ride: the EF residual lives per (device,
# slice) next to the per-table exchange — the grouped plane's
# concatenated multi-table streams and the cache plane's replica psum
# would each need their own residual story, and psum has no routed wire
INT8_EF_PLANES = ("a2a",)


def parse_plane(plane: str) -> Tuple[str, str, str]:
    """``plane`` token -> (base_plane, exchange_precision, push_precision).

    ``"a2a+bf16"`` -> ``("a2a", "bf16", "bf16")``; tokens without a
    precision suffix come back at the f32 rung.
    """
    for suffix, (ep, pp) in PLANE_SUFFIXES.items():
        if plane.endswith(suffix):
            return plane[: -len(suffix)], ep, pp
    return plane, "f32", "f32"


def plane_label(base_plane: str, exchange_precision: str,
                push_precision: str) -> str:
    """Canonical observable label of a (plane, precision) combination.

    The label keys the contract registry, the graftscope byte ledger
    AND the plane_timed span histograms — pull and push of one spec
    share it, so the ledger join lines up. Mixed non-canonical combos
    (e.g. bf16 pull + f32 push) label ``+bf16``; anything int8 labels
    ``+int8``.
    """
    if push_precision == "int8_ef":
        return base_plane + "+int8"
    if "bf16" in (exchange_precision, push_precision):
        return base_plane + "+bf16"
    return base_plane


def wire_dtype(precision: str):
    """jnp dtype rows take on the wire for one rung (None = no cast)."""
    return jnp.bfloat16 if precision == "bf16" else None


def wire_itemsize(precision: str, *, f32_itemsize: int = 4) -> int:
    """Per-element bytes of gradient/row payload on the wire."""
    if precision == "bf16":
        return 2
    if precision == "int8_ef":
        return 1
    return f32_itemsize


def check_spec_precision(base_plane: str, exchange_precision: str,
                         push_precision: str, *, name: str = "") -> None:
    """Validate one spec's precision rungs against its base plane."""
    where = f"embedding {name!r}: " if name else ""
    if exchange_precision not in EXCHANGE_PRECISIONS:
        raise ValueError(
            f"{where}unknown exchange_precision {exchange_precision!r}; "
            f"known: {EXCHANGE_PRECISIONS}")
    if push_precision not in PUSH_PRECISIONS:
        raise ValueError(
            f"{where}unknown push_precision {push_precision!r}; "
            f"known: {PUSH_PRECISIONS}")
    compressed = (exchange_precision, push_precision) != ("f32", "f32")
    if base_plane == "psum" and compressed:
        raise ValueError(
            f"{where}the psum ablation plane has no routed wire to "
            "compress; keep precision='f32' or use an a2a plane")
    if push_precision == "int8_ef" and base_plane not in INT8_EF_PLANES:
        raise ValueError(
            f"{where}push_precision='int8_ef' rides only the per-table "
            f"owner exchange (base planes {INT8_EF_PLANES}); "
            f"{base_plane!r} needs its own residual story — use 'bf16'")


# --- error-feedback residual state -------------------------------------------

@struct.dataclass
class EFState:
    """Authoritative table + the int8_ef push residual, one pytree.

    ``keys``/``resid`` are the previous step's per-sender unique keys
    and quantization errors, positionally sharded over the exchange
    grid (dim 0 = ``num_devices * slice_uniq_capacity``; each device
    owns its own block inside the push's shard_map). Threaded through
    ``TrainState.emb`` like the hot-row replica's ``CachedState`` —
    derived state: checkpoints dump only ``table`` and a restore
    re-attaches an empty residual (one step of feedback forfeited,
    never correctness).
    """

    table: Any                    # TableState | HashTableState
    keys: jnp.ndarray             # [P*m] or [P*m, kw] int32, sentinel-padded
    resid: jnp.ndarray            # [P*m, dim] float32


def unwrap(state: Any) -> Any:
    """The authoritative table of a possibly-EF-wrapped state."""
    return state.table if isinstance(state, EFState) else state


def empty_ef(table_state: Any, *, dim: int, wide: bool,
             sentinel: int, key_dtype=jnp.int32) -> EFState:
    """Fresh zero-length residual (attached at init/restore; the first
    push sizes it for its batch shape and every later step reuses it)."""
    kshape = (0, 2) if wide else (0,)
    return EFState(
        table=table_state,
        keys=jnp.full(kshape, sentinel, key_dtype),
        resid=jnp.zeros((0, dim), jnp.float32))


def ef_global_len(n_flat_global: int, data: int, model: int,
                  batch_sharded: bool) -> int:
    """dim-0 length of the global EF arrays for one push batch shape.

    Mirrors the exchange's slice math: each of the ``data`` batch
    slices is divided among its ``model`` peers (or the whole grid when
    the batch is replicated), and every device carries one
    ``m``-entry residual block.
    """
    if batch_sharded:
        n_local = -(-n_flat_global // data)
        m = -(-n_local // model)
    else:
        m = -(-n_flat_global // (data * model))
    return data * model * m


def ef_key_space(*, use_hash: bool, wide: bool = False, key_dtype=None
                 ) -> Tuple[int, Any]:
    """(sentinel, key_dtype) of one table's EF key buffer.

    THE single derivation shared by spec-level wrapping
    (``EmbeddingCollection.wrap_hot_cache``) and push-dispatch sizing
    (the array/hash ``ensure_ef`` call sites) — if these ever
    disagreed, ``sized_ef`` would silently reset the residual every
    step (pure lossy int8, feedback forfeited). Array streams and wide
    pairs carry int32 words; narrow hash tables keep their own key
    dtype. Both sentinel families (``dedup.FILL``,
    ``hash_table.empty_key``) are the dtype's minimum, so one rule
    covers all tables.
    """
    kd = jnp.int32 if (not use_hash or wide) else jnp.dtype(key_dtype)
    return int(jnp.iinfo(kd).min), kd


def ensure_ef(state: Any, *, dim: int, wide: bool, sentinel: int,
              n_flat: int, data: int, model: int, batch_sharded: bool,
              key_dtype=jnp.int32
              ) -> Tuple[Any, jnp.ndarray, jnp.ndarray]:
    """(table, ef_keys, ef_resid) for one int8_ef push dispatch.

    The shared prologue of the array and hash apply paths: unwrap a
    possibly-EF-wrapped state (serving restores may hand a bare
    table), fall back to an empty residual, and size the buffers for
    this push's batch shape (``ef_global_len``/``sized_ef`` — a fresh
    or wrong-shape buffer forfeits one step of feedback, never
    correctness).
    """
    table = unwrap(state)
    ef = state if isinstance(state, EFState) \
        else empty_ef(table, dim=dim, wide=wide, sentinel=sentinel,
                      key_dtype=key_dtype)
    glen = ef_global_len(n_flat, data, model, batch_sharded)
    keys, resid = sized_ef(ef, glen, dim=dim, wide=wide,
                           sentinel=sentinel, key_dtype=key_dtype)
    return table, keys, resid


def sized_ef(ef: EFState, glen: int, *, dim: int, wide: bool,
             sentinel: int, key_dtype=jnp.int32
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(keys, resid) buffers of exactly ``glen`` rows for this push.

    A buffer from a different batch shape (or the fresh empty one) is
    replaced by sentinel-keys/zero-residual — one step of feedback
    forfeited; steady-state training reuses one shape and keeps all of
    it.
    """
    if ef.keys.shape[0] == glen and ef.resid.shape[0] == glen \
            and (ef.keys.ndim == 2) == wide \
            and ef.keys.dtype == jnp.dtype(key_dtype):
        return ef.keys, ef.resid
    kshape = (glen, 2) if wide else (glen,)
    return (jnp.full(kshape, sentinel, key_dtype),
            jnp.zeros((glen, dim), jnp.float32))
