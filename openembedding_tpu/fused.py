"""Fused feature groups: many categorical features, one table, one gather.

The reference keeps one PS variable per Embedding layer and pays one pull RPC
fan-out per variable per batch (SURVEY §3.2). On TPU the same per-variable
layout costs one XLA gather + collectives *per feature* — 26 Criteo features
become 52 small kernels and 52 separately-compiled table programs. The
TPU-native answer (DLRM-style) is to **fuse all same-config features into one
table**:

* bounded vocabs: fused row space is the concatenation of member vocabs;
  feature f's id i maps to ``offset[f] + i``. One ``[B, F]`` indices array,
  one pull, one ``[B, F, dim]`` result.
* hash (unbounded) vocabs: feature f's key k maps to ``k * F + f`` — member
  key spaces are interleaved, so one open-addressing table serves all
  features. (With int32 keys this divides the usable per-feature key space by
  F; use ``key_dtype='wide'`` — [B, F, 2] pair keys, x64 OFF — or
  ``key_dtype='int64'`` under x64 for the full reference-scale space.)

Semantically identical to per-feature variables (offsets are disjoint;
out-of-range ids still yield zero rows and dropped gradients) while cutting
program count and kernel launches by 2F, and giving XLA one large gather that
tiles well onto the MXU pipeline.

``make_fused_specs`` + ``FusedMapper`` are the public surface; the model zoo
accepts the fused layout directly (rows["fields"] of shape [B, F, dim]).

Fusion requires HOMOGENEOUS features (one dim, one optimizer, one table
config). The heterogeneous counterpart is the grouped exchange plane
(``parallel/grouped.py``, ``plane="a2a+grouped"``): tables stay separate
(per-table dims/optimizers/serving) but the collection batches each
same-shape GROUP into one routed exchange per step, reusing exactly this
disjoint-offset trick (``alltoall.segment_offsets``) for array groups.
Prefer fused when you can, grouped when dims/configs differ.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from .analysis.lint import host_fn
from .embedding import EmbeddingSpec
from .parallel.alltoall import segment_offsets

FUSED_NAME = "fields"
LINEAR_SUFFIX = ":linear"


@dataclasses.dataclass(frozen=True)
class FusedMapper:
    """Static map from per-feature id columns to fused table ids."""

    feature_names: Tuple[str, ...]
    vocab_sizes: Tuple[int, ...]        # -1 everywhere => hash fusion
    name: str = FUSED_NAME
    need_linear: bool = True
    key_dtype: str = "wide"             # hash fusion default: [B, F, 2]
                                        # pair keys, full 64-bit space with
                                        # x64 OFF; "int32" opts into the
                                        # 31-bit mixed space

    @property
    def use_hash(self) -> bool:
        return self.vocab_sizes[0] == -1

    @property
    def num_features(self) -> int:
        return len(self.feature_names)

    @property
    def offsets(self) -> np.ndarray:
        # the same static exclusive prefix sums the grouped exchange
        # plane uses for its array-group bases (parallel/grouped.py)
        return np.asarray(segment_offsets(self.vocab_sizes)[:-1],
                          dtype=np.int64)

    @property
    def total_vocab(self) -> int:
        return int(sum(self.vocab_sizes))

    @host_fn
    def fuse(self, sparse: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Per-feature columns -> {name: [B, F] fused ids} (+ :linear twin).

        Host-side (numpy) BY CONTRACT (``@host_fn``): runs in the input
        pipeline like the reference's dataset-map hashing
        (criteo_deepctr.py:202-240); calling it on tracers inside a
        jitted step is exactly what graftlint rule JG002 flags.

        The ``:linear`` entry is the SAME array object as the table's, not
        a copy: ``Trainer.train_step`` sees that on the host
        (``EmbeddingCollection.same_columns``) and the one-chip step then
        deduplicates the column once for both tables (one ``dedup.Plan``).
        A pipeline that copies it still shares, after one comparison.
        """
        cols = [np.asarray(sparse[f]) for f in self.feature_names]
        ids = np.stack(cols, axis=1)  # [B, F]
        if self.use_hash:
            from .utils.hashing import mix64
            F = np.int64(self.num_features)
            fused = ids.astype(np.int64) * F + np.arange(
                self.num_features, dtype=np.int64)[None, :]
            if self.key_dtype == "wide":
                # full 64-bit interleaved key space carried as [B, F, 2]
                # int32 (lo, hi) pairs — no truncation, no x64 flag. The
                # pair encoding excludes keys with hi == INT32_MIN (the
                # EMPTY band); ids near 2^63/F can wrap into it, so those
                # keys are remapped up one hi step — a 2^-32 alias band,
                # far below the reference's own 2^62 hash-collision rate
                from . import hash_table as _ht
                pairs = _ht.split64(fused)
                band = pairs[..., 1] == _ht.empty_key(np.int32)
                if band.any():
                    pairs[..., 1][band] = _ht.empty_key(np.int32) + 1
                fused = pairs
            elif ids.dtype == np.int32:
                # avalanche-mix before truncating to 31 bits: F shares a
                # factor with 2^31, so a plain mask would alias distinct
                # features onto the same row in a structured way
                fused = (mix64(fused) & np.uint64(2**31 - 1)).astype(np.int64)
                fused = fused.astype(ids.dtype)
            else:
                fused = fused.astype(ids.dtype)
        else:
            vocab = np.asarray(self.vocab_sizes, dtype=np.int64)[None, :]
            valid = (ids >= 0) & (ids < vocab)
            fused = np.where(valid, ids + self.offsets[None, :], -1)
            fused = fused.astype(np.int32 if self.total_vocab < 2**31
                                 else np.int64)
        out = {self.name: fused}
        if self.need_linear:
            out[self.name + LINEAR_SUFFIX] = fused
        return out

    def fuse_batch(self, batch: Dict) -> Dict:
        """Convenience: rewrite a {'label','dense','sparse'} batch in place."""
        return {**batch, "sparse": self.fuse(batch["sparse"])}


def make_fused_specs(feature_names: Sequence[str],
                     vocab_sizes,
                     embedding_dim: int,
                     *,
                     name: str = FUSED_NAME,
                     need_linear: bool = True,
                     dtype: str = "float32",
                     optimizer: Any = None,
                     initializer: Any = None,
                     hash_capacity: int = 2**20,
                     key_dtype: str = "wide",
                     num_shards: int = -1,
                     plane: str = "a2a",
                     a2a_capacity: int = 0,
                     a2a_slack: float = 2.0,
                     cache_k: int = 0,
                     cache_refresh_every: int = 64,
                     cache_decay: float = 0.8,
                     exchange_precision: str = "f32",
                     push_precision: str = "f32"
                     ) -> Tuple[Tuple[EmbeddingSpec, ...], FusedMapper]:
    """Specs + mapper for one fused table over ``feature_names``.

    ``vocab_sizes``: per-feature ints, a single int, or -1 for hash fusion.
    Returns (specs, mapper): one dim-k spec named ``name`` plus (optionally)
    one dim-1 ``name:linear`` spec — the fused analogue of
    ``models.deepctr.make_feature_specs``.
    """
    if isinstance(vocab_sizes, int):
        vocab_sizes = [vocab_sizes] * len(feature_names)
    if len(vocab_sizes) != len(feature_names):
        raise ValueError("vocab_sizes must match feature_names")
    hash_members = [v == -1 for v in vocab_sizes]
    if any(hash_members) and not all(hash_members):
        raise ValueError("cannot fuse hash (-1) and bounded vocabs in one "
                         "group; make two groups")
    mapper = FusedMapper(feature_names=tuple(feature_names),
                         vocab_sizes=tuple(int(v) for v in vocab_sizes),
                         name=name, need_linear=need_linear,
                         key_dtype=key_dtype)
    input_dim = -1 if mapper.use_hash else mapper.total_vocab
    emb_init = initializer or {"category": "normal", "mean": 0.0,
                               "stddev": 1e-4}
    specs = [EmbeddingSpec(
        name=name, input_dim=input_dim, output_dim=embedding_dim,
        dtype=dtype, optimizer=optimizer, initializer=emb_init,
        hash_capacity=hash_capacity, key_dtype=key_dtype,
        num_shards=num_shards, plane=plane,
        a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
        cache_k=cache_k, cache_refresh_every=cache_refresh_every,
        cache_decay=cache_decay,
        exchange_precision=exchange_precision,
        push_precision=push_precision)]
    if need_linear:
        specs.append(EmbeddingSpec(
            name=name + LINEAR_SUFFIX, input_dim=input_dim, output_dim=1,
            dtype=dtype, optimizer=optimizer,
            initializer={"category": "constant", "value": 0.0},
            hash_capacity=hash_capacity, key_dtype=key_dtype,
            num_shards=num_shards, plane=plane,
            a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
            cache_k=cache_k, cache_refresh_every=cache_refresh_every,
            cache_decay=cache_decay,
            exchange_precision=exchange_precision,
            push_precision=push_precision))
    return tuple(specs), mapper
