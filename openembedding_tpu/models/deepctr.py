"""CTR model zoo: LR, Wide&Deep, DeepFM, xDeepFM as flax modules.

Capability parity with the reference's model families — its examples train
DeepCTR's WDL/DeepFM/xDeepFM over embedding layers
(/root/reference/examples/criteo_deepctr_network.py:33-51,
/root/reference/test/benchmark/criteo_deepctr.py WDL/DeepFM/xDeepFM switch)
and an LR subclass model (/root/reference/examples/criteo_lr_subclass.py).

Design: these modules hold ONLY the dense math. Embedding rows are pulled by
the EmbeddingCollection outside the module and passed in as a dict
``rows[name] -> [B, dim]`` (dim-k field embeddings) and
``rows[name + ':linear'] -> [B, 1]`` (first-order weights), mirroring
DeepCTR's embedding_dim-k / linear split. That keeps the flax params purely
dense (replicated, optax-updated) while the sparse variables stay on the
sharded PS-equivalent path — the same split the reference draws between
tf.Variables and PS variables. A feature and its ``:linear`` twin read the
same id column: fed the same array (or equal ones), they share the one-chip
step's dedup, one ``dedup.Plan`` for both (``EmbeddingCollection.plan``).

``LINEAR_SUFFIX`` features are created by ``linear_spec_names`` /
``make_feature_specs`` in this module so models and spec builders agree.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..embedding import EmbeddingSpec

LINEAR_SUFFIX = ":linear"


def make_feature_specs(feature_names: Sequence[str],
                       vocab_sizes,
                       embedding_dim: int,
                       *,
                       need_linear: bool = True,
                       dtype: str = "float32",
                       optimizer: Any = None,
                       initializer: Any = None,
                       hash_capacity: int = 2**20,
                       num_shards: int = -1,
                       plane: str = "a2a",
                       a2a_capacity: int = 0,
                       a2a_slack: float = 2.0,
                       cache_k: int = 0,
                       cache_refresh_every: int = 64,
                       cache_decay: float = 0.8,
                       exchange_precision: str = "f32",
                       push_precision: str = "f32"
                       ) -> Tuple[EmbeddingSpec, ...]:
    """Build the spec list for a set of categorical features.

    ``vocab_sizes``: int per feature, or a single int, or -1 for the hash
    space (reference input_dim=-1, exb.py:231-233). Each feature gets a dim-k
    spec plus (for models with a linear term) a dim-1 ``:linear`` spec —
    DeepCTR's linear_feature_columns equivalent.
    """
    if isinstance(vocab_sizes, int):
        vocab_sizes = [vocab_sizes] * len(feature_names)
    if len(vocab_sizes) != len(feature_names):
        raise ValueError("vocab_sizes must match feature_names")
    emb_init = initializer or {"category": "normal", "mean": 0.0,
                               "stddev": 1e-4}
    specs = []
    for name, vocab in zip(feature_names, vocab_sizes):
        specs.append(EmbeddingSpec(
            name=name, input_dim=vocab, output_dim=embedding_dim,
            dtype=dtype, optimizer=optimizer, initializer=emb_init,
            hash_capacity=hash_capacity, num_shards=num_shards, plane=plane,
            a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
            cache_k=cache_k, cache_refresh_every=cache_refresh_every,
            cache_decay=cache_decay,
            exchange_precision=exchange_precision,
            push_precision=push_precision))
        if need_linear:
            specs.append(EmbeddingSpec(
                name=name + LINEAR_SUFFIX, input_dim=vocab, output_dim=1,
                dtype=dtype, optimizer=optimizer,
                initializer={"category": "constant", "value": 0.0},
                hash_capacity=hash_capacity, num_shards=num_shards,
                plane=plane, a2a_capacity=a2a_capacity,
                a2a_slack=a2a_slack, cache_k=cache_k,
                cache_refresh_every=cache_refresh_every,
                cache_decay=cache_decay,
                exchange_precision=exchange_precision,
                push_precision=push_precision))
    return tuple(specs)


FUSED_NAME = "fields"


def _stack_fields(rows: Dict[str, jnp.ndarray],
                  names: Sequence[str]) -> jnp.ndarray:
    """[B, F, dim] field-major embedding block.

    Accepts either the per-feature layout (one [B, dim] entry per name —
    reference-style one variable per Embedding layer) or the fused layout
    (a single [B, F, dim] entry under ``FUSED_NAME`` from ``fused.py``).
    """
    if FUSED_NAME in rows:
        return rows[FUSED_NAME]
    return jnp.stack([rows[n] for n in names], axis=1)


def _linear_term(rows: Dict[str, jnp.ndarray],
                 names: Sequence[str]) -> jnp.ndarray:
    """Sum of first-order (dim-1) embeddings -> [B]."""
    fused = FUSED_NAME + LINEAR_SUFFIX
    if fused in rows:
        return jnp.sum(rows[fused], axis=(-2, -1))
    lin = jnp.concatenate([rows[n + LINEAR_SUFFIX] for n in names], axis=-1)
    return jnp.sum(lin, axis=-1)


class MLP(nn.Module):
    """Plain ReLU tower (DeepCTR dnn_hidden_units equivalent)."""

    units: Sequence[int]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        for u in self.units:
            x = nn.relu(nn.Dense(u, dtype=self.dtype)(x))
        return x


class LogisticRegression(nn.Module):
    """criteo_lr_subclass.py equivalent: sum of per-feature weights + dense."""

    feature_names: Tuple[str, ...]

    @nn.compact
    def __call__(self, dense, rows):
        logit = _linear_term(rows, self.feature_names)
        if dense is not None:
            logit = logit + nn.Dense(1)(dense).reshape(-1)
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return logit + bias[0]


class WideDeep(nn.Module):
    """Wide&Deep: linear (wide) + MLP over field embeddings (deep)."""

    feature_names: Tuple[str, ...]
    dnn_units: Tuple[int, ...] = (256, 128)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, dense, rows):
        wide = _linear_term(rows, self.feature_names)
        fields = _stack_fields(rows, self.feature_names)
        deep_in = fields.reshape(fields.shape[0], -1)
        if dense is not None:
            deep_in = jnp.concatenate(
                [deep_in, dense.astype(deep_in.dtype)], axis=-1)
        deep = MLP(self.dnn_units, dtype=self.dtype)(deep_in)
        deep_logit = nn.Dense(1, dtype=self.dtype)(deep).reshape(-1)
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return wide + deep_logit.astype(wide.dtype) + bias[0]


class DeepFM(nn.Module):
    """DeepFM: linear + FM second-order + DNN, shared field embeddings."""

    feature_names: Tuple[str, ...]
    dnn_units: Tuple[int, ...] = (256, 128)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, dense, rows):
        linear = _linear_term(rows, self.feature_names)
        fields = _stack_fields(rows, self.feature_names)  # [B, F, k]
        # FM second order: 0.5 * sum_d ((sum_f x)^2 - sum_f x^2)
        sum_f = jnp.sum(fields, axis=1)
        fm = 0.5 * jnp.sum(sum_f * sum_f - jnp.sum(fields * fields, axis=1),
                           axis=-1)
        deep_in = fields.reshape(fields.shape[0], -1)
        if dense is not None:
            deep_in = jnp.concatenate(
                [deep_in, dense.astype(deep_in.dtype)], axis=-1)
        deep = MLP(self.dnn_units, dtype=self.dtype)(deep_in)
        deep_logit = nn.Dense(1, dtype=self.dtype)(deep).reshape(-1)
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return linear + fm + deep_logit.astype(linear.dtype) + bias[0]


class CIN(nn.Module):
    """Compressed Interaction Network (xDeepFM's core block).

    Each layer: outer-product feature maps of (X_k, X_0) compressed by a
    1x1 "conv" (einsum) to layer_size maps; sum-pool over the embedding dim
    of every layer's output and concatenate.
    """

    layer_sizes: Tuple[int, ...] = (128, 128)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x0):  # [B, F, D]
        xk = x0
        pooled = []
        for li, h in enumerate(self.layer_sizes):
            # z[b, i, j, d] = xk[b, i, d] * x0[b, j, d]
            z = jnp.einsum("bid,bjd->bijd", xk, x0)
            z = z.reshape(z.shape[0], -1, z.shape[-1])  # [B, Hk*F, D]
            w = self.param(f"cin_w_{li}", nn.initializers.glorot_uniform(),
                           (z.shape[1], h), self.dtype)
            xk = jnp.einsum("bnd,nh->bhd", z.astype(self.dtype), w)
            xk = nn.relu(xk)
            pooled.append(jnp.sum(xk, axis=-1))  # [B, h]
        return jnp.concatenate(pooled, axis=-1)


class XDeepFM(nn.Module):
    """xDeepFM: linear + CIN + DNN."""

    feature_names: Tuple[str, ...]
    dnn_units: Tuple[int, ...] = (256, 128)
    cin_layer_sizes: Tuple[int, ...] = (128, 128)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, dense, rows):
        linear = _linear_term(rows, self.feature_names)
        fields = _stack_fields(rows, self.feature_names)
        cin_out = CIN(self.cin_layer_sizes, dtype=self.dtype)(
            fields.astype(self.dtype))
        cin_logit = nn.Dense(1, dtype=self.dtype)(cin_out).reshape(-1)
        deep_in = fields.reshape(fields.shape[0], -1)
        if dense is not None:
            deep_in = jnp.concatenate(
                [deep_in, dense.astype(deep_in.dtype)], axis=-1)
        deep = MLP(self.dnn_units, dtype=self.dtype)(deep_in)
        deep_logit = nn.Dense(1, dtype=self.dtype)(deep).reshape(-1)
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return (linear + cin_logit.astype(linear.dtype)
                + deep_logit.astype(linear.dtype) + bias[0])


class CrossNet(nn.Module):
    """DCN cross layers: x_{k+1} = x0 * (w_k . x_k) + b_k + x_k."""

    num_layers: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x0):  # [B, d]
        x = x0
        d = x0.shape[-1]
        for k in range(self.num_layers):
            w = self.param(f"cross_w_{k}", nn.initializers.glorot_uniform(),
                           (d, 1), self.dtype)
            b = self.param(f"cross_b_{k}", nn.initializers.zeros, (d,),
                           self.dtype)
            xw = (x.astype(self.dtype) @ w).astype(x0.dtype)  # [B, 1]
            x = x0 * xw + b.astype(x0.dtype) + x
        return x


class DCN(nn.Module):
    """Deep & Cross Network: CrossNet + MLP over flattened fields + dense."""

    feature_names: Tuple[str, ...]
    cross_layers: int = 3
    dnn_units: Tuple[int, ...] = (256, 128)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, dense, rows):
        fields = _stack_fields(rows, self.feature_names)
        x0 = fields.reshape(fields.shape[0], -1)
        if dense is not None:
            x0 = jnp.concatenate([x0, dense.astype(x0.dtype)], axis=-1)
        cross = CrossNet(self.cross_layers, dtype=self.dtype)(x0)
        deep = MLP(self.dnn_units, dtype=self.dtype)(x0)
        out = jnp.concatenate([cross, deep.astype(cross.dtype)], axis=-1)
        logit = nn.Dense(1, dtype=self.dtype)(out).reshape(-1)
        bias = self.param("bias", nn.initializers.zeros, (1,))
        return logit.astype(jnp.float32) + bias[0]


MODELS = {
    "lr": LogisticRegression,
    "wdl": WideDeep,
    "deepfm": DeepFM,
    "xdeepfm": XDeepFM,
    "dcn": DCN,
}


def build_model(name: str, feature_names: Sequence[str], **kwargs):
    """Factory mirroring the reference benchmark's --model switch
    (test/benchmark/criteo_deepctr.py WDL/DeepFM/xDeepFM)."""
    if name not in MODELS:
        raise ValueError(f"unknown model {name!r}; known: {sorted(MODELS)}")
    return MODELS[name](feature_names=tuple(feature_names), **kwargs)
