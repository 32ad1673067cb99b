"""The plain reference: DeepFM, dense Adagrad on the rows, Adam on the net.

Straightforward ``jax.numpy`` in float32 with matrix products at
``highest`` precision; no kernels, no exchange, no dedup, no hashing. It
imports nothing of ``openembedding_tpu`` and takes nothing the program made:
tables and dense parameters come from :mod:`benchmark.seeded`, the batches
from the traffic generator.

A table with 10^8 rows is never materialised. Adagrad leaves a row whose
gradient is zero exactly where it was, so the reference holds the rows the
followed batches touch, in a compact table, and trains that densely:
autodiff's scatter-add sums duplicate ids as the program's contract says.

``dtype=bfloat16`` is the control: the same mathematics with every array
stored and computed in the next precision down.

``fault`` plants, in the reference put in the program's place, the faults a
training cell can have (read on the chip by ``benchmark/controls.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded

ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
TABLES = ("fields", "linear")           # dim-k rows, dim-1 first-order rows
FAULTS = ("state_unchanged", "half_batch", "no_exchange")


def dense_shapes(config):
    """name -> shape of the dense leaves: MLP over the flattened fields and
    the dense columns, a dim-1 head, one global bias."""
    width = config["sparse_features"] * config["embedding_dim"] \
        + config["dense_features"]
    u0, u1 = config["dnn_units"]
    return {"h0_w": (width, u0), "h0_b": (u0,), "h1_w": (u0, u1),
            "h1_b": (u1,), "out_w": (u1, 1), "out_b": (1,), "bias": (1,)}


def dense_init(seed, config):
    """Dense leaves from the seed: Glorot-uniform kernels, small biases
    (not zero, so that every leaf differs and every leaf moves)."""
    out = {}
    for n, (name, shape) in enumerate(dense_shapes(config).items()):
        scale = np.sqrt(6.0 / sum(shape)) if len(shape) == 2 else 0.01
        out[name] = seeded.dense_leaf(seed, n, shape, scale)
    return out


def logits(params, dense, fields, linear):
    """DeepFM: first order + FM second order + MLP. ``fields`` [B, F, k],
    ``linear`` [B, F, 1], ``dense`` [B, d]."""
    first = jnp.sum(linear, axis=(1, 2))
    sum_f = jnp.sum(fields, axis=1)
    fm = 0.5 * jnp.sum(sum_f * sum_f - jnp.sum(fields * fields, axis=1),
                       axis=-1)
    x = jnp.concatenate([fields.reshape(fields.shape[0], -1), dense], axis=1)
    x = jax.nn.relu(x @ params["h0_w"] + params["h0_b"])
    x = jax.nn.relu(x @ params["h1_w"] + params["h1_b"])
    deep = (x @ params["out_w"] + params["out_b"]).reshape(-1)
    return first + fm + deep + params["bias"][0]


def logloss(x, label):
    """Mean sigmoid cross-entropy."""
    return jnp.mean(jnp.maximum(x, 0) - x * label
                    + jnp.log1p(jnp.exp(-jnp.abs(x))))


def _loss(params, tables, idx, dense, label, keep):
    fields = tables["fields"][idx] * keep[..., None]
    linear = tables["linear"][idx] * keep[..., None]
    return logloss(logits(params, dense, fields, linear), label)


_grads = jax.jit(jax.value_and_grad(_loss, argnums=(0, 1)))


def compact_ids(batches):
    """(feature, id) pairs of the followed batches -> (unique feature
    [U], unique id [U], index [n, B, F] into them, rank [U], position [U]
    of each pair's first lookup among the n*B*F)."""
    ids = np.stack([b["ids"] for b in batches])            # [n, B, F]
    feat = np.broadcast_to(np.arange(ids.shape[-1], dtype=np.uint64),
                           ids.shape)
    pairs = np.stack([feat.ravel(), ids.ravel()], axis=1)
    uniq, first, inverse = np.unique(pairs, axis=0, return_index=True,
                                     return_inverse=True)
    ranks = np.stack([b["ranks"] for b in batches]).ravel()[first]
    return (uniq[:, 0].astype(np.int64), uniq[:, 1],
            inverse.reshape(ids.shape).astype(np.int32), ranks, first)


def fresh_key(seed):
    """The PRNG key fresh rows of the dim-k table are drawn under."""
    return jax.random.PRNGKey(int(seed) % (1 << 31))


def fresh_rows(seed, config, feature, ids):
    """Rows of keys a hash table has not seen, as its configuration states
    them (``fresh_rows``): the dim-k row is N(mean, stddev) drawn under
    the table's key folded with the low and the high word of the fused key
    ``key * F + feature`` (64-bit wrap-around; a high word equal to the
    empty marker moves up by one). The dim-1 row is nought."""
    stated = config["fresh_rows"]
    fused = ids.astype(np.uint64) * np.uint64(config["sparse_features"]) \
        + feature.astype(np.uint64)
    lo, hi = (w.view(np.int32) for w in seeded.split_words(fused))
    empty = np.iinfo(np.int32).min
    hi = np.where(hi == empty, empty + 1, hi)
    base = fresh_key(seed)
    dim = config["embedding_dim"]

    def one(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(base, lo), hi)
        return jax.random.normal(key, (dim,), jnp.float32)

    drawn = np.asarray(jax.vmap(one)(jnp.asarray(lo), jnp.asarray(hi)))
    return {"fields": drawn * np.float32(stated["stddev"])
            + np.float32(stated["mean"]),
            "linear": np.zeros((len(ids), 1), np.float32)}


def initial_tables(seed, config, feature, ids, ranks):
    """Seeded rows of the compact tables. A hash table holds the ranks it
    was filled with; a key beyond them is fresh."""
    lo, hi = seeded.split_words(ids)
    out = {}
    for t, name in enumerate(TABLES):
        dim = config["embedding_dim"] if name == "fields" else 1
        out[name] = seeded.table_rows(seed, t, feature, lo, hi, dim,
                                      config["init_scale"][name])
    if config["table_kind"] == "hash":
        fresh = ranks > config["prefill_ranks_per_feature"]
        if fresh.any():
            drawn = fresh_rows(seed, config, feature[fresh], ids[fresh])
            for name in TABLES:
                out[name][fresh] = drawn[name]
    return out


def _keep_mask(config, feature, ids, index, fault):
    """1 where a lookup reaches its row. All ones, but for the planted
    ``no_exchange``: a lookup is served only where the chip that holds the
    example also owns the row (rows laid out id mod chips)."""
    keep = np.ones(index.shape, np.float32)
    if fault == "no_exchange":
        chips = config["chips"]
        fused = feature * config.get("rows_per_feature", 1) \
            + ids.astype(np.int64)
        owner = (fused % chips)[index]                      # [n, B, F]
        batch = index.shape[1]
        holder = (np.arange(batch) * chips // batch)[None, :, None]
        keep = (owner == holder).astype(np.float32)
    return keep


PAD_ROWS = 8192     # compact tables grow in steps of this many rows, so
                    # that seeds share compiled programs


def _pad(rows):
    extra = -len(rows) % PAD_ROWS
    return np.concatenate([rows, np.zeros((extra,) + rows.shape[1:],
                                          rows.dtype)])


@jax.jit
def _apply(params, tables, accum, mu, nu, g_params, g_tables, t, adagrad):
    """One optimizer step: Adagrad at the row (accum += g^2;
    w -= lr g / (sqrt(accum) + eps)), bias-corrected Adam on the net."""
    dtype = tables["fields"].dtype
    lr, eps = (jnp.asarray(adagrad[k], dtype)
               for k in ("learning_rate", "epsilon"))
    accum = jax.tree.map(lambda a, g: a + g * g, accum, g_tables)
    tables = jax.tree.map(lambda w, g, a: w - lr * g / (jnp.sqrt(a) + eps),
                          tables, g_tables, accum)
    mu = jax.tree.map(lambda m, g: ADAM["b1"] * m + (1 - ADAM["b1"]) * g,
                      mu, g_params)
    nu = jax.tree.map(lambda v, g: ADAM["b2"] * v + (1 - ADAM["b2"]) * g * g,
                      nu, g_params)
    c1, c2 = 1 - ADAM["b1"] ** t, 1 - ADAM["b2"] ** t
    params = jax.tree.map(
        lambda p, m, v: (p - ADAM["lr"] * (m / c1)
                         / (jnp.sqrt(v / c2) + ADAM["eps"])).astype(p.dtype),
        params, mu, nu)
    return params, tables, accum, mu, nu


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.linalg.norm(a.astype(jnp.float32).ravel()), tree)


@jax.jit
def _grown(accum, accum0):
    """A table's gradient norm read as the program's is: from what one
    step added to the accumulator (g^2 under the float32 spacing of the
    accumulator is lost on both sides alike)."""
    return jax.tree.map(
        lambda a, a0: jnp.sqrt(jnp.sum(a.astype(jnp.float32)
                                       - a0.astype(jnp.float32))),
        accum, accum0)


def follow(seed, config, batches, *, dtype=jnp.float32, fault=None):
    """Train ``len(batches)`` steps from the seed and return what the
    comparison reads: ``loss`` per step, ``grad`` = norm of the first
    step's gradient per leaf as the optimizer gets it, ``delta`` = norm of
    each leaf's change over all the steps."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    feature, ids, index, ranks, _first = compact_ids(batches)
    keep = _keep_mask(config, feature, ids, index, fault)
    cast = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)
    params = cast(dense_init(seed, config))
    tables = cast({k: _pad(v) for k, v in initial_tables(
        seed, config, feature, ids, ranks).items()})
    start = {**params, **tables}
    accum = accum0 = jax.tree.map(
        lambda a: jnp.full_like(
            a, config["adagrad"]["initial_accumulator_value"]), tables)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            rows = slice(None)
            if fault == "half_batch":       # mean over the first half only
                rows = slice(0, batch["label"].shape[0] // 2)
            loss, (g_params, g_tables) = _grads(
                params, tables, jnp.asarray(index[t - 1][rows]),
                jnp.asarray(batch["dense"][rows], dtype),
                jnp.asarray(batch["label"][rows], dtype),
                jnp.asarray(keep[t - 1][rows], dtype))
            losses.append(float(loss))
            if fault != "state_unchanged":
                params, tables, accum, mu, nu = _apply(
                    params, tables, accum, mu, nu, g_params, g_tables, t,
                    config["adagrad"])
            if first_grad is None:
                # worked out from the state one step leaves, as the
                # program's is (nought from a state that did not move)
                first_grad = {**_norms(g_params), **_grown(accum, accum0)} \
                    if fault != "state_unchanged" else \
                    {k: 0.0 for k in (*g_params, *g_tables)}
    end = {**params, **tables}
    delta = _norms({k: end[k].astype(jnp.float32)
                    - start[k].astype(jnp.float32) for k in end})
    return {"loss": losses,
            "grad": {k: float(v) for k, v in first_grad.items()},
            "delta": {k: float(v) for k, v in delta.items()}}
