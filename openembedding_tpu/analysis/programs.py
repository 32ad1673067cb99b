"""Lower the framework's data-plane programs to compiled HLO text.

Shared by ``tests/test_analysis_contracts.py`` and the
``tools/graftcheck.py`` CI gate: build a collection on a mesh, lower the
pull / push / train-step programs exactly as the training path runs them
(batch-sharded inputs, batch-sharded outputs — a replicated output would
force an artifact gather and fail the pull bound for the wrong reason),
and return ``(hlo_text, params)`` ready for
:func:`..analysis.contracts.check_program`.

Imports of the wider package happen inside the functions (this module
is part of ``analysis``, which the rest of the package may import at
module level — see the package docstring).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

CACHE_K = 128


def _collection(mesh, plane: str, *, vocab: int, dim: int,
                use_hash: bool):
    from ..embedding import EmbeddingCollection, EmbeddingSpec
    if use_hash:
        spec = EmbeddingSpec(name="t", input_dim=-1, output_dim=dim,
                             hash_capacity=vocab, plane=plane,
                             cache_k=CACHE_K)
    else:
        spec = EmbeddingSpec(name="t", input_dim=vocab, output_dim=dim,
                             plane=plane, cache_k=CACHE_K)
    return EmbeddingCollection((spec,), mesh)


def contract_params(mesh, *, batch: int, dim: int, itemsize: int = 4,
                    vocab: Optional[int] = None,
                    state_nbytes: Optional[int] = None) -> Dict[str, int]:
    from ..parallel.mesh import DATA_AXIS
    data = mesh.shape[DATA_AXIS]
    params = {"batch_slice": batch // data, "global_batch": batch,
              "dim": dim, "itemsize": itemsize, "cache_k": CACHE_K,
              "num_shards": mesh.size}
    if vocab is not None:
        # one table shard's WEIGHT bytes — the unit the memory-ledger
        # peak-temp audit detects accidental materializations in
        params["table_shard_bytes"] = vocab * dim * itemsize // mesh.size
    if state_nbytes is not None:
        # the whole state pytree's per-device share (weights + optimizer
        # slots + hash keys); replicated leaves (cache replicas) make
        # this a slight underestimate, absorbed by the audit's slack
        params["state_shard_bytes"] = int(state_nbytes) // mesh.size
    return params


def _state_nbytes(states) -> int:
    import jax
    return int(sum(x.nbytes for x in jax.tree.leaves(states)))


def _wire_params(plane: str, program: str) -> Dict[str, int]:
    """Precision-aware contract params for a (possibly compressed)
    plane token: the wire itemsize of the program's row/grad payload
    (``parallel/precision.py``). Empty for uncompressed planes, so the
    f32 bounds stay byte-identical to before."""
    from ..parallel import precision
    _base, ep, pp = precision.parse_plane(plane)
    rung = ep if program == "pull" else pp
    if rung == "f32":
        return {}
    return {"wire_itemsize": precision.wire_itemsize(rung)}


def compile_pull(mesh, plane: str, *, vocab: int = 1 << 16, dim: int = 16,
                 batch: int = 1024, use_hash: bool = False,
                 out_replicated: bool = False):
    """Compiled pull program + contract params — the object form, for
    callers that also need ``memory_analysis()`` (graftwatch's memory
    ledger); :func:`lower_pull` is the HLO-text view of the same build.

    ``out_replicated=True`` deliberately breaks the output sharding
    annotation (rows replicated instead of batch-sharded): XLA must then
    gather the global batch onto every device — the regression shape the
    a2a pull contract exists to catch. Test-only.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS
    coll = _collection(mesh, plane, vocab=vocab, dim=dim,
                       use_hash=use_hash)
    states = coll.init(jax.random.PRNGKey(0))

    def pull_fn(states, idx):
        return coll.pull(states, {"t": idx})["t"]

    idx = jax.device_put(jnp.zeros((batch,), jnp.int32),
                         NamedSharding(mesh, P(DATA_AXIS)))
    out_spec = P() if out_replicated else P(DATA_AXIS)
    compiled = jax.jit(
        pull_fn, out_shardings=NamedSharding(mesh, out_spec)
    ).lower(states, idx).compile()
    params = contract_params(mesh, batch=batch, dim=dim, vocab=vocab,
                             state_nbytes=_state_nbytes(states))
    params.update(_wire_params(plane, "pull"))
    return compiled, params


def lower_pull(mesh, plane: str, *, vocab: int = 1 << 16, dim: int = 16,
               batch: int = 1024, use_hash: bool = False,
               out_replicated: bool = False) -> Tuple[str, Dict[str, int]]:
    """Compiled HLO text of one plane's pull program on ``mesh``."""
    compiled, params = compile_pull(mesh, plane, vocab=vocab, dim=dim,
                                    batch=batch, use_hash=use_hash,
                                    out_replicated=out_replicated)
    return compiled.as_text(), params


def compile_push(mesh, plane: str, *, vocab: int = 1 << 16, dim: int = 16,
                 batch: int = 1024, use_hash: bool = False):
    """Compiled push (apply_gradients) program + contract params."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS
    coll = _collection(mesh, plane, vocab=vocab, dim=dim,
                       use_hash=use_hash)
    states = coll.init(jax.random.PRNGKey(0))

    def push_fn(states, idx, grads):
        return coll.apply_gradients(states, {"t": idx}, {"t": grads})

    sh = NamedSharding(mesh, P(DATA_AXIS))
    idx = jax.device_put(jnp.zeros((batch,), jnp.int32), sh)
    grads = jax.device_put(jnp.zeros((batch, dim), jnp.float32), sh)
    compiled = jax.jit(push_fn).lower(states, idx, grads).compile()
    params = contract_params(mesh, batch=batch, dim=dim, vocab=vocab,
                             state_nbytes=_state_nbytes(states))
    params.update(_wire_params(plane, "push"))
    return compiled, params


def lower_push(mesh, plane: str, *, vocab: int = 1 << 16, dim: int = 16,
               batch: int = 1024,
               use_hash: bool = False) -> Tuple[str, Dict[str, int]]:
    """Compiled HLO text of one plane's push program."""
    compiled, params = compile_push(mesh, plane, vocab=vocab, dim=dim,
                                    batch=batch, use_hash=use_hash)
    return compiled.as_text(), params


def _grouped_collection(mesh, *, tables: int, vocab: int, dim: int,
                        use_hash: bool):
    from ..embedding import EmbeddingCollection, EmbeddingSpec
    if use_hash:
        specs = tuple(
            EmbeddingSpec(name=f"t{i}", input_dim=-1, output_dim=dim,
                          hash_capacity=vocab, plane="a2a+grouped")
            for i in range(tables))
    else:
        # distinct vocabs: heterogeneous tables that the per-table loop
        # could never fuse, but the planner batches (same dim bucket)
        specs = tuple(
            EmbeddingSpec(name=f"t{i}", input_dim=vocab + 64 * i,
                          output_dim=dim, plane="a2a+grouped")
            for i in range(tables))
    return EmbeddingCollection(specs, mesh)


def count_exchange_a2a(mesh, program: str, *, vocab: int = 1 << 16,
                       dim: int = 16, batch: int = 1024,
                       use_hash: bool = False) -> int:
    """All-to-all ops ONE single-table a2a exchange compiles to on this
    mesh — the empirical per-exchange unit the grouped plane's launch-count
    contract multiplies by ``num_groups``."""
    from . import contracts
    lower = lower_pull if program == "pull" else lower_push
    txt, _ = lower(mesh, "a2a", vocab=vocab, dim=dim, batch=batch,
                   use_hash=use_hash)
    return contracts.summarize(txt).get("all-to-all", (0, 0))[0]


def grouped_params(mesh, coll, names, *, batch: int, dim: int,
                   program: str, a2a_ops: Optional[int] = None,
                   itemsize: int = 4,
                   state_nbytes: Optional[int] = None,
                   vocab: Optional[int] = None) -> Dict[str, int]:
    """Contract params for a grouped-plane program: the base params plus
    num_tables / num_groups (from the planner itself) / the padded bucket
    dim / the per-exchange all-to-all count.

    The per-exchange unit is counted from a SINGLE-TABLE a2a program at
    the LARGEST group's concatenated stream size (``max group members *
    batch`` — XLA's all-to-all decomposition depends on the exchanged
    buffer size, so a unit counted at the per-table batch undercounts
    once the concat stream crosses a split threshold: at batch 256 the
    grouped pull compiles 8 all-to-alls where the 256-entry
    single-table unit is 4). The widest group, not ``num_tables``: on a
    multi-group plan the whole-collection stream size would inflate the
    unit past what any one group exchanges, slackening the
    ``num_groups * unit`` cap. Counting at the widest group's stream
    calibrates the cap for ANY audited batch; a per-table-loop
    regression still fails it (num_tables x per-table units always
    exceeds one stream-sized unit set per group).
    """
    from ..parallel import grouped
    plans = grouped.plan_groups(coll, tuple(names), read_only=True)
    if a2a_ops is None:
        widest = max(len(p.members) for p in plans)
        a2a_ops = count_exchange_a2a(mesh, program,
                                     batch=batch * widest, dim=dim)
    params = contract_params(mesh, batch=batch, dim=dim, itemsize=itemsize,
                             vocab=vocab, state_nbytes=state_nbytes)
    params.update({
        "num_tables": len(names), "num_groups": len(plans),
        "dim_bucket": max(p.bucket_dim for p in plans),
        "a2a_ops_per_exchange": a2a_ops})
    return params


def compile_grouped_pull(mesh, *, tables: int = 3, vocab: int = 1 << 14,
                         dim: int = 16, batch: int = 1024,
                         use_hash: bool = False,
                         a2a_ops: Optional[int] = None,
                         out_replicated: bool = False):
    """Compiled COLLECTION-level grouped pull over ``tables`` same-dim
    tables (one exchange group) + params. ``out_replicated=True`` breaks
    the output annotation like :func:`compile_pull` — the negative test."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS
    coll = _grouped_collection(mesh, tables=tables, vocab=vocab, dim=dim,
                               use_hash=use_hash)
    states = coll.init(jax.random.PRNGKey(0))
    names = tuple(coll.specs)

    def pull_fn(states, idxs):
        return coll.pull(states, idxs)

    sh = NamedSharding(mesh, P(DATA_AXIS))
    idxs = {n: jax.device_put(jnp.zeros((batch,), jnp.int32), sh)
            for n in names}
    out_spec = P() if out_replicated else P(DATA_AXIS)
    compiled = jax.jit(
        pull_fn, out_shardings=NamedSharding(mesh, out_spec)
    ).lower(states, idxs).compile()
    return compiled, grouped_params(
        mesh, coll, names, batch=batch, dim=dim, program="pull",
        a2a_ops=a2a_ops, vocab=vocab,
        state_nbytes=_state_nbytes(states))


def lower_grouped_pull(mesh, *, tables: int = 3, vocab: int = 1 << 14,
                       dim: int = 16, batch: int = 1024,
                       use_hash: bool = False,
                       a2a_ops: Optional[int] = None,
                       out_replicated: bool = False
                       ) -> Tuple[str, Dict[str, int]]:
    """Compiled HLO text of the collection-level grouped pull."""
    compiled, params = compile_grouped_pull(
        mesh, tables=tables, vocab=vocab, dim=dim, batch=batch,
        use_hash=use_hash, a2a_ops=a2a_ops, out_replicated=out_replicated)
    return compiled.as_text(), params


def compile_grouped_push(mesh, *, tables: int = 3, vocab: int = 1 << 14,
                         dim: int = 16, batch: int = 1024,
                         use_hash: bool = False,
                         a2a_ops: Optional[int] = None):
    """Compiled collection-level grouped push + params."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS
    coll = _grouped_collection(mesh, tables=tables, vocab=vocab, dim=dim,
                               use_hash=use_hash)
    states = coll.init(jax.random.PRNGKey(0))
    names = tuple(coll.specs)

    def push_fn(states, idxs, grads):
        return coll.apply_gradients(states, idxs, grads)

    sh = NamedSharding(mesh, P(DATA_AXIS))
    idxs = {n: jax.device_put(jnp.zeros((batch,), jnp.int32), sh)
            for n in names}
    grads = {n: jax.device_put(jnp.zeros((batch, dim), jnp.float32), sh)
             for n in names}
    compiled = jax.jit(push_fn).lower(states, idxs, grads).compile()
    return compiled, grouped_params(
        mesh, coll, names, batch=batch, dim=dim, program="push",
        a2a_ops=a2a_ops, vocab=vocab,
        state_nbytes=_state_nbytes(states))


def lower_grouped_push(mesh, *, tables: int = 3, vocab: int = 1 << 14,
                       dim: int = 16, batch: int = 1024,
                       use_hash: bool = False,
                       a2a_ops: Optional[int] = None
                       ) -> Tuple[str, Dict[str, int]]:
    """Compiled HLO text of the collection-level grouped push."""
    compiled, params = compile_grouped_push(
        mesh, tables=tables, vocab=vocab, dim=dim, batch=batch,
        use_hash=use_hash, a2a_ops=a2a_ops)
    return compiled.as_text(), params


def compile_train_step(mesh, plane: str = "a2a", *, vocab: int = 4096,
                       dim: int = 8, batch: int = 256,
                       model: str = "deepfm"):
    """Compiled Trainer train-step program + contract params.

    The step contract audits cross-cutting properties: donation of the
    state pytree honored (tables updated in place), no f64, no host
    transfers smuggled into the step.
    """
    import numpy as np
    import jax
    import optax
    from ..embedding import EmbeddingCollection
    from ..models import deepctr
    from ..training import Trainer
    features = ("c0", "c1")
    specs = deepctr.make_feature_specs(features, vocab, dim, plane=plane)
    coll = EmbeddingCollection(
        specs, mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    trainer = Trainer(deepctr.build_model(model, features), coll,
                     optax.adam(1e-2))
    rng = np.random.RandomState(0)
    batch_data = {
        "label": rng.randint(0, 2, size=batch).astype(np.float32),
        "dense": rng.randn(batch, 4).astype(np.float32),
        "sparse": {f: rng.randint(0, vocab, size=batch).astype(np.int32)
                   for f in features}
    }
    for f in features:
        batch_data["sparse"][f + deepctr.LINEAR_SUFFIX] = \
            batch_data["sparse"][f]
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batch_data))
    compiled = trainer.lower_train_step(
        state, trainer.shard_batch(batch_data)).compile()
    return compiled, contract_params(mesh, batch=batch, dim=dim,
                                     vocab=vocab,
                                     state_nbytes=_state_nbytes(state))


def lower_train_step(mesh, plane: str = "a2a", *, vocab: int = 4096,
                     dim: int = 8, batch: int = 256,
                     model: str = "deepfm"
                     ) -> Tuple[str, Dict[str, int]]:
    """Compiled HLO text of the Trainer's whole jitted train step."""
    compiled, params = compile_train_step(mesh, plane, vocab=vocab,
                                          dim=dim, batch=batch,
                                          model=model)
    return compiled.as_text(), params
