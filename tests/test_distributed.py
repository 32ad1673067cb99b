"""2-process multi-host test — the reference's MultiProcess simulation.

Spawns two worker processes (each a "host" with 2 virtual CPU devices) that
join one jax.distributed cluster and run a REAL cross-process training step:
a 2x2 mesh spanning both processes, per-process batch shards, gradients that
must cross the process boundary to land. Mirrors the reference's fork-based
N-node tests (core::MultiProcess, entry/c_api_test.h:194,285).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "distributed_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_training_step(tmp_path, devices8):
    port = _free_port()
    ckpt_dir = str(tmp_path / "mh_ckpt")
    root = os.path.dirname(os.path.dirname(_WORKER))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": root + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)  # workers set their own device counts
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(r), str(port), ckpt_dir], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {r} failed:\n{out}"
        assert f"worker {r}: ok" in out
        assert f"worker {r}: multihost checkpoint ok" in out

    # the 2-host dump (part files per process) reloads in THIS single
    # process on a different mesh — cross-topology like the reference's
    # re-sharding load
    import jax
    import numpy as np
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
    from openembedding_tpu import checkpoint as ckpt
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, jax.devices()[:8])
    specs = (
        EmbeddingSpec(name="t", input_dim=32, output_dim=4,
                      initializer={"category": "constant", "value": 0.0},
                      optimizer={"category": "sgd", "learning_rate": 1.0}),
        EmbeddingSpec(name="h", input_dim=-1, output_dim=4,
                      hash_capacity=256,
                      initializer={"category": "constant", "value": 0.25},
                      optimizer={"category": "sgd", "learning_rate": 1.0}),
    )
    coll = EmbeddingCollection(specs, mesh)
    loaded = ckpt.load_checkpoint(ckpt_dir, coll)
    import jax.numpy as jnp
    rows = np.asarray(coll.pull(
        loaded, {"t": jnp.asarray([5, 6, 7], jnp.int32)},
        batch_sharded=False)["t"])
    np.testing.assert_allclose(rows[:, 0], [-8.0, 0.0, 0.0],
                               rtol=1e-6, atol=1e-6)
    hrows = np.asarray(coll.pull(
        loaded, {"h": jnp.asarray([1002, 1004, 77], jnp.int32)},
        batch_sharded=False, read_only=True)["h"])
    np.testing.assert_allclose(hrows[:2], 0.25 - 1.0, rtol=1e-6)
    np.testing.assert_allclose(hrows[2], 0.0)
