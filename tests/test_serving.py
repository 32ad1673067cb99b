"""Serving plane: registry lifecycle, read-only lookups, REST controller.

Mirrors the reference's serving flow (SURVEY §3.5): dump a trained model,
create it in the serving cluster with a sign, look up variables read-only,
model CRUD over HTTP (controller.cc endpoints)."""

import json
import http.client

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
from openembedding_tpu import checkpoint as ckpt
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.serving.registry import ModelRegistry
from openembedding_tpu.serving.rest import ControllerServer

VOCAB, DIM = 32, 4


@pytest.fixture()
def dumped_model(devices8, tmp_path):
    mesh = create_mesh(2, 4, devices8)
    specs = (EmbeddingSpec(name="arr", input_dim=VOCAB, output_dim=DIM),
             EmbeddingSpec(name="hsh", input_dim=-1, output_dim=DIM,
                           hash_capacity=256))
    coll = EmbeddingCollection(
        specs, mesh,
        default_optimizer={"category": "sgd", "learning_rate": 1.0})
    states = coll.init(jax.random.PRNGKey(0))
    idx = {"arr": jnp.arange(8, dtype=jnp.int32),
           "hsh": jnp.arange(8, dtype=jnp.int32) * 31 + 5}
    rows = coll.pull(states, idx, batch_sharded=False)
    states = coll.apply_gradients(
        states, idx, {k: jnp.ones_like(v) for k, v in rows.items()},
        batch_sharded=False)
    path = str(tmp_path / "model")
    ckpt.save_checkpoint(path, coll, states, model_sign="uuid-3")
    expected = coll.pull(states, idx, batch_sharded=False, read_only=True)
    return mesh, path, idx, expected


def test_registry_lifecycle_and_lookup(dumped_model):
    mesh, path, idx, expected = dumped_model
    reg = ModelRegistry(mesh, default_hash_capacity=256)
    sign = reg.create_model(path, replica_num=3)
    assert sign == "uuid-3"
    info = reg.show_model(sign)
    assert info["model_status"] == "NORMAL"
    assert info["replica_num"] == 3

    model = reg.find_model(sign)
    rows = model.lookup("arr", np.asarray(idx["arr"]))
    np.testing.assert_allclose(np.asarray(rows), np.asarray(expected["arr"]),
                               rtol=1e-6)
    # lookup by variable_id too (reference find_model_variable signature)
    rows2 = model.lookup(model.collection.variable_id("hsh"),
                         np.asarray(idx["hsh"]))
    np.testing.assert_allclose(np.asarray(rows2), np.asarray(expected["hsh"]),
                               rtol=1e-6)
    # read-only: unknown hash key -> zeros, and the table is unchanged
    zero = model.lookup("hsh", np.array([999999], np.int32))
    np.testing.assert_array_equal(np.asarray(zero), np.zeros((1, DIM)))
    # a host int64 id is split on host, never wrapped to int32: 2^40 + 5
    # is an unseen key, not trained key 5's row
    far = model.lookup("hsh", np.array([(1 << 40) + 5, 5], np.int64))
    np.testing.assert_array_equal(np.asarray(far)[0], np.zeros(DIM))
    np.testing.assert_allclose(np.asarray(far)[1],
                               np.asarray(expected["hsh"])[0], rtol=1e-6)

    reg.delete_model(sign)
    with pytest.raises(KeyError):
        reg.find_model(sign)


def test_registry_error_paths(dumped_model, tmp_path):
    mesh, path, _, _ = dumped_model
    reg = ModelRegistry(mesh)
    with pytest.raises(FileNotFoundError):
        reg.create_model(str(tmp_path / "nope"))
    with pytest.raises(KeyError):
        reg.show_model("ghost")


def test_rest_controller(dumped_model):
    mesh, path, idx, expected = dumped_model
    reg = ModelRegistry(mesh, default_hash_capacity=256)
    srv = ControllerServer(reg, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)

        def req(method, url, body=None):
            c.request(method, url,
                      json.dumps(body) if body is not None else None)
            r = c.getresponse()
            return r.status, json.loads(r.read() or b"null"), dict(
                r.getheaders())

        # create (block so the test is deterministic)
        code, obj, headers = req("POST", "/models",
                                 {"model_uri": path, "block": True})
        assert code == 201 and obj["model_sign"] == "uuid-3"
        assert headers.get("Location") == "/models/uuid-3"
        # list + show
        code, models, _ = req("GET", "/models")
        assert code == 200 and models[0]["model_status"] == "NORMAL"
        code, one, _ = req("GET", "/models/uuid-3")
        assert code == 200 and one["model_uri"] == path
        # nodes
        code, nodes, _ = req("GET", "/nodes")
        assert code == 200 and len(nodes) == 8
        code, node, _ = req("GET", f"/nodes/{nodes[0]['node_id']}")
        assert code == 200
        code, _, _ = req("DELETE", f"/nodes/{nodes[0]['node_id']}")
        assert code == 501
        # lookup
        code, obj, _ = req("POST", "/models/uuid-3/lookup",
                           {"variable": "arr",
                            "indices": np.asarray(idx["arr"]).tolist()})
        assert code == 200
        np.testing.assert_allclose(np.asarray(obj["rows"], np.float32),
                                   np.asarray(expected["arr"]), rtol=1e-5)
        # unknown model 404-ish errors
        code, obj, _ = req("GET", "/models/ghost")
        assert code == 404
        # delete
        code, obj, _ = req("DELETE", "/models/uuid-3")
        assert code == 200
        code, obj, _ = req("GET", "/models/uuid-3")
        assert code == 404
    finally:
        srv.stop()


@pytest.mark.slow
def test_health_reports_applied_seq(dumped_model):
    """/health carries ``applied_seq`` — the newest delta seq this
    replica has applied across models — so one liveness read is enough
    for a recovery probe (graftload --respawn, graftchaos) to judge
    catch-up after a kill."""
    from openembedding_tpu.checkpoint_delta import Delta
    mesh, path, _idx, _expected = dumped_model
    reg = ModelRegistry(mesh, default_hash_capacity=256)
    srv = ControllerServer(reg, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)

        def health():
            c.request("GET", "/health")
            r = c.getresponse()
            return r.status, json.loads(r.read())

        code, obj = health()
        assert code == 200 and obj["ok"] is True
        assert obj["models"] == [] and obj["applied_seq"] == 0
        reg.create_model(path, block=True)
        code, obj = health()
        assert code == 200 and obj["applied_seq"] == 0
        payload = {
            "weights": np.full((VOCAB, DIM), 2.0, np.float32),
            "chunks": np.array([0], np.int64),
            "rows_per_chunk": np.array(VOCAB, np.int64),
            "vocab": np.array(VOCAB, np.int64),
        }
        out = reg.apply_delta(
            "uuid-3", Delta(seq=1, step=1, vars={"arr": payload}))
        assert out["applied"] and out["version"] == 1
        code, obj = health()
        assert code == 200 and obj["applied_seq"] == 1
        assert [m["version"] for m in obj["models"]] == [1]
    finally:
        srv.stop()
        reg.close()
