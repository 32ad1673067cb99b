"""The benchmark's entry point and its comparison, on the CPU.

A cell of ``BENCHMARK.json`` refuses to run without its TPU chips; the
``tiny_*`` rehearsal configurations, in no cell, drive the same code end to
end with every timing ``null``; ``--dry`` resolves every cell to its files
without JAX. The control (the reference in bfloat16, put in the program's
place) and each fault a training cell can have, planted under the timed
path, have to come out as not correct.
"""

import concurrent.futures
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import correct, reference, run, train_runner
from benchmark.traffic_gen import zipf_train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = ("tiny_array", "tiny_hash", "tiny_array_x4")


def _cli(*args):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)


def test_dry_resolves_every_cell_without_jax():
    code = ("import sys; from benchmark import run; rc = run.main(['--dry']);"
            " assert 'jax' not in sys.modules, 'the dry path imported jax';"
            " sys.exit(rc)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    cells = [w["name"] for w in run.manifest()["workloads"]]
    assert [line.split(":")[0] for line in out.stdout.splitlines()] == cells


def test_manifest_names_only_files_that_exist():
    bench = run.manifest()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        stated = run.load("configs", c["name"])
        assert stated["reduced"] == c["reduced"]
        assert stated["source"] == c["source"]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in end_to_end
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           f"{m['name']}.py"))


@pytest.mark.parametrize("cell", [w["name"]
                                  for w in run.manifest()["workloads"]])
def test_a_cell_refuses_to_run_without_its_chips(cell):
    out = _cli("--workload", cell, "--seed", "1", "--seconds", "1")
    assert out.returncode == 2
    assert "needs" in out.stderr and "TPU" in out.stderr
    assert '"metrics"' not in out.stdout and '"correct"' not in out.stdout


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("config", TINY)
def test_rehearsal_runs_end_to_end_with_null_timings(config, trace):
    out = _cli("--workload", f"{config}.train_zipf", "--seed", "3000000019",
               "--seconds", "0.5", "--trace", str(trace))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == run.load("configs", config)["chips"]
    assert list(line)[-1] == "compared"
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name
        assert f"compared {name} " in out.stderr
    metrics = line["metrics"]
    if trace:
        assert metrics["train_compiles_in_window"]["value"] == 0
        timings = [k for k in metrics if k != "train_compiles_in_window"]
    else:
        assert set(metrics) == {"examples_per_s", "setup_s"}
        timings = list(metrics)
    assert timings and all(metrics[k]["value"] is None for k in timings)


def _raw(config, seed):
    traffic = dict(run.load("traffic", "train_zipf"), pool_batches=3)
    return zipf_train.make(traffic, config, seed)


@pytest.mark.parametrize("config_name", TINY)
def test_the_control_in_bfloat16_is_not_correct(config_name):
    config = run.load("configs", config_name)
    raw = _raw(config, 11)
    ref = reference.follow(11, config, raw)
    control = reference.follow(11, config, raw, dtype=jnp.bfloat16)
    values, _ = correct.numbers(control, ref)
    ok, compared = correct.decide(values, config["limits"])
    assert not ok
    assert compared["grad_gap"]["value"] > 10 * config["limits"]["grad_gap"]


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_fault_planted_in_the_reference_is_not_correct(fault):
    config = run.load("configs", "tiny_array_x4")
    raw = _raw(config, 12)
    ref = reference.follow(12, config, raw)
    values, _ = correct.numbers(reference.follow(12, config, raw,
                                                 fault=fault), ref)
    assert not correct.decide(values, config["limits"])[0]


def _drive(config_name, seed=13):
    """The rest of a run without the look for a chip."""
    config = run.load("configs", config_name)
    traffic = dict(run.load("traffic", "train_zipf"), pool_batches=8,
                   warmup_steps=2)
    inputs = concurrent.futures.Future()
    inputs.set_result(zipf_train.make(traffic, config, seed))
    return train_runner.run(
        f"{config_name}.train_zipf", config, traffic, inputs, seed=seed,
        seconds=0.2, trace=False, t_process=0.0, on_device=False)


@pytest.fixture
def planted(monkeypatch):
    """``monkeypatch`` for a fault under the timed path; whatever was
    traced with the fault in place is dropped afterwards, so that no later
    test of this process is handed a broken program."""
    yield monkeypatch
    monkeypatch.undo()
    jax.clear_caches()


def test_a_sound_run_driven_in_process_is_correct():
    assert _drive("tiny_array")["correct"] is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(planted):
    from openembedding_tpu.training import Trainer
    build = Trainer._build_train_step

    def unchanged(self):
        step = build(self)

        def step_fn(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return step_fn
    planted.setattr(Trainer, "_build_train_step", unchanged)
    result = _drive("tiny_array")
    assert result["correct"] is False
    assert result["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(planted):
    from openembedding_tpu.training import Trainer
    train_step = Trainer.train_step

    def half(self, state, batch, **kw):
        first = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
        return train_step(self, state, first, **kw)
    planted.setattr(Trainer, "train_step", half)
    assert _drive("tiny_array")["correct"] is False


def test_the_exchange_between_chips_left_out_is_not_correct(planted):
    from openembedding_tpu.parallel import alltoall
    planted.setattr(alltoall.lax, "all_to_all",
                        lambda x, *a, **kw: x)
    assert _drive("tiny_array_x4")["correct"] is False
