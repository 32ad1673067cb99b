"""graftwatch trajectory schema + regression gate (tools/graftwatch.py).

Pure-host lanes (no lowering): the run-record schema validator and the
rolling-baseline gate — including the acceptance-criterion negative
test: an injected synthetic 2x-slower record exits nonzero.
"""

import json

import pytest

from tools import graftwatch as gw

_FP = "cpu8-test-c2"
_DEV = {"platform": "cpu", "n_devices": 8, "device_kind": "cpu"}


def _record(ts: str, eps: float, p50_ms: float = 1.0):
    return gw.make_record(
        plane="a2a",
        config={"mesh": "2x4", "batch": 256, "dim": 8, "steps": 4,
                "blocks": 3, "source": "graftwatch-quick"},
        eps=eps, eps_min=eps * 0.95, eps_max=eps * 1.05,
        scope={stage: {"calls": 12, "p50_ms": p50_ms,
                       "p95_ms": p50_ms * 1.3, "expected_bytes": 4096,
                       "gbps_p50": 0.1} for stage in ("pull", "push")},
        memory={"pull": {"argument_bytes": 1 << 20, "output_bytes": 1024,
                         "temp_bytes": 2048, "alias_bytes": 0,
                         "generated_code_bytes": 0,
                         "peak_bytes": (1 << 20) + 3072},
                "push": None},
        fingerprint=_FP, device=_DEV, ts=ts)


# --- schema ------------------------------------------------------------------

def test_record_schema_roundtrip():
    rec = _record("2026-08-01T00:00:00+00:00", 1000.0)
    assert gw.validate_record(rec) == []
    # provenance fields are live (sha + versions resolved at build time)
    assert rec["schema_version"] == gw.SCHEMA_VERSION
    assert rec["git_sha"] and rec["jax"] and rec["jaxlib"]
    # survives a JSON roundtrip (the JSONL on-disk form)
    assert gw.validate_record(json.loads(json.dumps(rec))) == []


@pytest.mark.parametrize("mutate,fragment", [
    (lambda r: r.pop("git_sha"), "git_sha"),
    (lambda r: r.pop("fingerprint"), "fingerprint"),
    (lambda r: r.update(schema_version=99), "schema_version"),
    (lambda r: r.update(eps=-1.0), "eps"),
    (lambda r: r.update(eps=True), "eps"),
    (lambda r: r.update(eps_min=r["eps_max"] * 2), "band"),
    (lambda r: r.update(device={"platform": "cpu"}), "n_devices"),
    (lambda r: r["scope"]["pull"].pop("p50_ms"), "p50_ms"),
])
def test_record_schema_lists_each_problem(mutate, fragment):
    rec = _record("2026-08-01T00:00:00+00:00", 1000.0)
    mutate(rec)
    problems = gw.validate_record(rec)
    assert problems and any(fragment in p for p in problems), problems


def test_append_refuses_invalid_record(tmp_path):
    rec = _record("2026-08-01T00:00:00+00:00", 1000.0)
    del rec["ts"]
    with pytest.raises(ValueError, match="schema-invalid"):
        gw.append_record(str(tmp_path / "t.jsonl"), rec)


def test_load_trajectory_rejects_corrupt_lines(tmp_path):
    path = tmp_path / "t.jsonl"
    good = _record("2026-08-01T00:00:00+00:00", 1000.0)
    path.write_text(json.dumps(good) + "\nnot json\n")
    with pytest.raises(ValueError, match="invalid record"):
        gw.load_trajectory(str(path))
    assert gw.load_trajectory(str(tmp_path / "missing.jsonl")) == []


def test_record_from_bench_conversion():
    entry = {"metric": "deepfm_dim9_examples_per_sec_cpu8",
             "value": 1000.0, "unit": "examples/s", "vs_baseline": 0.01,
             "eps_min": 900.0, "eps_max": 1100.0,
             "config": {"plane": "a2a+grouped", "batch": 4096, "dim": 9},
             "ts": "2026-08-01T00:00:00+00:00"}
    rec = gw.record_from_bench(entry, fingerprint=_FP, device=_DEV)
    assert rec is not None and gw.validate_record(rec) == []
    assert rec["plane"] == "a2a+grouped" and rec["eps"] == 1000.0
    assert rec["config"]["source"] == "bench"
    assert rec["scope"] is None          # bench entries carry no spans
    # inconvertible shapes: errors, non-throughput units, missing band
    assert gw.record_from_bench({"metric": "m", "error": "x"}) is None
    assert gw.record_from_bench(
        {"metric": "m", "value": 1.0, "unit": "GB/s"}) is None
    assert gw.record_from_bench(
        {"metric": "m", "value": 1.0, "unit": "examples/s"}) is None


def _ingest_entry():
    return {"metric": "deepfm_dim9_ingest_ab_examples_per_sec_cpu8",
            "value": 1800.0, "unit": "examples/s", "vs_baseline": 0.01,
            "eps_min": 1700.0, "eps_max": 1900.0,
            "stream_vs_mem": 0.97,
            "ingest": {"stall_p95_ms": 0.0, "stall_p99_ms": 0.0,
                       "bad_rows": 0, "pops": 15},
            "config": {"kind": "ingest_ab", "batch": 4096, "dim": 9},
            "ts": "2026-08-01T00:00:00+00:00"}


def test_record_from_bench_ingest_kind():
    """Ingest A/B entries convert to the synthetic `ingest` plane with
    the stall/bad-row evidence attached and schema-validated."""
    rec = gw.record_from_bench(_ingest_entry(), fingerprint=_FP,
                               device=_DEV)
    assert rec is not None and gw.validate_record(rec) == []
    assert rec["plane"] == "ingest" and rec["eps"] == 1800.0
    assert rec["ingest"]["stall_p95_ms"] == 0.0
    assert rec["ingest"]["stream_vs_mem"] == 0.97
    assert rec["ingest"]["bad_rows"] == 0


@pytest.mark.parametrize("mutate, fragment", [
    (lambda i: i.__setitem__("stall_p95_ms", -1.0),
     "ingest.stall_p95_ms"),
    (lambda i: i.__setitem__("stall_p99_ms", "zero"),
     "ingest.stall_p99_ms"),
    (lambda i: i.__setitem__("bad_rows", -2), "ingest.bad_rows"),
    (lambda i: i.__setitem__("bad_rows", 1.5), "ingest.bad_rows"),
    (lambda i: i.__setitem__("pops", None), "ingest.pops"),
    (lambda i: i.__setitem__("stream_vs_mem", 0.0),
     "ingest.stream_vs_mem"),
])
def test_ingest_record_schema_lists_problems(mutate, fragment):
    rec = gw.record_from_bench(_ingest_entry(), fingerprint=_FP,
                               device=_DEV)
    mutate(rec["ingest"])
    problems = gw.validate_record(rec)
    assert problems and any(fragment in p for p in problems), problems


def test_ingest_record_missing_evidence_fails_loudly():
    """A bench entry missing the A/B ratio or stall evidence must be
    REJECTED, not defaulted to the perfect value the gate verifies
    (stream_vs_mem=1.0 / stall_p95_ms=0.0 are exactly those)."""
    e = _ingest_entry()
    del e["stream_vs_mem"]
    with pytest.raises(ValueError, match="stream_vs_mem"):
        gw.record_from_bench(e, fingerprint=_FP, device=_DEV)
    e = _ingest_entry()
    del e["ingest"]["stall_p95_ms"]
    with pytest.raises(ValueError, match="stall_p95_ms"):
        gw.record_from_bench(e, fingerprint=_FP, device=_DEV)


def test_ingest_record_non_dict_section_rejected():
    rec = gw.record_from_bench(_ingest_entry(), fingerprint=_FP,
                               device=_DEV)
    rec["ingest"] = ["not", "a", "dict"]
    assert any("ingest:" in p for p in gw.validate_record(rec))


# --- the regression gate -----------------------------------------------------

def _trajectory():
    return [_record("2026-08-01T00:00:00+00:00", 1000.0),
            _record("2026-08-02T00:00:00+00:00", 1050.0),
            _record("2026-08-03T00:00:00+00:00", 980.0)]


def test_gate_healthy_and_soft_pass():
    failures, lines = gw.gate(_trajectory())
    assert failures == 0
    assert any("ok" in ln and "a2a/eps" in ln for ln in lines)
    # a single record (first run on new hardware) soft-passes with a warn
    failures, lines = gw.gate(_trajectory()[:1])
    assert failures == 0 and "no baseline" in lines[0]
    # an empty trajectory warns instead of passing silently
    failures, lines = gw.gate([])
    assert failures == 0 and "empty" in lines[0]


def test_gate_catches_injected_2x_regression(tmp_path):
    """THE acceptance-criterion negative test: a synthetic 2x-slower
    record (eps halved, p50 doubled) against a healthy baseline exits
    nonzero through the CLI."""
    records = _trajectory()
    records.append(_record("2026-08-04T00:00:00+00:00", 500.0,
                           p50_ms=2.0))
    failures, lines = gw.gate(records)
    assert failures >= 1
    assert any("REGRESSION" in ln and "eps" in ln for ln in lines)
    assert any("REGRESSION" in ln and "p50_ms" in ln for ln in lines)
    path = tmp_path / "t.jsonl"
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    assert gw.main(["--gate", "--trajectory", str(path)]) == 1
    # drop the injected record -> the same CLI invocation is clean
    with open(path, "w") as f:
        for r in records[:-1]:
            f.write(json.dumps(r) + "\n")
    assert gw.main(["--gate", "--trajectory", str(path)]) == 0


def test_gate_noise_band_derived_from_eps_spread():
    """A wide measured band (noisy box) must widen the gate: the same
    -40% delta that fails a tight-band group passes a wide-band one."""
    tight = _trajectory()
    tight.append(_record("2026-08-04T00:00:00+00:00", 600.0))
    failures, _ = gw.gate(tight)
    assert failures >= 1                      # 40% drop vs ~35% band
    noisy = []
    for i, eps in enumerate((1000.0, 1050.0, 980.0, 600.0)):
        r = _record(f"2026-08-0{i + 1}T00:00:00+00:00", eps)
        r["eps_min"], r["eps_max"] = eps * 0.6, eps * 1.4   # 80% spread
        noisy.append(r)
    failures, lines = gw.gate(noisy)
    assert failures == 0, lines


def test_gate_groups_by_fingerprint():
    """Records from different hardware never gate each other."""
    records = _trajectory()
    slow = _record("2026-08-04T00:00:00+00:00", 100.0)
    slow["fingerprint"] = "tpu8-real-device"
    records.append(slow)
    failures, lines = gw.gate(records)
    assert failures == 0
    assert any("no baseline" in ln and "tpu8-real-device" in ln
               for ln in lines)


def test_committed_trajectory_gates_clean():
    """The repo's own BENCH_trajectory.jsonl must load schema-valid and
    gate clean — a PR that lands a regressing record (or corrupts the
    file) fails here before CI's gate even runs."""
    records = gw.load_trajectory(gw.TRAJECTORY_FILE)
    assert records, "committed trajectory is missing or empty"
    failures, lines = gw.gate(records)
    assert failures == 0, lines


# --- recovery records (graftload --respawn / chaos_smoke lanes) --------------

_RECOVERY_CFG = {"lane": "kill-mid-fit", "autosave_every": 2,
                 "source": "chaos_smoke"}


def _recovery_record(ts: str, mttr_s: float):
    return gw.make_recovery_record(
        mttr_s=mttr_s, steps_lost=1, bytes_replayed=4096,
        config=_RECOVERY_CFG, fingerprint=_FP, device=_DEV, ts=ts)


def test_recovery_record_schema_roundtrip():
    rec = _recovery_record("2026-08-01T00:00:00+00:00", 2.5)
    assert gw.validate_record(rec) == []
    assert rec["plane"] == "recovery"
    # eps is recoveries/s so the throughput gate reads MTTR directly
    assert rec["eps"] == pytest.approx(1.0 / 2.5)
    assert rec["recovery"]["mttr_s"] == 2.5
    assert gw.validate_record(json.loads(json.dumps(rec))) == []


def test_make_recovery_record_rejects_nonpositive_mttr():
    for bad in (0.0, -1.0):
        with pytest.raises(ValueError, match="mttr_s"):
            gw.make_recovery_record(
                mttr_s=bad, steps_lost=0, bytes_replayed=0,
                config=_RECOVERY_CFG, fingerprint=_FP, device=_DEV)


@pytest.mark.parametrize("mutate, fragment", [
    (lambda r: r["recovery"].__setitem__("mttr_s", 0.0),
     "recovery.mttr_s"),
    (lambda r: r["recovery"].__setitem__("mttr_s", "fast"),
     "recovery.mttr_s"),
    (lambda r: r["recovery"].__setitem__("mttr_s", True),
     "recovery.mttr_s"),
    (lambda r: r["recovery"].__setitem__("steps_lost", -1),
     "recovery.steps_lost"),
    (lambda r: r["recovery"].__setitem__("steps_lost", 1.5),
     "recovery.steps_lost"),
    (lambda r: r["recovery"].__setitem__("bytes_replayed", None),
     "recovery.bytes_replayed"),
    (lambda r: r.__setitem__("recovery", ["not", "a", "dict"]),
     "recovery:"),
])
def test_recovery_record_schema_lists_problems(mutate, fragment):
    rec = _recovery_record("2026-08-01T00:00:00+00:00", 2.5)
    mutate(rec)
    problems = gw.validate_record(rec)
    assert problems and any(fragment in p for p in problems), problems


def test_gate_catches_slower_recovery():
    """eps = 1/MTTR by construction, so a 2x-slower respawn trips the
    SAME rolling gate as a throughput regression — no recovery-specific
    gate code to rot."""
    records = [_recovery_record(f"2026-08-0{i + 1}T00:00:00+00:00", m)
               for i, m in enumerate((2.0, 2.1, 1.9))]
    failures, _ = gw.gate(records)
    assert failures == 0
    records.append(_recovery_record("2026-08-04T00:00:00+00:00", 4.0))
    failures, lines = gw.gate(records)
    assert failures >= 1, lines
    assert any("recovery" in ln for ln in lines)
