"""graftwatch CLI: versioned bench trajectory + perf-regression gate.

    python -m tools.graftwatch --record --quick     # cpu8 micro-bench
    python -m tools.graftwatch --gate               # regression gate

Bench entries used to be schemaless one-off JSON blobs: no git sha, no
hardware fingerprint, nothing consuming them — a perf regression
between PRs was undetectable until someone eyeballed numbers. This
tool closes the loop (the reference's own benchmark discipline is
reproducible per-config records, ``documents/en/benchmark.md``):

* ``--record`` runs a small per-plane pull/push micro-bench on a
  virtual cpu mesh (``--quick`` for the CI-sized variant) and appends
  ONE schema-versioned record per registered plane to
  ``BENCH_trajectory.jsonl``: git sha, jax/jaxlib versions, hardware
  fingerprint, eps with min/max band, graftscope span percentiles,
  HLO-derived expected collective bytes, and the graftwatch memory
  ledger (``analysis/memwatch.py``) for the same programs.
* ``--gate`` compares the NEWEST record of each (plane, fingerprint,
  config) group against the trailing baseline (median of the previous
  ``--window`` records) with a noise band derived from each record's
  own eps_min/eps_max spread. No baseline -> soft pass with a warning
  (the first record on new hardware cannot regress against anything);
  baseline present + any metric worse than the band -> exit 1.

``bench.py --trajectory <path>`` appends its own throughput entries
through :func:`record_from_bench`, so real device rounds land in the
same trajectory as the CI micro-bench. ``tools/graftload.py`` appends
``serving`` records (:func:`make_serving_record`: offered/achieved
QPS, coordinated-omission-free per-route p50/p95/p99, error + replica
counts) to the same file, and the gate covers their latency quantiles:
a serving regression is **p99 up OR sustained QPS down** beyond the
noise band.

The gate imports no jax — it runs anywhere, instantly.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

SCHEMA_VERSION = 1
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAJECTORY_FILE = os.path.join(REPO_ROOT, "BENCH_trajectory.jsonl")

# gate tuning: the band is derived from measured eps spread, floored at
# MIN_BAND (2-core CI boxes jitter ~20% between blocks) and widened by
# SAFETY; a genuine 2x regression (50% drop) always clears the band,
# block-to-block noise never should
MIN_BAND = 0.25
BAND_SAFETY = 1.4
BASELINE_WINDOW = 5
# tail quantiles (p99) carry far more sampling variance than medians:
# an O(500)-sample serving storm's p99 is its handful of worst
# requests, which on an oversubscribed CI box measure scheduler
# preemption as much as the server (observed ±50% run-to-run at a
# stable p50). The band doubles for *_p99_ms metrics — a sustained 2x
# tail shift (+100% > 2 x 35%) still fails, scheduler flutter passes.
TAIL_BAND_MULT = 2.0


# --- provenance --------------------------------------------------------------

def git_info() -> Tuple[str, bool]:
    """(sha, dirty) of the repo, or ("unknown", False) outside git."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10).stdout.strip()
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10).stdout.strip())
        return (sha or "unknown"), dirty
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return "unknown", False


def _cpu_model_slug() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    model = line.split(":", 1)[1].strip()
                    return re.sub(r"[^a-z0-9]+", "_",
                                  model.lower()).strip("_")[:40]
    except OSError:
        pass
    import platform as _platform
    return re.sub(r"[^a-z0-9]+", "_",
                  (_platform.processor() or _platform.machine() or
                   "unknown").lower())[:40]


def device_fingerprint() -> Tuple[str, Dict[str, Any]]:
    """(fingerprint string, device dict) of the LIVE jax backend.

    The fingerprint keys baseline grouping: records from different
    hardware must never gate each other (a GH runner regressing against
    a workstation record is noise, not signal), so it folds in platform,
    device count, device kind, and the host CPU model + core count.
    """
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    kind = getattr(devs[0], "device_kind", "") or platform
    device = {"platform": platform, "n_devices": len(devs),
              "device_kind": kind}
    fp = (f"{platform}{len(devs)}-{_cpu_model_slug()}"
          f"-c{os.cpu_count() or 0}")
    return fp, device


def make_record(*, plane: str, config: Mapping[str, Any], eps: float,
                eps_min: float, eps_max: float,
                scope: Optional[Mapping[str, Any]] = None,
                memory: Optional[Mapping[str, Any]] = None,
                host_memory: Optional[Mapping[str, Any]] = None,
                fingerprint: Optional[str] = None,
                device: Optional[Mapping[str, Any]] = None,
                ts: Optional[str] = None) -> Dict[str, Any]:
    """Assemble one schema-valid trajectory record (provenance fields
    computed live when not supplied)."""
    import datetime
    if fingerprint is None or device is None:
        fingerprint, device = device_fingerprint()
    sha, dirty = git_info()
    try:
        import jax
        jax_v = jax.__version__
    except Exception:  # noqa: BLE001
        jax_v = "unknown"
    try:
        import jaxlib
        jaxlib_v = jaxlib.__version__
    except Exception:  # noqa: BLE001
        jaxlib_v = "unknown"
    return {
        "schema_version": SCHEMA_VERSION,
        "ts": ts or datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "git_sha": sha, "git_dirty": dirty,
        "jax": jax_v, "jaxlib": jaxlib_v,
        "fingerprint": fingerprint, "device": dict(device),
        "plane": plane, "config": dict(config),
        "eps": float(eps), "eps_min": float(eps_min),
        "eps_max": float(eps_max),
        "scope": dict(scope) if scope else None,
        "memory": dict(memory) if memory else None,
        "host_memory": dict(host_memory) if host_memory else None,
    }


# --- schema validation -------------------------------------------------------

_NUM = (int, float)


def validate_record(rec: Any) -> List[str]:
    """Problems with one trajectory record ([] == schema-valid)."""
    if not isinstance(rec, dict):
        return ["record is not a JSON object"]
    p: List[str] = []

    def need(key, types):
        v = rec.get(key)
        tt = types if isinstance(types, tuple) else (types,)
        # bool is an int subclass — only accept it where bool is asked
        if not isinstance(v, tt) or (isinstance(v, bool)
                                     and bool not in tt):
            p.append(f"{key}: expected "
                     f"{'/'.join(t.__name__ for t in tt)}, "
                     f"got {type(v).__name__}")
            return None
        return v

    if rec.get("schema_version") != SCHEMA_VERSION:
        p.append(f"schema_version: expected {SCHEMA_VERSION}, "
                 f"got {rec.get('schema_version')!r}")
    for key in ("ts", "git_sha", "jax", "jaxlib", "fingerprint", "plane"):
        need(key, str)
    need("git_dirty", bool)
    need("config", dict)
    dev = need("device", dict)
    if dev is not None:
        if not isinstance(dev.get("platform"), str):
            p.append("device.platform: expected str")
        if not isinstance(dev.get("n_devices"), int):
            p.append("device.n_devices: expected int")
    for key in ("eps", "eps_min", "eps_max"):
        v = need(key, _NUM)
        if v is not None and (isinstance(v, bool) or v <= 0):
            p.append(f"{key}: must be a positive number, got {v!r}")
    if not p and not (rec["eps_min"] <= rec["eps"] <= rec["eps_max"]):
        p.append("eps band violated: need eps_min <= eps <= eps_max")
    scope = rec.get("scope")
    if scope is not None:
        if not isinstance(scope, dict):
            p.append("scope: expected object or null")
        else:
            for stage, entry in scope.items():
                if not isinstance(entry, dict):
                    p.append(f"scope.{stage}: expected object")
                    continue
                for k in ("p50_ms", "p95_ms"):
                    if not isinstance(entry.get(k), _NUM):
                        p.append(f"scope.{stage}.{k}: expected number")
                # p99 is optional (serving records carry it; the
                # micro-bench's 12-sample windows cannot estimate one)
                if "p99_ms" in entry and \
                        not isinstance(entry["p99_ms"], _NUM):
                    p.append(f"scope.{stage}.p99_ms: expected number")
                if not isinstance(entry.get("calls"), int):
                    p.append(f"scope.{stage}.calls: expected int")
                if not isinstance(entry.get("expected_bytes"), int):
                    p.append(f"scope.{stage}.expected_bytes: expected int")
    mem = rec.get("memory")
    if mem is not None and not isinstance(mem, dict):
        p.append("memory: expected object or null")
    ingest = rec.get("ingest")
    if ingest is not None:
        # streaming-ingest records (bench.py run_ingest_ab -> plane
        # "ingest"): eps is the streamed examples/s the gate covers;
        # this section carries the stall/bad-row evidence
        if not isinstance(ingest, dict):
            p.append("ingest: expected object or null")
        else:
            for k in ("stall_p95_ms", "stall_p99_ms"):
                v = ingest.get(k)
                if not isinstance(v, _NUM) or isinstance(v, bool) \
                        or v < 0:
                    p.append(f"ingest.{k}: expected number >= 0")
            for k in ("bad_rows", "pops"):
                v = ingest.get(k)
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    p.append(f"ingest.{k}: expected int >= 0")
            v = ingest.get("stream_vs_mem")
            if not isinstance(v, _NUM) or isinstance(v, bool) or v <= 0:
                p.append("ingest.stream_vs_mem: expected positive "
                         "number")
    recovery = rec.get("recovery")
    if recovery is not None:
        # fault-recovery records (graftload --respawn, chaos_smoke):
        # eps is recoveries/s (1/MTTR) so the rolling gate catches
        # recovery-time regressions; this section carries the evidence
        if not isinstance(recovery, dict):
            p.append("recovery: expected object or null")
        else:
            v = recovery.get("mttr_s")
            if not isinstance(v, _NUM) or isinstance(v, bool) or v <= 0:
                p.append("recovery.mttr_s: expected positive number")
            for k in ("steps_lost", "bytes_replayed"):
                v = recovery.get(k)
                if not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    p.append(f"recovery.{k}: expected int >= 0")
    serving = rec.get("serving")
    if serving is not None:
        if not isinstance(serving, dict):
            p.append("serving: expected object or null")
        else:
            for k in ("offered_qps", "achieved_qps"):
                v = serving.get(k)
                if not isinstance(v, _NUM) or isinstance(v, bool) \
                        or v <= 0:
                    p.append(f"serving.{k}: expected positive number")
            if not isinstance(serving.get("errors"), int) \
                    or serving.get("errors", 0) < 0:
                p.append("serving.errors: expected int >= 0")
            if not isinstance(serving.get("replicas"), int) \
                    or serving.get("replicas", 0) < 1:
                p.append("serving.replicas: expected int >= 1")
            # batched-serving fields (optional: pre-batching records
            # carry neither): rejected offers and the scraped
            # server-side coalescing counters
            if "rejected" in serving and (
                    not isinstance(serving["rejected"], int)
                    or isinstance(serving["rejected"], bool)
                    or serving["rejected"] < 0):
                p.append("serving.rejected: expected int >= 0")
            batch = serving.get("batch")
            if batch is not None:
                if not isinstance(batch, dict):
                    p.append("serving.batch: expected object or null")
                else:
                    for k, v in batch.items():
                        if not isinstance(v, _NUM) \
                                or isinstance(v, bool) or v < 0:
                            p.append(f"serving.batch.{k}: expected "
                                     "number >= 0")
    return p


# --- trajectory IO -----------------------------------------------------------

def load_trajectory(path: str) -> List[Dict[str, Any]]:
    """Schema-valid records from a JSONL trajectory (raises ValueError
    listing every invalid line — a half-corrupt trajectory must not
    silently gate on the readable half)."""
    records: List[Dict[str, Any]] = []
    problems: List[str] = []
    try:
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError as e:
                    problems.append(f"line {lineno}: bad JSON ({e})")
                    continue
                bad = validate_record(rec)
                if bad:
                    problems.append(f"line {lineno}: {'; '.join(bad)}")
                else:
                    records.append(rec)
    except FileNotFoundError:
        return []
    if problems:
        raise ValueError(
            f"{path}: {len(problems)} invalid record(s): "
            + " | ".join(problems[:5]))
    return records


def append_record(path: str, rec: Dict[str, Any]) -> None:
    bad = validate_record(rec)
    if bad:
        raise ValueError(f"refusing to append a schema-invalid record: "
                         f"{bad}")
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True) + "\n")


def record_from_bench(result: Mapping[str, Any], *,
                      fingerprint: Optional[str] = None,
                      device: Optional[Mapping[str, Any]] = None
                      ) -> Optional[Dict[str, Any]]:
    """Convert one bench.py result dict into a trajectory record:
    throughput entries (examples/s with an eps band) and checkpoint
    write-rate entries (GB/s with a gbps band, recorded under the
    synthetic ``ckpt`` plane so checkpoint perf gates like step perf);
    None for inconvertible entries."""
    if not isinstance(result, dict) or "error" in result:
        return None
    cfg = dict(result.get("config") or {})
    cfg["source"] = "bench"
    cfg["metric"] = result.get("metric", "")
    if result.get("unit") == "GB/s" \
            and all(isinstance(result.get(k), _NUM)
                    for k in ("value", "gbps_min", "gbps_max")):
        return make_record(
            plane="ckpt", config=cfg,
            eps=result["value"], eps_min=result["gbps_min"],
            eps_max=max(result["gbps_max"], result["value"]),
            fingerprint=fingerprint, device=device,
            ts=result.get("ts"))
    if result.get("unit") != "examples/s":
        return None
    if not all(isinstance(result.get(k), _NUM)
               for k in ("value", "eps_min", "eps_max")):
        return None
    if isinstance(result.get("ingest"), dict):
        # streaming-ingest A/B entries land under the synthetic
        # "ingest" plane (their own baseline group, like "ckpt" and
        # "serving") with the stall/bad-row evidence attached; the
        # gate covers the streamed eps exactly like step throughput
        ing = result["ingest"]
        rec = make_record(
            plane="ingest", config=cfg,
            eps=result["value"], eps_min=result["eps_min"],
            eps_max=result["eps_max"], fingerprint=fingerprint,
            device=device, ts=result.get("ts"))
        # NO defaults: a missing stall/bad-row/A-B measurement must
        # fail schema validation below, not masquerade as a perfect one
        # (stall_p95_ms=0.0 or stream_vs_mem=1.0 are exactly the values
        # the gate exists to verify)
        rec["ingest"] = {
            "stall_p95_ms": ing.get("stall_p95_ms"),
            "stall_p99_ms": ing.get("stall_p99_ms"),
            "bad_rows": ing.get("bad_rows"),
            "pops": ing.get("pops"),
            "stream_vs_mem": result.get("stream_vs_mem"),
        }
        bad = validate_record(rec)
        if bad:
            raise ValueError(
                f"assembled ingest record is schema-invalid: {bad}")
        return rec
    return make_record(
        plane=str(cfg.get("plane", "a2a")), config=cfg,
        eps=result["value"], eps_min=result["eps_min"],
        eps_max=result["eps_max"], fingerprint=fingerprint,
        device=device, ts=result.get("ts"))


def make_serving_record(*, routes: Mapping[str, Mapping[str, Any]],
                        offered_qps: float, achieved_qps: float,
                        errors: int, replicas: int,
                        qps_band: Tuple[float, float],
                        config: Mapping[str, Any],
                        rejected: int = 0,
                        batch_stats: Optional[Mapping[str, Any]] = None,
                        fingerprint: Optional[str] = None,
                        device: Optional[Mapping[str, Any]] = None,
                        ts: Optional[str] = None) -> Dict[str, Any]:
    """One ``serving`` trajectory record (``tools/graftload.py``).

    ``routes`` maps route name (``rest`` / ``native``) to its measured
    latency summary (``calls``, ``p50_ms``, ``p95_ms``, ``p99_ms`` —
    coordinated-omission-free, from intended send time); the quantiles
    land in the record's ``scope`` section so the rolling-baseline gate
    covers them exactly like pull/push stage latencies, with the p99
    gated explicitly. ``eps`` is the sustained (achieved) QPS with
    ``qps_band`` as its per-second spread, so "sustained QPS down"
    gates like step throughput. The ``serving`` section carries the
    open-loop accounting (offered vs achieved, error count, replica
    count) plus — batched storms — the backpressure/coalescing stats:
    ``rejected`` (429-busy offers; a DEFINED response distinct from
    errors) and ``batch`` (the replicas' ``oe_batch_*`` counters:
    flushes / requests / rows / unique rows, scraped off /metrics).
    Raises on a schema-invalid assembly."""
    scope_section = {
        str(route): {"calls": int(r["calls"]),
                     "p50_ms": round(float(r["p50_ms"]), 4),
                     "p95_ms": round(float(r["p95_ms"]), 4),
                     "p99_ms": round(float(r["p99_ms"]), 4),
                     # serving latencies have no HLO-derived byte
                     # expectation — 0 keeps the shared scope schema
                     "expected_bytes": 0, "gbps_p50": 0.0}
        for route, r in routes.items()}
    lo, hi = qps_band
    rec = make_record(
        plane="serving", config=dict(config),
        eps=float(achieved_qps),
        eps_min=min(float(lo), float(achieved_qps)),
        eps_max=max(float(hi), float(achieved_qps)),
        scope=scope_section, fingerprint=fingerprint, device=device,
        ts=ts)
    rec["serving"] = {
        "offered_qps": float(offered_qps),
        "achieved_qps": float(achieved_qps),
        "errors": int(errors), "replicas": int(replicas),
        "rejected": int(rejected)}
    if batch_stats:
        rec["serving"]["batch"] = {str(k): float(v)
                                   for k, v in batch_stats.items()}
    bad = validate_record(rec)
    if bad:
        raise ValueError(f"assembled serving record is schema-invalid: "
                         f"{bad}")
    return rec


def make_recovery_record(*, mttr_s: float, steps_lost: int,
                         bytes_replayed: int,
                         config: Mapping[str, Any],
                         fingerprint: Optional[str] = None,
                         device: Optional[Mapping[str, Any]] = None,
                         ts: Optional[str] = None) -> Dict[str, Any]:
    """One ``recovery`` trajectory record (``tools/graftload.py
    --respawn`` kill-and-respawn lane; ``tools/chaos_smoke.py``
    kill-mid-fit + resume lane).

    ``eps`` is recoveries/second (``1 / mttr_s``) so the rolling
    baseline gate — including ``--strict`` — treats a slower recovery
    exactly like a throughput regression. The ``recovery`` section
    carries the evidence: ``mttr_s`` (kill to serving/trained-again),
    ``steps_lost`` (training steps past the last autosave that had to
    be retrained; 0 for serving respawns), ``bytes_replayed``
    (checkpoint/delta-chain bytes re-read to rebuild the state). Raises
    on a schema-invalid assembly."""
    if mttr_s <= 0:
        raise ValueError(f"mttr_s must be > 0, got {mttr_s}")
    eps = 1.0 / float(mttr_s)
    rec = make_record(plane="recovery", config=dict(config),
                      eps=eps, eps_min=eps, eps_max=eps,
                      fingerprint=fingerprint, device=device, ts=ts)
    rec["recovery"] = {"mttr_s": round(float(mttr_s), 4),
                       "steps_lost": int(steps_lost),
                       "bytes_replayed": int(bytes_replayed)}
    bad = validate_record(rec)
    if bad:
        raise ValueError(f"assembled recovery record is schema-invalid: "
                         f"{bad}")
    return rec


# --- the regression gate -----------------------------------------------------

def _rel_spread(rec: Mapping[str, Any]) -> float:
    eps = float(rec["eps"]) or 1e-9
    return max(0.0, (float(rec["eps_max"]) - float(rec["eps_min"])) / eps)


def _gate_metrics(rec: Mapping[str, Any]) -> Dict[str, Tuple[float, bool]]:
    """metric -> (value, higher_is_better) for one record.

    ``eps`` (examples/s, GB/s, or — serving records — sustained QPS)
    gates higher-is-better; the per-stage/per-route latency quantiles
    gate lower-is-better, so a serving regression is "p50/p99 up OR
    sustained QPS down" beyond the noise band."""
    out: Dict[str, Tuple[float, bool]] = {
        "eps": (float(rec["eps"]), True)}
    for stage, entry in (rec.get("scope") or {}).items():
        for q in ("p50_ms", "p99_ms"):
            v = entry.get(q)
            if isinstance(v, _NUM) and v > 0:
                out[f"{stage}_{q}"] = (float(v), False)
    return out


def _group_key(rec: Mapping[str, Any]) -> Tuple[str, str, str]:
    return (str(rec["plane"]), str(rec["fingerprint"]),
            json.dumps(rec.get("config") or {}, sort_keys=True))


def _median(xs: List[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def gate(records: List[Dict[str, Any]], *, window: int = BASELINE_WINDOW,
         min_band: float = MIN_BAND, safety: float = BAND_SAFETY,
         strict_fingerprint: Optional[str] = None
         ) -> Tuple[int, List[str]]:
    """(regressions, report lines): for each (plane, fingerprint,
    config) group, the newest record vs the trailing-median baseline
    with a spread-derived noise band. Groups without a baseline warn
    and pass (first run on new hardware — "soft-fail" mode) — unless
    ``strict_fingerprint`` is set (the ``--strict`` ARMED mode): then a
    no-baseline group on THAT fingerprint fails loudly — with baselines
    committed for the hardware the gate runs on, a missing one means
    the record/commit pipeline broke, not a new machine. Other
    machines' historical single-record groups stay soft (their
    baselines are not this runner's to demand)."""
    groups: Dict[Tuple[str, str, str], List[Dict[str, Any]]] = {}
    for rec in records:
        groups.setdefault(_group_key(rec), []).append(rec)
    failures = 0
    lines: List[str] = []
    for key in sorted(groups):
        plane, fp, _cfg = key
        seq = sorted(groups[key], key=lambda r: r["ts"])
        newest, base = seq[-1], seq[:-1][-window:]
        if not base:
            if strict_fingerprint is not None \
                    and fp == strict_fingerprint:
                failures += 1
                lines.append(
                    f"NO-BASELINE {plane} [{fp}]: strict gate — commit "
                    "a baseline record for this fingerprint (run "
                    "--record twice) or drop --strict on new hardware")
            else:
                lines.append(f"warn {plane} [{fp}]: no baseline record "
                             "yet — soft pass (gate arms once this "
                             "record lands in the trajectory)")
            continue
        band = safety * max([min_band, _rel_spread(newest)]
                            + [_rel_spread(r) for r in base])
        new_metrics = _gate_metrics(newest)
        for metric, (value, higher) in sorted(new_metrics.items()):
            base_vals = []
            for r in base:
                bm = _gate_metrics(r).get(metric)
                if bm is not None:
                    base_vals.append(bm[0])
            if not base_vals:
                continue
            baseline = _median(base_vals)
            if baseline <= 0:
                continue
            mband = band * (TAIL_BAND_MULT if metric.endswith("_p99_ms")
                            else 1.0)
            delta = (value - baseline) / baseline
            worse = -delta if higher else delta
            verdict = "REGRESSION" if worse > mband else "ok"
            if verdict == "REGRESSION":
                failures += 1
            lines.append(
                f"{verdict:<10} {plane}/{metric} [{fp}]: new={value:.4g} "
                f"baseline={baseline:.4g} ({len(base_vals)} rec) "
                f"delta={delta * 100:+.1f}% band=±{mband * 100:.1f}%")
    if not groups:
        lines.append("warn: trajectory is empty — nothing to gate")
    return failures, lines


# --- the cpu micro-bench (--record) ------------------------------------------

def run_record(args) -> List[Dict[str, Any]]:
    """Per-plane pull/push micro-bench on a virtual CPU mesh: measured
    span percentiles + contract-audited expected bytes + the memory
    ledger, one trajectory record per registered plane."""
    data, model = (int(x) for x in args.mesh.split("x"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", data * model)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from openembedding_tpu.analysis import memwatch, programs, scope
    from openembedding_tpu.parallel.mesh import create_mesh, DATA_AXIS
    from openembedding_tpu.utils import observability

    mesh = create_mesh(data, model)
    planes = memwatch.registered_planes()
    fingerprint, device = device_fingerprint()
    rng = np.random.RandomState(0)
    sh = NamedSharding(mesh, P(DATA_AXIS))

    def _vocab(plane: str) -> int:
        return (1 << 14) if plane == "a2a+grouped" else (1 << 16)

    def _batches(coll, vocab):
        names = tuple(coll.specs)
        idxs = {n: jax.device_put(
            jnp.asarray(rng.randint(0, vocab, size=args.batch)
                        .astype(np.int32)), sh) for n in names}
        grads = {n: jax.device_put(
            jnp.zeros((args.batch, args.dim), jnp.float32), sh)
            for n in names}
        return idxs, grads

    # expected bytes + memory ledger per plane/program (contract-audited
    # lowering — a plane whose ledger cannot be produced fails --record)
    expected: Dict[str, Dict[str, Any]] = {}
    for plane in planes:
        expected[plane] = {}
        for program in ("pull", "push"):
            expected[plane][program] = scope.plane_expected_bytes(
                mesh, plane, program, batch=args.batch, dim=args.dim)

    # warm every plane's eager dispatch programs with the SAME
    # evaluate_performance flag as measurement (it keys the jit cache)
    observability.set_evaluate_performance(True)
    try:
        worlds = {}
        for plane in planes:
            vocab = _vocab(plane)
            if plane == "a2a+grouped":
                coll = programs._grouped_collection(
                    mesh, tables=3, vocab=vocab, dim=args.dim,
                    use_hash=False)
            else:
                coll = programs._collection(mesh, plane, vocab=vocab,
                                            dim=args.dim, use_hash=False)
            states = coll.init(jax.random.PRNGKey(0))
            idxs, grads = _batches(coll, vocab)
            jax.block_until_ready(coll.pull(states, idxs))
            states = coll.apply_gradients(states, idxs, grads)
            jax.block_until_ready(jax.tree.leaves(states))
            worlds[plane] = (coll, states)
        scope.HISTOGRAMS.reset()      # drop compile-inclusive samples
        scope.reset()

        records = []
        for plane in planes:
            coll, states = worlds[plane]
            vocab = _vocab(plane)
            block_eps = []
            for _ in range(args.blocks):
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    idxs, grads = _batches(coll, vocab)
                    coll.pull(states, idxs)          # plane_timed blocks
                    states = coll.apply_gradients(states, idxs, grads)
                dt = time.perf_counter() - t0
                block_eps.append(args.steps * args.batch / dt)
            worlds[plane] = (coll, states)
            rows = scope.ledger_rows(
                [expected[plane]["pull"], expected[plane]["push"]])
            scope_section = {
                r["stage"]: {"calls": int(r["calls"]),
                             "p50_ms": round(r["p50_ms"], 4),
                             "p95_ms": round(r["p95_ms"], 4),
                             "expected_bytes": int(r["expected_bytes"]),
                             "gbps_p50": round(r["gbps_p50"], 4)
                             if r["gbps_p50"] == r["gbps_p50"] else 0.0}
                for r in rows}
            for r in rows:
                if r["calls"] < args.blocks * args.steps:
                    raise RuntimeError(
                        f"{plane}/{r['stage']}: {r['calls']} span(s) "
                        f"recorded < {args.blocks * args.steps} "
                        "dispatched — the measurement instrumentation "
                        "is broken")
            memory_section = {
                program: dict(expected[plane][program].memory or {})
                or None for program in ("pull", "push")}
            host_mem = {
                src: {k: round(v, 1) for k, v in fields.items()}
                for src, fields in observability.memory_stats().items()}
            records.append(make_record(
                plane=plane,
                config={"mesh": args.mesh, "batch": args.batch,
                        "dim": args.dim, "steps": args.steps,
                        "blocks": args.blocks,
                        "source": "graftwatch-quick" if args.quick
                        else "graftwatch"},
                eps=_median(block_eps), eps_min=min(block_eps),
                eps_max=max(block_eps), scope=scope_section,
                memory=memory_section, host_memory=host_mem,
                fingerprint=fingerprint, device=device))
    finally:
        observability.set_evaluate_performance(False)
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench trajectory recorder + perf-regression gate")
    ap.add_argument("--record", action="store_true",
                    help="run the per-plane micro-bench and append one "
                         "record per plane to the trajectory")
    ap.add_argument("--gate", action="store_true",
                    help="compare newest records against the trailing "
                         "baseline; exit 1 on regression beyond band")
    ap.add_argument("--strict", action="store_true",
                    help="armed gate: a group with no baseline FAILS "
                         "instead of soft-passing (use once baselines "
                         "for this fingerprint are committed)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized micro-bench (fewer/smaller blocks)")
    ap.add_argument("--trajectory", default=TRAJECTORY_FILE,
                    help=f"JSONL path (default {TRAJECTORY_FILE})")
    ap.add_argument("--mesh", default="2x4")
    ap.add_argument("--batch", type=int, default=0, help="0 = auto")
    ap.add_argument("--dim", type=int, default=0, help="0 = auto")
    ap.add_argument("--steps", type=int, default=0, help="0 = auto")
    ap.add_argument("--blocks", type=int, default=0, help="0 = auto")
    ap.add_argument("--window", type=int, default=BASELINE_WINDOW,
                    help="trailing records per baseline median")
    ap.add_argument("--min-band", type=float, default=MIN_BAND)
    ap.add_argument("--safety", type=float, default=BAND_SAFETY)
    args = ap.parse_args(argv)
    args.batch = args.batch or (256 if args.quick else 1024)
    args.dim = args.dim or (8 if args.quick else 16)
    args.steps = args.steps or (4 if args.quick else 10)
    args.blocks = args.blocks or (3 if args.quick else 5)

    if not (args.record or args.gate):
        ap.error("pick at least one of --record / --gate")
    rc = 0

    if args.record:
        try:
            records = run_record(args)
        except Exception as e:  # noqa: BLE001 — a plane whose ledger or
            # spans cannot be produced must fail the recorder loudly
            print(f"graftwatch: --record failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1
        for rec in records:
            append_record(args.trajectory, rec)
            sc = rec["scope"]
            print(json.dumps({
                "plane": rec["plane"], "eps": round(rec["eps"], 1),
                "eps_band": [round(rec["eps_min"], 1),
                             round(rec["eps_max"], 1)],
                "pull_p50_ms": sc["pull"]["p50_ms"],
                "push_p50_ms": sc["push"]["p50_ms"],
                "fingerprint": rec["fingerprint"]}), flush=True)
        print(f"graftwatch: appended {len(records)} record(s) to "
              f"{args.trajectory}")

    if args.gate:
        try:
            records = load_trajectory(args.trajectory)
        except ValueError as e:
            print(f"graftwatch: {e}", file=sys.stderr)
            return 2
        strict_fp = device_fingerprint()[0] if args.strict else None
        failures, lines = gate(records, window=args.window,
                               min_band=args.min_band,
                               safety=args.safety,
                               strict_fingerprint=strict_fp)
        for ln in lines:
            print(ln)
        if failures:
            print(f"graftwatch: {failures} perf regression(s) beyond "
                  "the noise band", file=sys.stderr)
            rc = 1
        else:
            print("graftwatch: gate clean")
    return rc


if __name__ == "__main__":
    sys.exit(main())
