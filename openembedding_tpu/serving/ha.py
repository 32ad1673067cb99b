"""Replicated serving: replica daemons, failover routing, restore-on-respawn.

Capability parity with the reference's serving HA plane:

* the reference places shard x replica over PS servers and every pull picks
  one live replica per shard (/root/reference/openembedding/client/Model.cpp:
  153-186, server/EmbeddingPullOperator.cpp:50-57 ``pick_one_replica``);
  a SIGKILLed server is replaced by ``server --restore``, which rebuilds its
  shards from a living replica via the coordinated-restore iterator or from
  the dump URI (server/EmbeddingRestoreOperator.cpp:12-152, entry/server.cc:
  53-56); the chaos test kills servers mid-lookup and requires continuous
  service (entry/c_api_ha_test.cpp:150-210).

* TPU-native: a serving *process* holds one full copy of every table (one
  SPMD program over its local mesh) — a process IS a replica, so replica
  placement collapses to "run N identical daemons". The pieces:

  - :func:`replica_main` / :func:`spawn_replica` — one replica daemon:
    registry + REST controller. Booting with ``--peers`` performs
    **restore-from-peer**: it fetches a living replica's model catalog
    (GET /health) and re-creates every NORMAL model from its checkpoint
    URI. The hand-off gives the catalog; the dump gives the state — and
    because serving tables are read-only, the dump *is* the replica state,
    collapsing the reference's two restore paths into one.
  - :class:`RoutingClient` — ``pick_one_replica`` + retry: lookups rotate
    over replicas from a random start, skip dead ones, and only fail when
    no replica answers (the reference serving test's 500 ms retry loop,
    entry/c_api_test.h:117-121).
  - liveness — every replica exposes GET /health; GET /cluster on any
    replica health-probes its peers (rest.py), and
    :meth:`RoutingClient.nodes` aggregates the same client-side.
"""

from __future__ import annotations

import dataclasses
import http.client
import io
import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..analysis import scope
from ..analysis.concurrency import sync_point
from .rest import TRACE_HEADER, probe_health


# --- replica daemon ---------------------------------------------------------

def replica_main(argv: Optional[Sequence[str]] = None) -> int:
    """Entry point of one serving replica (the reference's ``server`` +
    ``controller`` daemons in one process).

    --port P          REST port (0 = ephemeral, printed on stdout)
    --load SIGN=URI   model(s) to serve at boot (repeatable)
    --peers H:P,...   living replicas; restore their catalog on boot
                      (``server --restore`` equivalent)
    """
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--load", action="append", default=[])
    p.add_argument("--peers", default="")
    p.add_argument("--shard_index", type=int, default=0)
    p.add_argument("--shard_count", type=int, default=1,
                   help=">1: this replica serves only its shard slice of "
                        "each --load model (ids/keys ≡ shard_index mod "
                        "shard_count) — shard-group serving for models "
                        "larger than one process")
    p.add_argument("--hash_capacity", type=int, default=None)
    p.add_argument("--config", default="",
                   help="EnvConfig JSON file (serving section: port, "
                        "replica_num, hash_capacity, message_compress)")
    p.add_argument("--compress", default=None,
                   help="binary data-plane codec (''|zlib|zstd) — the "
                        "reference's server.message_compress; overrides "
                        "the config file")
    p.add_argument("--batch-rows", type=int, default=None,
                   help="arm the micro-batching lookup scheduler with "
                        "this per-flush row cap (0 = unbatched; "
                        "serving/batcher.py — concurrent flat lookups "
                        "coalesce into one key-deduped pull)")
    p.add_argument("--batch-wait-us", type=int, default=None,
                   help="adaptive-flush wait budget in microseconds "
                        "(the latency an idle server adds collecting "
                        "batch-mates)")
    p.add_argument("--batch-queue-rows", type=int, default=None,
                   help="bounded batcher queue depth in rows; offers "
                        "past it get 429-busy backpressure")
    p.add_argument("--adaptive", action="store_true",
                   help="arm the graftplan online tuner: the batcher's "
                        "rows/wait knobs track the offered load inside "
                        "the EnvConfig plan envelope "
                        "(serving/batcher.AdaptiveBatchTuner; "
                        "equivalent to OE_PLAN_ONLINE=1)")
    p.add_argument("--trace-out", default="",
                   help="record graftscope spans and export them as "
                        "Chrome-trace JSON here on (SIGTERM/ctrl-C) "
                        "shutdown — the server-side half of a "
                        "request-scoped trace (tools/graftload merges "
                        "it with the client capture)")
    args = p.parse_args(argv)

    import jax
    from .registry import ModelRegistry
    from .rest import ControllerServer
    from ..parallel.mesh import create_mesh
    from ..utils.compile_cache import enable_compile_cache
    from ..utils.envconfig import EnvConfig

    enable_compile_cache()
    cfg_tree = EnvConfig.load(path=args.config or None)
    cfg = cfg_tree.serving
    plan = cfg_tree.apply_chaos()
    if plan is not None:
        print(f"replica: CHAOS armed ({len(plan.faults)} fault(s))",
              flush=True)
    port = args.port if args.port is not None else cfg.port
    hash_capacity = (args.hash_capacity if args.hash_capacity is not None
                     else cfg.hash_capacity)
    compress = (args.compress if args.compress is not None
                else cfg.message_compress)
    if args.trace_out:
        # arm span recording BEFORE any request lands, and convert
        # SIGTERM into an orderly unwind so the finally below exports
        # the rings (SIGKILL still loses them — chaos kills are honest)
        import signal as signal_mod
        scope.set_tracing(True)
        signal_mod.signal(signal_mod.SIGTERM,
                          lambda *_: sys.exit(0))

    mesh = create_mesh(1, len(jax.devices()))
    registry = ModelRegistry(mesh, default_hash_capacity=hash_capacity)
    batch_rows = (args.batch_rows if args.batch_rows is not None
                  else cfg.batch_rows)
    if batch_rows > 0:
        plan_cfg = cfg_tree.plan
        if args.adaptive and not plan_cfg.online:
            import dataclasses as dc
            plan_cfg = dc.replace(plan_cfg, online=True)
        registry.enable_batching(
            max_batch_rows=batch_rows,
            max_wait_us=(args.batch_wait_us
                         if args.batch_wait_us is not None
                         else cfg.batch_wait_us),
            max_queue_rows=(args.batch_queue_rows
                            if args.batch_queue_rows is not None
                            else cfg.batch_queue_rows),
            plan=plan_cfg if plan_cfg.online else None)
        mode = (f"adaptive [{plan_cfg.rows_floor}, "
                f"{plan_cfg.rows_ceiling}]" if plan_cfg.online
                else "static")
        print(f"replica: micro-batching armed (rows={batch_rows}, "
              f"{mode})", flush=True)
    peers = [e for e in args.peers.split(",") if e]
    server = ControllerServer(registry, port=port, peers=peers,
                              compress=compress).start()
    print(f"replica: listening on {server.port}", flush=True)

    try:
        for item in args.load:
            sign, _, uri = item.partition("=")
            registry.create_model(uri, model_sign=sign or None, block=True,
                                  shard_index=args.shard_index,
                                  shard_count=args.shard_count)
            print(f"replica: loaded {sign or uri} "
                  f"(shard {args.shard_index}/{args.shard_count})",
                  flush=True)

        if peers:
            n = restore_from_peers(registry, peers, compress=compress)
            print(f"replica: restored {n} model(s) from peers", flush=True)

        if batch_rows > 0:
            # compile the batched pull programs BEFORE declaring ready:
            # the first storm must measure steady state, not XLA
            # compiles (one program per pow2 flush bucket x key dtype)
            n = registry.warm_batch_programs()
            print(f"replica: warmed {n} batched pull program(s)",
                  flush=True)

        print("replica: ready", flush=True)
        while True:
            time.sleep(3600)
    except (KeyboardInterrupt, SystemExit):
        pass
    finally:
        # graceful — on ANY exit, including a failed boot load: join the
        # accept loop + quiesce async loaders instead of letting daemon
        # teardown kill them mid-commit (graftrace JG104 discipline
        # applied to the daemon entry point)
        server.stop()
        if args.trace_out:
            scope.export_chrome_trace(
                args.trace_out,
                process_name=f"oe-replica:{server.port}")
            print(f"replica: trace -> {args.trace_out}", flush=True)
    return 0


def restore_from_peers(registry, peers: Sequence[str],
                       wait: float = 30.0, compress: str = "") -> int:
    """Re-create every NORMAL model living peers serve (catalog hand-off).

    Aggregates the catalogs of ALL live peers (a replica must not pass its
    own endpoint here — it would see its own empty catalog as live). Peers
    still loading (models in CREATING) are polled for up to ``wait`` seconds
    so concurrently-booting clusters converge. A model whose checkpoint
    URI cannot be read falls back to STREAMING THE ROWS from the living
    peer itself (the reference's coordinated-restore iterator,
    server/EmbeddingRestoreOperator.cpp:12-106) — losing the dump store
    does not prevent recovery while a replica lives. Returns the number
    restored.
    """
    deadline = time.time() + wait
    catalog: Dict[str, tuple] = {}
    while True:
        catalog.clear()
        creating = False
        for ep in peers:
            h = probe_health(ep, timeout=3.0)
            if not h or not h.get("ok"):
                continue
            for m in h.get("models", []):
                status = m.get("model_status")
                if status == "NORMAL":
                    catalog.setdefault(m["model_sign"],
                                       (m["model_uri"], ep))
                elif status == "CREATING":
                    creating = True
        # keep polling while any peer model is still loading — a settled
        # catalog (no CREATING anywhere) or the deadline ends the wait
        if not creating or time.time() >= deadline:
            break
        time.sleep(0.5)
    # interleaving marker: the catalog is settled; every restore below
    # re-creates a model a LIVING peer served as NORMAL (the graftproto
    # ha_registry model's restore_start guard — CREATING entries never
    # restore, they were polled away above)
    sync_point("ha.restore.catalog")
    n = 0
    for sign, (uri, ep) in catalog.items():
        try:
            sync_point("ha.restore.model")
            registry.create_model(uri, model_sign=sign, block=True)
            n += 1
        except ValueError:
            pass  # already loading/loaded locally
        except (RuntimeError, OSError) as e:
            # RuntimeError: load thread failed; OSError: the dump URI itself
            # is gone (deleted/unreachable store) — the exact case the
            # peer-row stream exists for
            print(f"replica: dump restore of {sign!r} from {uri!r} failed "
                  f"({e}); streaming rows from peer {ep}", flush=True)
            try:
                restore_model_from_peer(registry, ep, sign,
                                        compress=compress)
                n += 1
            except Exception as e2:  # noqa: BLE001 — logged, not fatal
                print(f"replica: peer-row restore of {sign!r} failed: "
                      f"{e2}", flush=True)
    return n


def _np_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def fetch_rows_page(endpoint: str, sign: str, variable: str, offset: int,
                    limit: int, timeout: float = 60.0,
                    compress: str = ""):
    """One page of the peer-restore row stream: ``(ids, rows, total)``.
    ``compress`` asks the peer to pack the page body (the requester picks
    the codec — a restore crossing a WAN-ish link trades CPU for bytes,
    the reference's compressed RpcView reads, server/RpcView.h:63-105)."""
    url = (f"http://{endpoint}/models/{sign}/rows?variable={variable}"
           f"&offset={offset}&limit={limit}")
    if compress:
        url += f"&compress={compress}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        raw = r.read()
    nl = raw.index(b"\n")
    head = json.loads(raw[:nl])
    body = raw[nl + 1:]
    if head.get("compress"):
        from ..utils import compress as compress_lib
        body = compress_lib.decompress(head["compress"], body)
    n = head["n"]
    ids = np.frombuffer(body[:n * 8], np.int64)
    rows = np.frombuffer(body[n * 8:], _np_dtype(head["dtype"]))
    rows = rows.reshape(n, head["dim"]) if head["dim"] else \
        rows.reshape(n, 0)
    return ids, rows, head["total"]


def restore_model_from_peer(registry, endpoint: str, sign: str, *,
                            page: int = 1 << 16,
                            timeout: float = 60.0,
                            compress: str = "") -> str:
    """Rebuild ``sign`` purely from a LIVING replica's memory.

    The dump-less restore path: fetch the peer's ModelMeta, allocate blank
    states, page every variable's rows over the binary /rows endpoint and
    deliver them through the same machinery the checkpoint loader uses —
    the reference's replica-iterator restore
    (server/EmbeddingRestoreOperator.cpp:12-106) as HTTP row streaming.
    For shard-group models the peer must belong to the SAME group (ids are
    global; the restorer re-filters by its own slice on delivery).
    """
    import jax
    from ..meta import ModelMeta
    from ..parallel import sharded_hash as sh
    from ..parallel import sharded_table as st
    from .. import hash_table as hash_lib
    from .. import table as table_lib
    from ..embedding import EmbeddingCollection
    from .registry import ServingModel, _specs_from_meta

    with urllib.request.urlopen(
            f"http://{endpoint}/models/{sign}/meta", timeout=timeout) as r:
        info = json.loads(r.read())
    meta = ModelMeta.loads(info["meta"])
    shard_slice = ((info["shard_index"], info["shard_count"])
                   if info.get("shard_count", 1) > 1 else None)
    specs = _specs_from_meta(meta, registry.default_hash_capacity, -1,
                             shard_slice)
    coll = EmbeddingCollection(specs, registry.mesh)
    hash_names = [n for n, s in coll.specs.items() if s.use_hash]
    states = coll.init(jax.random.PRNGKey(0), only=hash_names)
    out = {}
    codec = compress

    def fetch(vname, off):
        nonlocal codec
        try:
            return fetch_rows_page(endpoint, sign, vname, off, page,
                                   timeout, compress=codec)
        except urllib.error.HTTPError as e:
            if codec and e.code in (400, 404):
                # 404: pre-upgrade peer (its /rows route has no compress
                # parameter); 400: the peer knows the parameter but not
                # this codec — either way, raw pages restore fine
                codec = ""
                return fetch_rows_page(endpoint, sign, vname, off, page,
                                       timeout)
            raise

    for name, spec in coll.specs.items():
        sspec = coll.sharding_spec(name)
        offset, total = 0, None
        if spec.use_hash:
            from ..parallel import hot_cache
            state = hot_cache.unwrap(states[name])
            empty = hash_lib.empty_key(np.dtype(state.keys.dtype))
            wide = hash_lib.is_wide(state.keys)
            while total is None or offset < total:
                ids, rows, total = fetch(name, offset)
                offset += page
                if not ids.size:
                    continue
                if wide:
                    # ids travel joined as int64; re-split for the table
                    ck = np.full((page, 2), empty, np.int32)
                    ck[:ids.size] = hash_lib.split64(ids)
                else:
                    ck = np.full((page,), empty,
                                 dtype=np.dtype(state.keys.dtype))
                    ck[:ids.size] = ids
                cw = np.zeros((page,) + rows.shape[1:], rows.dtype)
                cw[:ids.size] = rows
                import jax.numpy as jnp
                state = sh.insert_rows_sharded(
                    state, jnp.asarray(ck), jnp.asarray(cw), {},
                    mesh=coll.mesh, spec=sspec)
            if int(jax.device_get(state.insert_failures)) > 0:
                raise RuntimeError(
                    f"peer restore of {name!r}: rows did not fit the "
                    "local hash capacity")
            # cached-plane variables get a fresh all-pad replica back
            out[name] = coll.wrap_hot_cache(name, state)
        else:
            import jax.numpy as jnp
            dtype = np.dtype(table_lib.resolve_dtype(spec.meta()))
            weights = st.filled_sharded(coll.mesh, sspec,
                                        (spec.output_dim,), 0.0, dtype)
            while total is None or offset < total:
                ids, rows, total = fetch(name, offset)
                offset += page
                if not ids.size:
                    continue
                if shard_slice is not None:
                    k, G = shard_slice
                    sel = (ids % G) == k
                    local = ids[sel] // G
                    rows = rows[sel]
                else:
                    local = ids
                shard, loc = sspec.shard_and_local(local)
                phys = np.where(local < spec.input_dim,
                                shard * sspec.rows_per_shard + loc, -1)
                phys_p = np.full((page,), -1, np.int64)
                phys_p[:phys.size] = phys
                rows_p = np.zeros((page,) + rows.shape[1:], dtype)
                rows_p[:rows.shape[0]] = rows
                weights = st.deliver_rows_sharded(
                    weights, jnp.asarray(phys_p), jnp.asarray(rows_p),
                    mesh=coll.mesh, spec=sspec)
            out[name] = coll.wrap_hot_cache(
                name, table_lib.TableState(weights=weights, slots={}))
    # carry the peer's hot-swap version: the streamed rows already
    # reflect every delta it applied (pre-upgrade peers send none -> 0)
    model = ServingModel(sign, coll, out, meta, shard_slice=shard_slice,
                         version=int(info.get("version", 0)))
    return registry.register_model(model)


def spawn_replica(port: int, *, load: Sequence[str] = (),
                  peers: Sequence[str] = (),
                  env: Optional[Dict[str, str]] = None,
                  devices: int = 1,
                  shard_index: int = 0,
                  shard_count: int = 1,
                  compress: str = "",
                  trace_out: str = "",
                  batch_rows: int = 0,
                  batch_wait_us: Optional[int] = None,
                  batch_queue_rows: Optional[int] = None,
                  adaptive: bool = False
                  ) -> subprocess.Popen:
    """Start a replica daemon as a child process (test/driver helper)."""
    cmd = [sys.executable, "-m", "openembedding_tpu.serving.ha",
           "--port", str(port)]
    if compress:
        cmd += ["--compress", compress]
    if trace_out:
        cmd += ["--trace-out", trace_out]
    if batch_rows:
        cmd += ["--batch-rows", str(batch_rows)]
        if batch_wait_us is not None:
            cmd += ["--batch-wait-us", str(batch_wait_us)]
        if batch_queue_rows is not None:
            cmd += ["--batch-queue-rows", str(batch_queue_rows)]
        if adaptive:
            cmd += ["--adaptive"]
    for item in load:
        cmd += ["--load", item]
    if peers:
        cmd += ["--peers", ",".join(peers)]
    if shard_count > 1:
        cmd += ["--shard_index", str(shard_index),
                "--shard_count", str(shard_count)]
    # one process for each chip: a parent that has touched JAX holds the
    # chip and the libtpu lockfile, and a child reaching for either fails
    # or hangs — so a spawned replica is a CPU process. Only a caller whose
    # own process stays off JAX may hand a replica the chip (``env``).
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                 "JAX_NUM_CPU_DEVICES": str(devices), **(env or {})}
    child_env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    child_env["PYTHONPATH"] = root + os.pathsep + child_env.get(
        "PYTHONPATH", "")
    return subprocess.Popen(cmd, env=child_env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def wait_ready(endpoint: str, timeout: float = 120.0,
               sign: Optional[str] = None) -> bool:
    """Poll /health until the replica answers (and serves ``sign`` if given)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        h = probe_health(endpoint)
        if h and h.get("ok"):
            if sign is None:
                return True
            for m in h.get("models", []):
                if m.get("model_sign") == sign and \
                        m.get("model_status") == "NORMAL":
                    return True
        time.sleep(0.3)
    return False


# --- routing client ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """ONE deadline-budgeted retry policy for every RoutingClient verb.

    Replaces the ad-hoc per-verb behavior (lookups: one rotation then
    raise; delta pushes: one attempt per endpoint, no retry) with a
    shared budget: a logical request may spend ``deadline_s`` of wall
    clock total, across however many fleet rotations fit, with
    exponential backoff + jitter between rounds (decorrelated enough
    that a thundering herd of clients doesn't re-storm a recovering
    replica in lockstep). The deadline is a REQUEST property, not an
    attempt property — the per-connection HTTP timeout stays separate
    (``RoutingClient(timeout=)``) and bounds one socket wait.

    Budget spending is observable: ``oe_serving_retry_rounds_total``
    counts full-fleet rounds that failed and backed off,
    ``oe_serving_retry_budget_exhausted_total`` counts requests that
    died at the deadline, and the existing retry/failover counters keep
    their per-attempt meaning.
    """

    deadline_s: float = 10.0
    base_backoff_s: float = 0.05
    max_backoff_s: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.5       # sleep *= uniform(1 - jitter, 1)

    def __post_init__(self):
        if self.deadline_s <= 0:
            raise ValueError("deadline_s must be > 0")
        if self.base_backoff_s < 0 or self.max_backoff_s < 0:
            raise ValueError("backoff bounds must be >= 0")
        if self.multiplier < 1.0:
            raise ValueError("multiplier must be >= 1")
        if not (0.0 <= self.jitter <= 1.0):
            raise ValueError("jitter must be in [0, 1]")

    def backoff(self, round_index: int) -> float:
        """Jittered sleep before round ``round_index + 1`` (0-based:
        backoff(0) follows the first failed round)."""
        raw = min(self.max_backoff_s,
                  self.base_backoff_s * self.multiplier ** round_index)
        return raw * (1.0 - self.jitter * random.random())

class _NoDelayHTTPConnection(http.client.HTTPConnection):
    """Persistent client connection with Nagle disabled.

    A kept-alive connection carries each request as (at least) two
    small writes — header block, then body. With Nagle on, the second
    write queues behind the server's delayed ACK of the first: a flat
    ~40 ms added to EVERY request (measured on loopback; the
    interaction the keep-alive satellite exists to remove, reappearing
    one layer down). The server handler disables Nagle on its side for
    the same reason (rest.py ``disable_nagle_algorithm``)."""

    def connect(self):
        super().connect()
        import socket as socket_mod
        try:
            self.sock.setsockopt(socket_mod.IPPROTO_TCP,
                                 socket_mod.TCP_NODELAY, 1)
        except OSError:
            pass  # non-TCP transports (tests with mocks) just skip it


class RoutingClient:
    """Failover lookup client over N replica endpoints.

    The reference's replica selection + retry: start at a random replica
    (load spreading, ``pick_one_replica(PickAlgo)``), rotate on failure,
    raise only when every replica failed. Dead endpoints are remembered as
    suspect and probed again on later calls (a respawned replica rejoins
    automatically — there is no registration step, matching the reference
    where the master only tracks liveness).
    """

    def __init__(self, endpoints: Sequence[str], timeout: float = 10.0,
                 compress: str = "",
                 policy: Optional[RetryPolicy] = None):
        if not endpoints:
            raise ValueError("need at least one replica endpoint")
        from ..utils import compress as compress_lib
        self.endpoints = list(endpoints)
        self.timeout = timeout
        # the per-request budget defaults to the per-connection timeout:
        # a caller that accepted waiting `timeout` on one socket accepts
        # the same wall budget for the whole retry dance
        self.policy = policy if policy is not None \
            else RetryPolicy(deadline_s=timeout)
        # last delta version each endpoint ACKed, per sign — feeds the
        # degraded-replica staleness gauge (push_delta)
        self._acked_versions: Dict[tuple, int] = {}
        # advertised to servers on binary lookups; responses from servers
        # configured with the same message_compress codec arrive packed
        self.compress = compress_lib.check(compress)
        # keep-alive connection pool: one persistent HTTP/1.1 connection
        # per (thread, endpoint) — lookups used to open a fresh TCP
        # connection per request, so connect setup inflated every
        # measured serving latency. Per-THREAD pools keep the hot path
        # lock-free (http.client connections are not thread-safe); the
        # flat registry below exists only so close() can drop sockets
        # opened by worker threads that already exited.
        self._tls = threading.local()
        self._conns_lock = threading.Lock()
        self._conns: List[http.client.HTTPConnection] = []

    # -- raw http (keep-alive pool) ----------------------------------------
    def _connection(self, endpoint: str):
        """(conn, reused): this thread's persistent connection to
        ``endpoint``, opening one on first use."""
        pool = getattr(self._tls, "conns", None)
        if pool is None:
            pool = self._tls.conns = {}
        conn = pool.get(endpoint)
        if conn is not None:
            if conn.sock is not None:
                return conn, True
            # a pooled conn whose socket is gone (client close(), idle
            # teardown): http.client's auto_open would silently
            # reconnect with a socket neither close() nor the
            # connection counter ever sees — treat as a pool miss
            self._drop_connection(endpoint)
        host, sep, port = endpoint.rpartition(":")
        if not sep:
            host, port = endpoint, "80"   # bare hostname, like urllib
        conn = _NoDelayHTTPConnection(host, int(port),
                                      timeout=self.timeout)
        pool[endpoint] = conn
        with self._conns_lock:
            self._conns.append(conn)
        scope.HISTOGRAMS.inc("serving_client_connections",
                             endpoint=endpoint)
        return conn, False

    def _drop_connection(self, endpoint: str) -> None:
        pool = getattr(self._tls, "conns", None)
        conn = pool.pop(endpoint, None) if pool else None
        if conn is not None:
            try:
                conn.close()
            except Exception:  # noqa: BLE001 — already broken
                pass
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def close(self) -> None:
        """Close every pooled connection (all threads). Call when done
        with the client — otherwise each idle kept-alive socket pins a
        server handler thread until the server-side idle timeout."""
        with self._conns_lock:
            conns, self._conns = list(self._conns), []
        for conn in conns:
            try:
                conn.close()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass

    def __enter__(self) -> "RoutingClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _raw(self, endpoint: str, method: str, path: str,
             body: Optional[bytes], content_type: str) -> bytes:
        """One HTTP round trip on the pooled connection. A failure on a
        REUSED connection retries once on a fresh one (a server-side
        idle close is not a dead replica); HTTP error statuses raise
        ``urllib.error.HTTPError`` so the failover rotation keeps its
        status-code semantics."""
        headers = {"Content-Type": content_type}
        tid = scope.current_trace_id()
        if tid:
            headers[TRACE_HEADER] = tid
        while True:
            conn, reused = self._connection(endpoint)
            try:
                conn.request(method, path, body=body, headers=headers)
                resp = conn.getresponse()
                data = resp.read()   # drain fully: keeps conn reusable
                status, reason = resp.status, resp.reason
                rheaders = resp.headers
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                self._drop_connection(endpoint)
                if not reused:
                    raise
                # stale keep-alive connection — one fresh retry (reads
                # and delta pushes are both idempotent)
        if status >= 400:
            raise urllib.error.HTTPError(
                f"http://{endpoint}{path}", status, reason, rheaders,
                io.BytesIO(data))
        return data

    def _request(self, endpoint: str, method: str, path: str,
                 body: Optional[dict] = None) -> Any:
        data = json.dumps(body).encode() if body is not None else None
        payload = self._raw(endpoint, method, path, data,
                            "application/json")
        return json.loads(payload) if payload else None

    def _rotate(self, attempt) -> Any:
        """Shared failover rotation under the ONE retry policy: start at
        a random replica (load spreading), rotate on dead/busy replicas
        — the reference's pick_one_replica + retry — and when a whole
        round fails, back off (exponential + jitter) and rotate again
        until the per-request deadline is spent. Every attempt is
        recorded as a ``serving.rpc`` span labeled with the replica and
        its outcome (ok / ok_failover / busy / failover), carrying the
        active trace id — the router leg of the request-scoped Perfetto
        story — and bumps the ``serving_request_retries`` /
        ``serving_request_failovers`` counters on /metrics; failed
        rounds bump ``serving_retry_rounds`` and a request that dies at
        the deadline bumps ``serving_retry_budget_exhausted``."""
        policy = self.policy
        deadline = time.monotonic() + policy.deadline_s
        order = list(self.endpoints)
        start = random.randrange(len(order))
        order = order[start:] + order[:start]
        last_err: Optional[Exception] = None
        busy429: Optional[Exception] = None
        rnd = 0
        while True:
            for i, ep in enumerate(order):
                t0 = time.perf_counter()
                try:
                    # inside the try: an injected drop (chaos drop_net
                    # at this marker) classifies as a dead replica and
                    # rotates, exactly like a real connection loss
                    sync_point("routing.attempt")
                    out = attempt(ep)
                # NOTE: HTTPError subclasses URLError — it must be caught
                # first, else every 404 would read as a dead replica
                except urllib.error.HTTPError as e:
                    dt = time.perf_counter() - t0
                    # 409/503: CREATING etc; 429: batcher queue full —
                    # THIS replica is oversubscribed, another may have
                    # headroom
                    if e.code in (409, 429, 503):  # busy: try another
                        scope.record_span(
                            "serving.rpc", t0, dt,
                            {"replica": ep, "outcome": "busy"},
                            error=f"HTTP{e.code}")
                        scope.HISTOGRAMS.inc("serving_request_retries")
                        last_err = e
                        if e.code == 429:
                            busy429 = e
                        continue
                    scope.record_span("serving.rpc", t0, dt,
                                      {"replica": ep, "outcome": "error"},
                                      error=f"HTTP{e.code}")
                    raise
                except (urllib.error.URLError, http.client.HTTPException,
                        ConnectionError, OSError, TimeoutError) as e:
                    # dead/unreachable replica — including one killed mid-
                    # response (IncompleteRead/RemoteDisconnected): rotate
                    scope.record_span("serving.rpc", t0,
                                      time.perf_counter() - t0,
                                      {"replica": ep,
                                       "outcome": "failover"},
                                      error=type(e).__name__)
                    scope.HISTOGRAMS.inc("serving_request_failovers")
                    last_err = e
                    continue
                scope.record_span(
                    "serving.rpc", t0, time.perf_counter() - t0,
                    {"replica": ep,
                     "outcome": "ok" if rnd == 0 and i == 0
                     else "ok_failover"})
                return out
            if busy429 is not None:
                # SOME replica rejected with batcher backpressure (even
                # if the others were dead — the chaos + backpressure
                # mix): surface the 429 itself NOW, without spending
                # retry budget — backpressure is an ANSWER, not an
                # outage, and the caller (graftload) must count a
                # rejection promptly so overload propagates instead of
                # amplifying into deadline-long client stalls. Tracked
                # on its own flag: last_err holds whichever replica
                # failed LAST in rotation order, which under a mixed
                # storm is a coin flip between the dead and busy one.
                raise busy429
            # the whole fleet is DEAD this round: spend retry budget —
            # a respawning replica (the kill-and-respawn chaos lane)
            # rejoins within a backoff or two
            sleep = policy.backoff(rnd)
            rnd += 1
            if time.monotonic() + sleep >= deadline:
                scope.HISTOGRAMS.inc("serving_retry_budget_exhausted")
                break
            scope.HISTOGRAMS.inc("serving_retry_rounds")
            time.sleep(sleep)
        raise ConnectionError(
            f"no live replica among {self.endpoints} within "
            f"{policy.deadline_s:.3g}s ({rnd} round(s)): {last_err}")

    def _failover(self, method: str, path: str, body=None) -> Any:
        return self._rotate(
            lambda ep: self._request(ep, method, path, body))

    def _request_bin(self, endpoint: str, path: str, body: bytes) -> bytes:
        return self._raw(endpoint, "POST", path, body,
                         "application/octet-stream")

    # -- serving API -------------------------------------------------------
    def lookup(self, sign: str, variable: Any, indices) -> np.ndarray:
        """Read-only pull with replica failover (never fails while one
        replica lives — the chaos-test invariant). Rides the BINARY
        protocol — the default data plane (the reference's serving plane is
        zero-copy binary throughout, server/RpcView.h:63-105); see
        :meth:`lookup_json` for the debug-friendly JSON twin."""
        return self.lookup_bin(sign, variable, indices)

    def lookup_json(self, sign: str, variable: Any, indices) -> np.ndarray:
        """JSON-marshalled pull (human-readable wire, for debugging)."""
        with scope.trace_context(), \
                scope.span("client.lookup", proto="json"):
            out = self._failover(
                "POST", f"/models/{sign}/lookup",
                {"variable": variable,
                 "indices": np.asarray(indices).tolist()})
        return np.asarray(out["rows"], dtype=np.float32)

    def lookup_bin(self, sign: str, variable: Any, indices) -> np.ndarray:
        """Binary-protocol pull: packed ids out, packed f32 rows back — no
        JSON list marshalling (the reference's zero-copy RpcView role,
        server/RpcView.h). The request header carries the index SHAPE, so
        wide [n, 2] pair queries and multi-dim batch shapes reconstruct
        exactly server-side. NOTE the wide-spec shape carve-out
        (registry.ServingModel.lookup): on a WIDE spec any trailing dim
        of 2 is a pair axis — send a genuine narrow length-2 sequence as
        ``[B, L, 2]`` pairs or pad it to L != 2. When the client was
        built with a ``compress`` codec it is ADVERTISED here
        (``accept_compress``); a server
        configured with the same ``message_compress`` codec compresses the
        row payload (the reference's compressed pull responses,
        EmbeddingPullOperator.cpp:149-205). Same failover rotation as
        :meth:`lookup`."""
        idx = np.ascontiguousarray(np.asarray(indices))
        req = {"variable": variable, "dtype": idx.dtype.name,
               "shape": list(idx.shape)}
        if self.compress:
            req["accept_compress"] = [self.compress]
        head = json.dumps(req).encode() + b"\n"
        body = head + idx.tobytes()

        def attempt(ep):
            raw = self._request_bin(ep, f"/models/{sign}/lookup_bin", body)
            nl = raw.index(b"\n")
            h = json.loads(raw[:nl])
            payload = raw[nl + 1:]
            if h.get("compress"):
                from ..utils import compress as compress_lib
                payload = compress_lib.decompress(h["compress"], payload)
            # one release of tolerance for rolling upgrades: pre-r4
            # replicas answered {"n","dim"} instead of {"shape"}
            shape = h.get("shape") or [int(h["n"]), int(h["dim"])]
            return np.frombuffer(payload, np.float32).reshape(shape)

        # trace_context with no arg: a fresh request id — or the
        # enclosing one when this is a ShardedRoutingClient fan-out leg,
        # so every shard's spans stitch into the SAME trace
        with scope.trace_context(), \
                scope.span("client.lookup", proto="bin"):
            return self._rotate(attempt)

    def create_model(self, model_uri: str, *,
                     model_sign: Optional[str] = None,
                     block: bool = True) -> List[str]:
        """Create the model on EVERY replica (replica placement)."""
        signs = []
        for ep in self.endpoints:
            out = self._request(ep, "POST", "/models",
                                {"model_uri": model_uri,
                                 "model_sign": model_sign, "block": block})
            signs.append(out["model_sign"])
        return signs

    def _push_one(self, ep: str, path: str, body: bytes,
                  deadline: float) -> bytes:
        """One endpoint's delta push under the shared retry policy:
        connection-class failures retry with backoff until ``deadline``;
        an HTTP status is a definite server answer and never retries
        (delta applies are idempotent — a stale seq ACKs as a no-op —
        so the retries themselves are safe)."""
        rnd = 0
        while True:
            try:
                return self._request_bin(ep, path, body)
            except urllib.error.HTTPError:
                raise
            except (urllib.error.URLError, http.client.HTTPException,
                    ConnectionError, OSError, TimeoutError):
                sleep = self.policy.backoff(rnd)
                rnd += 1
                if time.monotonic() + sleep >= deadline:
                    scope.HISTOGRAMS.inc("serving_retry_budget_exhausted")
                    raise
                scope.HISTOGRAMS.inc("serving_request_retries")
                time.sleep(sleep)

    def push_delta(self, sign: str, delta) -> List[Dict[str, Any]]:
        """BROADCAST a trainer-published delta to every replica (the
        streaming train->serve hot-swap, ``registry.apply_delta``) —
        unlike lookups this is not a failover pick: every replica must
        converge to the published version. ``delta`` is a
        ``checkpoint_delta.Delta`` or its ``encode_delta`` bytes.

        Runs under the same :class:`RetryPolicy` as lookups (each
        endpoint retries connection failures with backoff inside the
        request deadline). Per-endpoint results carry ``error`` instead
        of raising — GRACEFUL DEGRADATION: a replica that misses the
        push keeps serving its last-good version (it catches up at
        respawn via ``read_deltas_since`` or reload), and the fleet's
        worst version lag is exported as the
        ``oe_serving_staleness_seq`` gauge (0 = every replica ACKed the
        newest published seq) with each endpoint's lag in the returned
        ``staleness`` field.
        """
        from .. import checkpoint_delta as cd
        from ..utils import observability
        body = bytes(delta) if isinstance(delta, (bytes, bytearray)) \
            else cd.encode_delta(delta)
        target = None if isinstance(delta, (bytes, bytearray)) \
            else int(delta.seq)
        deadline = time.monotonic() + self.policy.deadline_s
        out: List[Dict[str, Any]] = []
        for ep in self.endpoints:
            try:
                raw = self._push_one(ep, f"/models/{sign}/delta", body,
                                     deadline)
                res = {"endpoint": ep, **json.loads(raw)}
                if "version" in res:
                    self._acked_versions[(sign, ep)] = int(res["version"])
            except Exception as e:  # noqa: BLE001 — per-replica verdict
                res = {"endpoint": ep, "applied": False,
                       "error": f"{type(e).__name__}: {e}"}
            out.append(res)
        # staleness: lag of each replica behind the newest version any
        # replica (or the delta itself) is known to carry
        acked = [int(r["version"]) for r in out if "version" in r]
        if target is None:
            target = max(acked, default=None)
        if target is not None:
            worst = 0
            for r in out:
                last = int(r["version"]) if "version" in r else \
                    self._acked_versions.get((sign, r["endpoint"]), 0)
                r["staleness"] = max(0, target - last)
                worst = max(worst, r["staleness"])
            observability.set_gauge("serving_staleness_seq", float(worst))
        return out

    def nodes(self) -> List[Dict[str, Any]]:
        """Cluster liveness, client-side aggregated."""
        from .rest import probe_nodes
        return probe_nodes(self.endpoints)


class ShardedRoutingClient:
    """Shard-group lookup client: shards x replicas over N processes.

    The reference places shard x replica over PS nodes and a pull fans out
    per-shard requests, picking one live replica per shard
    (/root/reference/openembedding/client/Model.cpp:153-186,
    server/EmbeddingPullOperator.cpp:50-57). Here ``groups[k]`` lists the
    replica endpoints of shard k (ids/keys ≡ k mod G); a lookup partitions
    its indices by owner, queries each owner group through that group's
    failover rotation, and merges rows back by position. Service survives
    any failure that leaves >= 1 live replica per shard group.
    """

    def __init__(self, groups: Sequence[Sequence[str]],
                 timeout: float = 10.0, compress: str = ""):
        if not groups or any(not g for g in groups):
            raise ValueError("need >= 1 replica endpoint per shard group")
        self.groups = [RoutingClient(list(g), timeout=timeout,
                                     compress=compress)
                       for g in groups]

    @property
    def shard_count(self) -> int:
        return len(self.groups)

    def close(self) -> None:
        for g in self.groups:
            g.close()

    def __enter__(self) -> "ShardedRoutingClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def lookup(self, sign: str, variable: Any, indices, *,
               wide: bool = False) -> np.ndarray:
        """Partition ``indices`` by owner group, fan out, merge by position.

        ``wide=True``: indices are ``[..., 2]`` int32 (lo, hi) pairs (the
        x64-off 64-bit key encoding, ``hash_table.split64``); the owner is
        ``joined_id % G`` — the same rule the loader's shard slice and the
        in-process filter apply, so every pair routes to the group that
        holds its row.
        """
        idx = np.asarray(indices)
        G = self.shard_count
        if wide:
            from .. import hash_table as hash_lib
            if idx.ndim < 2 or idx.shape[-1] != 2:
                raise ValueError(
                    f"wide lookup takes [..., 2] int32 pairs "
                    f"(hash_table.split64), got shape {idx.shape}")
            if idx.dtype != np.int32:
                # nested Python lists arrive int64; the WORD values must
                # still be int32 (anything bigger is a raw 64-bit id that
                # belongs in split64, not a pair word)
                if (idx > np.iinfo(np.int32).max).any() or \
                        (idx < np.iinfo(np.int32).min).any():
                    raise ValueError(
                        "wide lookup pair words exceed int32 — pass "
                        "hash_table.split64(ids), not raw 64-bit ids")
                idx = idx.astype(np.int32)
            flat = np.ascontiguousarray(idx.reshape(-1, 2))
            owner = hash_lib.join64(flat) % G
            out_shape = idx.shape[:-1]
        else:
            flat = idx.ravel()
            owner = flat % G
            out_shape = idx.shape
        # ONE trace id for the whole fan-out: each owner-group leg runs
        # its RoutingClient.lookup INSIDE this context, so its client/
        # rpc spans — and the server-side spans they propagate to —
        # stitch into a single Perfetto trace. Fan-out width lands on
        # /metrics as a counter + distribution.
        with scope.trace_context(), \
                scope.span("client.lookup", proto="sharded") as sp:
            rows = None
            fanout = 0
            for k in range(G):
                sel = np.nonzero(owner == k)[0]
                if not sel.size:
                    continue
                fanout += 1
                part = self.groups[k].lookup(sign, variable, flat[sel])
                if rows is None:
                    rows = np.zeros((flat.shape[0],) + part.shape[1:],
                                    part.dtype)
                rows[sel] = part
            sp.detail = dict(sp.detail or {}, fanout=fanout)
            scope.HISTOGRAMS.inc("serving_request_fanout", float(fanout))
            scope.HISTOGRAMS.observe("serving_fanout_width",
                                     float(fanout))
        if rows is None:
            rows = np.zeros((0, 0), np.float32)
        return rows.reshape(out_shape + rows.shape[1:])

    def create_model(self, model_uri: str, *,
                     model_sign: Optional[str] = None,
                     block: bool = True) -> List[str]:
        """Create the model on every process with its group's shard slice."""
        signs = []
        for k, group in enumerate(self.groups):
            for ep in group.endpoints:
                out = group._request(
                    ep, "POST", "/models",
                    {"model_uri": model_uri, "model_sign": model_sign,
                     "shard_index": k, "shard_count": self.shard_count,
                     "block": block})
                signs.append(out["model_sign"])
        return signs

    def push_delta(self, sign: str, delta) -> List[Dict[str, Any]]:
        """Broadcast a delta to every replica of every shard group (each
        process's shard slice keeps only its owned rows, exactly like
        the load path's slice filter). Encoded ONCE here, not once per
        group."""
        from .. import checkpoint_delta as cd
        body = bytes(delta) if isinstance(delta, (bytes, bytearray)) \
            else cd.encode_delta(delta)
        return [res for g in self.groups
                for res in g.push_delta(sign, body)]

    def nodes(self) -> List[Dict[str, Any]]:
        from .rest import probe_nodes
        return probe_nodes([ep for g in self.groups for ep in g.endpoints])


if __name__ == "__main__":
    sys.exit(replica_main())
