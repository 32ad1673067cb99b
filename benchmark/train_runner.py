"""Runs one training cell: set-up, the followed first steps, warm-up, the
measured window through ``Trainer.fit``, then the reference and the
comparison. Traffic files of ``"kind": "train"`` come here."""

import collections
import gc
import json
import os
import shutil
import time

import jax
import numpy as np

from . import correct, reference, system as system_lib

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
FOLLOWED_STEPS = 3
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")


class CompileCounter:
    """Programs XLA compiled or fetched from the persistent cache, counted
    from ``jax.monitoring`` (the event ``analysis/retrace`` listens to)."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1


class Feed:
    """The iterable ``Trainer.fit`` draws from: the pool, cycled.

    ``fit`` dispatches without waiting for the device, so the feed bounds
    the steps in flight: before it hands out a batch it waits for a probe,
    a one-element program it queued behind an earlier step. The wait's
    return stamps that step's completion on the host clock. Stops after
    ``steps`` batches, or when the steps already handed out will fill
    ``seconds``. One call (the profiler's start) is made on the way: where
    the steps handed out will reach ``at_seconds``, or, for a window whose
    length is a count of steps, before batch ``at_step`` is handed out.
    """

    def __init__(self, pool, probe, *, lag, in_flight, steps=None,
                 seconds=None, lead_in=0, on_start=None, at_seconds=None,
                 at_step=None):
        self.pool, self.probe = pool, probe
        self.lag = lag              # steps between a batch's hand-out and
        self.in_flight = in_flight  # its dispatch: fit's lookahead window
        self.steps, self.seconds = steps, seconds
        # the window's clock starts when step ``lead_in`` - 1 is done:
        # whatever the first steps of a call to fit cost stays outside
        self.lead_in, self.on_start = lead_in, on_start
        self.at_seconds = at_seconds    # (seconds, callable), called once
        self.at_step = at_step          # (batches handed out, callable)
        self.done = []              # host-clock completion time per step
        self.handed = 0
        self.waited_s = 0.0
        self.started = None         # the window's start, on the host clock
        self.called_at = None       # (start, end) of that one call

    def _step_s(self):
        recent = self.done[-21:]
        if len(recent) < 2:
            return 0.0
        return (recent[-1] - recent[0]) / (len(recent) - 1)

    def _start(self, now):
        self.started = now
        if self.on_start:
            self.on_start()

    def _call(self, call, now):
        call()
        self.called_at = (now - self.started,
                          time.perf_counter() - self.started)
        self.waited_s += time.perf_counter() - now

    def __iter__(self):
        if not self.lead_in:
            self._start(time.perf_counter())
        pending = collections.deque()
        token = self.probe(np.zeros((), np.float32))
        while self.steps is None or self.handed < self.steps:
            now = time.perf_counter()
            if self.started is not None:
                # the steps handed out but not yet done run on past now
                ahead = now - self.started \
                    + (self.lag + self.in_flight) * self._step_s()
                if self.at_seconds and ahead >= self.at_seconds[0]:
                    call, self.at_seconds = self.at_seconds[1], None
                    self._call(call, now)
                if self.at_step and self.handed >= self.at_step[0]:
                    call, self.at_step = self.at_step[1], None
                    self._call(call, now)
                if self.seconds is not None and ahead >= self.seconds:
                    break
            token = self.probe(token)
            pending.append(token)
            if len(pending) >= self.in_flight:
                with jax.profiler.TraceAnnotation("benchmark.wait"):
                    pending.popleft().block_until_ready()
                after = time.perf_counter()
                self.waited_s += after - now
                if self.handed - self.in_flight >= self.lag:
                    self.done.append(after)
                    if self.started is None \
                            and len(self.done) == self.lead_in:
                        self.waited_s = 0.0
                        self._start(after)
            yield self.pool[self.handed % len(self.pool)]
            self.handed += 1


def _norm(a):
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def _followed(system, trainer, state, feed_of, raw, prog_batches):
    """Drive the first steps through the window's own call and feed, and
    read what the comparison needs of the program's side."""
    first = reference.compact_ids(raw)[4]
    dense0 = jax.device_get(system_lib.dense_leaves(state.params))
    rows0 = system_lib.pull_rows(system, state.emb, prog_batches)
    prog = {"loss": []}
    for t in range(FOLLOWED_STEPS):
        state, last = trainer.fit(state, feed_of(prog_batches[t:t + 1], 1))
        prog["loss"].append(float(last["loss"]))
        if t == 0:
            prog["grad"] = system_lib.first_grad_norms(system, state)
    dense3 = jax.device_get(system_lib.dense_leaves(state.params))
    rows3 = system_lib.pull_rows(system, state.emb, prog_batches)
    prog["delta"] = {k: _norm(dense3[k] - dense0[k]) for k in dense0}
    for table in rows0:
        dim = rows0[table].shape[-1]
        moved = rows3[table].reshape(-1, dim)[first] \
            - rows0[table].reshape(-1, dim)[first]
        prog["delta"][table] = _norm(moved)
    return state, prog


def run(cell, config, traffic, inputs, *, seed, seconds, trace, t_process,
        on_device):
    """One run of a training cell; returns the result line as a dict (and
    a ``context`` for the per-layer readers under ``"_context"``)."""
    counter = CompileCounter()

    def mark(phase):
        print(json.dumps({"set_up": phase, "at_s": round(
            time.perf_counter() - t_process, 2),
            "programs": counter.count}), flush=True)

    mark("imports")
    system = system_lib.build(config)
    trainer = system.trainer

    state = system_lib.initial_state(system, seed, on_device)
    mark("tables")
    raw_pool = inputs.result()
    pool = [system_lib.program_batch(system, b) for b in raw_pool]
    mark("pool")
    probe = jax.jit(lambda x: x + 1)
    lag = trainer.pipeline_depth + 1

    def feed_of(batches, steps=None, **kw):
        return Feed(batches, probe, lag=lag,
                    in_flight=traffic["steps_in_flight"], steps=steps, **kw)

    raw_first = raw_pool[:FOLLOWED_STEPS]
    state, prog = _followed(system, trainer, state, feed_of, raw_first,
                            pool[:FOLLOWED_STEPS])

    def to_the_end(state, last):
        jax.block_until_ready(state)
        return float(last["loss"])

    mark("followed")
    state, last = trainer.fit(state, feed_of(pool[FOLLOWED_STEPS:],
                                             traffic["warmup_steps"]))
    to_the_end(state, last)
    mark("warm")

    from openembedding_tpu.utils import observability
    trace_dir = os.path.join(OUT_DIR, f"{cell}.{seed}.trace")
    at_seconds = None
    if trace:
        # the trace covers the end of the window and stops once the window
        # has closed, so that only its start falls inside
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        at_seconds = (
            max(seconds - traffic["trace_seconds"], 0.0),
            lambda: jax.profiler.start_trace(trace_dir,
                                             profiler_options=options))

    gc.collect()
    gc.freeze()
    at_start = {}

    def on_start():
        at_start["stall"] = observability.GLOBAL.snapshot().get(
            "ingest_stall", {})
        at_start["compiles"] = counter.count

    feed = feed_of(pool, seconds=seconds, lead_in=traffic["lead_in_steps"],
                   on_start=on_start, at_seconds=at_seconds)
    with jax.profiler.TraceAnnotation("benchmark.fit"):
        state, last = trainer.fit(state, feed)
    with jax.profiler.TraceAnnotation("benchmark.drain"):
        to_the_end(state, last)
    t_end = time.perf_counter()
    gc.unfreeze()
    trace = trace and feed.called_at is not None
    if trace:
        jax.profiler.stop_trace()
    t_start = feed.started
    compiles = counter.count - at_start["compiles"]
    stall0 = at_start["stall"]
    stall1 = observability.GLOBAL.snapshot().get("ingest_stall", {})
    window_s = t_end - t_start
    steps = feed.handed - traffic["lead_in_steps"]
    done_s = [t - t_start for t in feed.done[traffic["lead_in_steps"]:]]
    print(json.dumps({"window_s": window_s, "steps": steps,
                      "train_compiles_in_window": compiles}), flush=True)

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell}.{seed}.steps.json"), "w") as f:
        json.dump({"cell": cell, "seed": seed, "window_s": window_s,
                   "steps": steps, "waited_s": feed.waited_s,
                   "step_done_s": done_s}, f)

    peaks = [d.memory_stats() for d in system.mesh.devices.flat]
    memory_peak = max((p or {}).get("peak_bytes_in_use", 0) for p in peaks)
    failures = system_lib.insert_failures(system, state.emb)
    step_hlo = None
    if trace:       # kept beside the trace: it names the trace's operations
        step_hlo = system_lib.step_hlo(system, state, pool[0])
        with open(os.path.join(OUT_DIR, f"{cell}.{seed}.step.hlo.txt"),
                  "w") as f:
            f.write(step_hlo)
    del state, last, pool                      # the tables leave the device
    ref = reference.follow(seed, config, raw_first)
    values, where = correct.numbers(prog, ref)
    ok, compared = correct.decide(
        values, config["limits"], extra=[("insert_failures", failures, 0)])
    print(json.dumps({"compared_at": where, "program": prog,
                      "reference": ref}), flush=True)

    end_to_end = {
        "examples_per_s": {
            "value": steps * config["batch"] / window_s if on_device
            else None, "unit": "examples/s"},
        "setup_s": {"value": t_start - t_process if on_device else None,
                    "unit": "s"},
    }
    context = {
        "cell": cell, "config": config, "traffic": traffic,
        "on_device": on_device, "window_s": window_s, "steps": steps,
        "waited_s": feed.waited_s,
        "device_kind": jax.devices()[0].device_kind,
        "step_done_s": done_s,
        "compiles_in_window": compiles,
        "ingest_stall_s": stall1.get("seconds", 0.0)
        - stall0.get("seconds", 0.0) - feed.waited_s,
        "memory_peak_bytes": memory_peak,
        "raw_window_batches": [raw_pool[i % len(raw_pool)]
                               for i in range(min(steps, len(raw_pool)))],
        "trace_dir": trace_dir if trace else None, "step_hlo": step_hlo,
        "feed_call_at_s": feed.called_at,
    }
    return {"correct": ok, "attempted": steps, "failed": failures,
            "metrics": end_to_end, "memory_peak_bytes": memory_peak,
            "compared": compared, "_context": context}
