"""Run one cell of the benchmark once.

    python3 -m benchmark.run --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1

A cell is ``<config>.<traffic>``: ``configs/<config>.json`` and
``traffic/<traffic>.json`` are found by name, the traffic's generator in
``traffic_gen/`` and each per-layer metric's reader in ``metrics/`` too, so
a later PR adds cells and metrics as files of their own.

Loads, warms up, measures for ``--seconds``, checks the timed path against
the plain reference and prints the result as the last line of standard
output. A cell needs the TPU chips it names: without them the exit code is
2 and there is no result. Only a configuration marked ``"rehearsal"``
(``configs/tiny_*.json``, in no cell of ``BENCHMARK.json``) runs on the
CPU, and then every timing reads ``null``. ``--dry`` resolves every cell of
``BENCHMARK.json`` to its files without touching JAX.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MAX_SEED = (1 << 32) - 1


def load(kind, name):
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.json")
    with open(path) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def split_cell(cell):
    config, _, traffic = cell.partition(".")
    if not config or not traffic:
        raise SystemExit(f"benchmark: a cell is <config>.<traffic>, got "
                         f"{cell!r}")
    return config, traffic


def metrics_of(bench, section, cell, traffic):
    """Metrics of ``section`` that this cell reports: those that list the
    cell, or list none. A rehearsal cell, in no list, reports what the
    cells of its traffic mix report."""
    cells = {w["name"] for w in bench["workloads"]}
    same_mix = {w["name"] for w in bench["workloads"]
                if w["traffic"] == traffic}
    out = []
    for m in bench[section]:
        listed = m.get("workloads")
        if listed is None or cell in listed or \
                (cell not in cells and same_mix & set(listed)):
            out.append(m)
    return out


def dry():
    """Resolve every cell to its files; no JAX."""
    bench = manifest()
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        config = load("configs", w["config"])
        traffic = load("traffic", w["traffic"])
        if configs[w["config"]]["file"] != \
                f"benchmark/configs/{w['config']}.json":
            raise SystemExit(f"benchmark: {w['config']} names another file")
        if config["chips"] != w["chips"]:
            raise SystemExit(f"benchmark: {w['name']} asks for {w['chips']} "
                             f"chips, its configuration for {config['chips']}")
        for needed in (f"traffic_gen/{traffic['generator']}.py",
                       f"{traffic['kind']}_runner.py"):
            if not os.path.isfile(os.path.join(HERE, needed)):
                raise SystemExit(f"benchmark: {w['name']} needs {needed}")
        for m in metrics_of(bench, "per_layer", w["name"], w["traffic"]):
            if not os.path.isfile(os.path.join(HERE, "metrics",
                                               f"{m['name']}.py")):
                raise SystemExit(f"benchmark: no metrics/{m['name']}.py")
        print(f"{w['name']}: configs/{w['config']}.json "
              f"traffic/{w['traffic']}.json "
              f"traffic_gen/{traffic['generator']}.py "
              f"{traffic['kind']}_runner.py", flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args(argv)
    if args.dry:
        return dry()
    if not args.workload:
        ap.error("--workload is required")
    if not 0 <= args.seed <= MAX_SEED:
        ap.error(f"--seed is a whole number up to {MAX_SEED}")
    config_name, traffic_name = split_cell(args.workload)
    config = load("configs", config_name)
    traffic = load("traffic", traffic_name)
    bench = manifest()
    rehearsal = bool(config.get("rehearsal"))

    # the traffic is drawn on a thread (numpy releases the interpreter
    # lock) while the main thread imports JAX and reaches the chip
    generator = importlib.import_module(
        f"{__package__}.traffic_gen.{traffic['generator']}")
    drawing = concurrent.futures.ThreadPoolExecutor(1)
    inputs = drawing.submit(generator.make, traffic, config, args.seed)
    drawing.shutdown(wait=False)

    if rehearsal:
        # a rehearsal never takes the chip: virtual CPU devices stand in
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if rehearsal:
        jax.config.update("jax_num_cpu_devices", config["chips"])
    try:
        from openembedding_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"benchmark: the system under test is not here: {e}",
              file=sys.stderr)
        return 2
    from . import system as system_lib
    found = system_lib.found_devices()
    on_device = found["platform"] == "tpu"
    if not rehearsal and (not on_device or found["count"] < config["chips"]):
        print(f"benchmark: {args.workload} needs {config['chips']} TPU "
              f"chip(s), JAX found {found}", file=sys.stderr)
        return 2
    if on_device:
        # every program, however small, comes from the cache on a warm run
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        print(json.dumps({"compile_cache": cache_dir}), flush=True)
    print(json.dumps({"cell": args.workload, "seed": args.seed,
                      "device": found}), flush=True)

    runner = importlib.import_module(
        f"{__package__}.{traffic['kind']}_runner")
    result = runner.run(args.workload, config, traffic, inputs,
                        seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t_process=T_PROCESS, on_device=on_device)
    context = result.pop("_context")
    device = {"platform": found["platform"], "kind": found["kind"],
              "count": config["chips"],
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        from . import trace_reduce
        reduced = trace_reduce.reduce_run(context)
        context["trace"] = reduced
        line["metrics"] = {}
        for m in metrics_of(bench, "per_layer", args.workload, traffic_name):
            reader = importlib.import_module(
                f"{__package__}.metrics.{m['name']}")
            value = reader.read(context)
            if value is not None:
                line["metrics"][m["name"]] = {
                    "value": value if on_device or not reader.TIMING
                    else None, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
    else:
        wanted = {m["name"] for m in metrics_of(
            bench, "end_to_end", args.workload, traffic_name)}
        line["metrics"] = {k: v for k, v in result["metrics"].items()
                           if k in wanted}
    line["device"] = device
    line["compared"] = result["compared"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
