"""Read the control and the planted faults at a cell's own size.

    python3 -m benchmark.controls <config> <seed> [<seed> ...]

For each seed: the plain reference follows the cell's first batches in
float32, then the control (the reference in bfloat16) and each fault a
training cell can have (``reference.FAULTS``) stand in the program's
place, and the numbers the comparison reads are printed, one JSON line
each. The limits in the configuration file are set between these upper
readings and the program's own (PERF.md); the benchmark's runs never call
this. Needs no table, so one chip serves a four-chip configuration too.
"""

import json
import sys

import jax.numpy as jnp

from . import correct, reference, run
from .traffic_gen import zipf_train


def main(argv):
    config = run.load("configs", argv[0])
    traffic = dict(run.load("traffic", "train_zipf"),
                   pool_batches=3)          # the followed batches only
    for seed in map(int, argv[1:]):
        raw = zipf_train.make(traffic, config, seed)
        ref = reference.follow(seed, config, raw)
        stand_ins = {"bfloat16": dict(dtype=jnp.bfloat16)}
        stand_ins.update({f: dict(fault=f) for f in reference.FAULTS
                          if f != "no_exchange" or config["chips"] > 1})
        for name, how in stand_ins.items():
            values, where = correct.numbers(
                reference.follow(seed, config, raw, **how), ref)
            print(json.dumps({"config": argv[0], "seed": seed,
                              "stand_in": name, **values, "at": where}),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
