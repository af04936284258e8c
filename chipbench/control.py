"""Readings for the limits of ``correct``: the program's, over many seeds,
and the control's.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 1 2 3]

For each seed, in one process: the cell's tables are made from the seed
and loaded into a ``Session``, one pass of its traffic runs through the
timed entry, and each result is compared with the reference, as a run
does. The control is the reference itself, put in the program's place
and computed in float32, the precision below the configuration's
float64; it needs no chip. Each reading is one JSON line on standard
output. The benchmark's own runs do not run this.
"""
import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


class _Answer:
    """A reference answer in the shape of a result table."""

    def __init__(self, cols: dict):
        self.cols = cols


def control_records(config: dict, seed: int, queries) -> list[dict]:
    """The float32 reference's answers as a run's records."""
    from chipbench import dbgen, reference
    tables = dbgen.generate(config["scale_factor"], seed)
    return [{"query": q, "start": 0.0, "end": 0.0, "failed": False,
             "result": _Answer(reference.answer(q, tables, np.float32))}
            for q in queries]


def readings(numbers: dict) -> dict:
    return {k: v["value"] for k, v in numbers.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from chipbench import harness

    parts = harness.resolve(args.workload, ROOT)
    config, traffic = parts["config"], parts["traffic"]
    queries = sorted(set(traffic["order"]))
    for seed in args.control_seeds:
        v = harness.check(control_records(config, seed, queries), config,
                          seed)
        print(json.dumps({"who": "control_float32", "seed": seed,
                          "correct": v["correct"],
                          **readings(v["numbers"])}), flush=True)
    if args.seeds:
        from repro.launch.compile_cache import enable_compile_cache
        harness.require_tpu(parts["cell"]["chips"])
        enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        sess = harness.build(config, seed)
        records, _ = harness.drive(sess, traffic, None)
        del sess
        v = harness.check(records, config, seed)
        print(json.dumps({"who": "program", "seed": seed,
                          "correct": v["correct"],
                          "wall_s": time.perf_counter() - t0,
                          **readings(v["numbers"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
