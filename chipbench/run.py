"""The chip benchmark's entry point.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, in
one process: it refuses anything but enough TPU chips, keeps JAX's
persistent compilation cache in ``<checkout>/.jax_cache``, makes the
cell's tables from the seed and loads them into a ``Session``, warms up with
one pass of the traffic, measures for ``--seconds``, checks every result
against the reference, and prints one JSON line last on standard
output. ``--trace 1`` measures the per-layer metrics from a profiler
trace of the window instead of the end-to-end ones.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the cache lives in the checkout at a fixed path; JAX reads the
    # variable when it is imported, and the program's own switch takes it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from chipbench import harness
    from repro.launch.compile_cache import enable_compile_cache

    parts = harness.resolve(args.workload, ROOT)
    devices = harness.require_tpu(parts["cell"]["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = harness.run_cell(parts, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS, devices,
                            log=lambda s: print(s, flush=True))
    for name, n in line["checks"].items():
        print(f"check {name}: {n['value']!r} (limit {n['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
