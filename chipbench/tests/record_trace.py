"""Record the small chip trace that test_chipbench_trace.py reads.

    python3 chipbench/tests/record_trace.py <out.xplane.pb>

On a TPU: TPC-H SF 0.01 through ``Session``, warmed up, then q6 and q12
once each under the profiler with the benchmark's probes installed (so
the host plane carries its annotations). Prints the reduction as JSON.
"""
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from chipbench import harness, trace
    from chipbench.probes import Probes
    from repro.core.session import Session

    harness.require_tpu(1)
    sess = Session(sf=0.01, target_bytes=64 << 20, seed=3, compute_scale=0)
    for q in ("q6", "q12"):
        sess.submit(q)
    probes = Probes().install()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    for q in ("q6", "q12"):
        sess.submit(q)
    jax.profiler.stop_trace()
    probes.uninstall()
    src = trace.find_xplane(tdir)
    shutil.copy(src, out)
    shutil.rmtree(tdir)
    print(json.dumps(trace.reduce(out)))
    print(f"{os.path.getsize(out)} bytes; probes {dict(probes.calls)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
