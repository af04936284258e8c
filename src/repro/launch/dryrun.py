"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this
  * builds the abstract train/prefill/decode step with production shardings,
  * ``.lower().compile()``s it for the target mesh (no allocation),
  * records ``memory_analysis()`` / ``cost_analysis()`` and the collective
    traffic parsed from the optimized HLO,
  * writes one JSON artifact per cell under benchmarks/artifacts/dryrun/.

The roofline analysis (benchmarks/roofline.py, EXPERIMENTS.md §Roofline)
reads these artifacts. Failures here are sharding bugs in the system.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import os
import pathlib
import time
import traceback

import jax

from repro.configs.base import SHAPES, ShapeConfig, cells, get_config, registry
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import make_mesh_by_name, mesh_chips
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models.model import build_model
from repro.models.modules import abstract_params, param_count
from repro.parallel.sharding import param_shardings
from repro.runtime.optimizer import make_optimizer

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / \
    "artifacts" / "dryrun"

# --- TPU v5e hardware constants (roofline targets; see EXPERIMENTS.md) ---
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (per-device aggregate modeled
                             # as one link per exchanged byte-stream)
HBM_PER_CHIP = 16e9          # v5e HBM capacity


def abstract_tree(defs, mesh, rules=None):
    sh = param_shardings(defs, mesh, rules)
    return abstract_params(defs, sh)


# ---------------------------------------------------------------------------
# model flops (6*N*D with N = active non-embedding params)
# ---------------------------------------------------------------------------

def active_params(cfg, defs) -> int:
    total = param_count(defs)
    emb = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n = total - emb
    if cfg.moe is not None:
        m = cfg.moe
        n_moe_layers = (cfg.num_layers - m.first_dense) if m.every_k_layers == 1 \
            else cfg.num_layers // m.every_k_layers
        expert_p = 3 * cfg.d_model * m.expert_d_ff
        routed_total = n_moe_layers * m.num_experts * expert_p
        routed_active = n_moe_layers * m.top_k * expert_p
        n = n - routed_total + routed_active
    return max(n, 0)


def model_flops(cfg, defs, shape: ShapeConfig) -> float:
    n = active_params(cfg, defs)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token / seq


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def _apply_variant(cfg, variant: str | None):
    """--variant k=v[,k=v...]: cfg.replace overrides for perf iterations."""
    if not variant:
        return cfg
    kw = {}
    for item in variant.split(","):
        k, v = item.split("=")
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            kw[k] = v.lower() in ("1", "true")
        elif isinstance(cur, int):
            kw[k] = int(v)
        elif isinstance(cur, float):
            kw[k] = float(v)
        else:
            kw[k] = v
    return cfg.replace(**kw)


def lower_cell(arch: str, shape_name: str, mesh, variant: str | None = None):
    """Build + lower + compile one cell. Returns (lowered, compiled, meta)."""
    from repro.parallel.sharding import effective_rules
    cfg = _apply_variant(get_config(arch), variant)
    shape = SHAPES[shape_name]
    rules = effective_rules(cfg)
    bundle = build_model(cfg, mesh=mesh, rules=rules)
    long = shape.seq_len >= 2 ** 19

    if shape.kind == "train":
        opt = make_optimizer(cfg.optimizer)
        step_fn, state_defs = make_train_step(bundle, opt)
        state_sh = param_shardings(state_defs, mesh, rules)
        state = abstract_params(state_defs, state_sh)
        batch = abstract_tree(bundle.batch_defs(shape), mesh, rules)
        # pin output state shardings: forces GSPMD to keep weight grads in
        # the parameter layout (reduce-scatter instead of all-reduce + slice)
        lowered = jax.jit(step_fn, donate_argnums=(0,),
                          out_shardings=(state_sh, None)).lower(state, batch)
    elif shape.kind == "prefill":
        step_fn = make_prefill_step(bundle)
        params = abstract_tree(bundle.param_defs, mesh, rules)
        batch = abstract_tree(bundle.batch_defs(shape), mesh, rules)
        lowered = jax.jit(step_fn).lower(params, batch)
    else:
        step_fn = make_decode_step(bundle)
        params = abstract_tree(bundle.param_defs, mesh, rules)
        cache_defs = bundle.cache_defs(shape.global_batch, shape.seq_len, long)
        cache_sh = param_shardings(cache_defs, mesh, rules)
        cache = abstract_params(cache_defs, cache_sh)
        batch = abstract_tree(bundle.batch_defs(shape), mesh, rules)
        lowered = jax.jit(step_fn, donate_argnums=(1,),
                          out_shardings=(None, cache_sh)).lower(
            params, cache, batch)
    compiled = lowered.compile()
    return lowered, compiled, {"cfg": cfg, "bundle": bundle, "shape": shape}


def run_cell(arch: str, shape_name: str, mesh_name: str,
             save_hlo: bool = False, variant: str | None = None) -> dict:
    t0 = time.time()
    mesh = make_mesh_by_name(mesh_name)
    chips = mesh_chips(mesh)
    lowered, compiled, meta = lower_cell(arch, shape_name, mesh, variant)
    cfg, bundle, shape = meta["cfg"], meta["bundle"], meta["shape"]

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # xla cpu cost_analysis counts while bodies ONCE (see hlo_analysis.py):
    # use the trip-count-corrected static analysis for flops + collectives,
    # and record the raw cost_analysis numbers alongside.
    ha = hlo_analyze(hlo, chips)
    colls = {"bytes_by_kind": ha["collective_bytes_by_kind"],
             "count_by_kind": ha["collective_count_by_kind"],
             "total_bytes": ha["collective_total_bytes"]}

    flops_dev = float(ha["flops"])
    raw_flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ha["memory_bytes"])
    raw_bytes_dev = float(ca.get("bytes accessed", 0.0))
    mf = model_flops(cfg, bundle.param_defs, shape)

    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = colls["total_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "wall_compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
            "hbm_per_chip": HBM_PER_CHIP,
            "fits": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                    < HBM_PER_CHIP,
            # CPU backend upcasts bf16 collectives to f32 (2x buffers) and
            # skips the AR->RS rewrite TPU gets; corrected = raw - 0.5 * the
            # largest f32 collective tuple (the dtype half of the artifact).
            "f32_collective_peak_buffer_bytes":
                ha["f32_collective_peak_buffer_bytes"],
            "tpu_corrected_peak_bytes":
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes
                 - ha["f32_collective_peak_buffer_bytes"] // 2),
            "fits_tpu_corrected":
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes
                 - ha["f32_collective_peak_buffer_bytes"] // 2)
                < HBM_PER_CHIP,
        },
        "cost": {"flops_per_device": flops_dev,
                 "raw_cost_analysis_flops": raw_flops_dev,
                 "bytes_per_device": bytes_dev,
                 "raw_cost_analysis_bytes": raw_bytes_dev},
        "collectives": colls,
        "roofline": {
            **terms,
            "dominant": dominant,
            "model_flops_total": mf,
            "model_flops_per_device": mf / chips,
            "useful_flops_ratio": (mf / chips) / flops_dev if flops_dev else 0.0,
            "step_time_lower_bound_s": max(terms.values()),
        },
        "params_total": param_count(bundle.param_defs),
        "params_active": active_params(cfg, bundle.param_defs),
    }
    rec["variant"] = variant or ""
    tag = f"{arch}__{shape_name}__{mesh_name}" + (
        f"__{variant.replace('=', '-').replace(',', '_')}" if variant else "")
    ART_DIR.mkdir(parents=True, exist_ok=True)
    (ART_DIR / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    if save_hlo:
        (ART_DIR / f"{tag}.hlo.txt").write_text(hlo)
    return rec


def main():
    # 512 placeholder host devices back both production meshes (16x16
    # single pod, 2x16x16 multi-pod). XLA reads the flag when the backend
    # starts, at the first device query, which comes after this line.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="cfg overrides k=v[,k=v] for perf iterations")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        targets = [(a, s) for a in sorted(registry()) for s in cells(a)]
    else:
        assert args.arch, "--arch or --all required"
        shapes = [args.shape] if args.shape else cells(args.arch)
        targets = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape in targets:
        for mesh_name in meshes:
            tag = f"{arch} x {shape} x {mesh_name}"
            out = ART_DIR / f"{arch}__{shape}__{mesh_name}.json"
            if args.skip_existing and out.exists():
                print(f"[skip] {tag}")
                continue
            try:
                rec = run_cell(arch, shape, mesh_name, save_hlo=args.save_hlo,
                               variant=args.variant)
                r = rec["roofline"]
                print(f"[ ok ] {tag}: compile {rec['wall_compile_s']}s "
                      f"mem/dev {rec['memory']['peak_estimate_bytes']/1e9:.2f}GB "
                      f"fits={rec['memory']['fits']} "
                      f"compute {r['compute_s']*1e3:.2f}ms "
                      f"memory {r['memory_s']*1e3:.2f}ms "
                      f"coll {r['collective_s']*1e3:.2f}ms "
                      f"dominant={r['dominant']} "
                      f"useful={r['useful_flops_ratio']:.2f}", flush=True)
            except Exception as e:  # noqa: BLE001 — report all cell failures
                failures.append((tag, repr(e)))
                print(f"[FAIL] {tag}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
