"""Training driver: elastic, fault-tolerant step-task loop over any arch.

CPU-scale usage (full configs need the TPU meshes — use dryrun.py there):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \\
      --steps 16 --steps-per-task 4
"""
from __future__ import annotations

import argparse

from repro.configs.base import get_config
from repro.configs.smoke import smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.objectstore.store import ObjectStore, StoreConfig
from repro.runtime.train_loop import ElasticTrainer, JobConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--steps-per-task", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject one worker failure at this global step")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    bundle = build_model(cfg)
    store = ObjectStore(StoreConfig(seed=0, simulate_visibility_lag=False))
    fails = {args.fail_at: 1} if args.fail_at >= 0 else {}

    def hook(task, step):
        if fails.get(step, 0) > 0:
            fails[step] -= 1
            print(f"[inject] worker failure at step {step}")
            return True
        return False

    job = JobConfig(steps_per_task=args.steps_per_task,
                    total_steps=args.steps, batch=args.batch, seq=args.seq)
    trainer = ElasticTrainer(bundle, store, job, failure_hook=hook)
    log = trainer.run()
    for m in log:
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}")
    print(f"done: {len(log)} committed checkpoints, "
          f"{store.stats.puts} PUTs / {store.stats.gets} GETs to the store")


if __name__ == "__main__":
    main()
