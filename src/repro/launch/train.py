"""Training driver: Hydra model-selection training on a real mesh.

Runs end-to-end on whatever devices exist (CPU/TPU). For multi-device CPU
testing set XLA_FLAGS=--xla_force_host_platform_device_count=8 before launch.

    PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --smoke \
        --trials 4 --steps 20 --n-data 2 --n-model 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import REGISTRY
from repro.core import pipeline as pl
from repro.core.hydra import HydraConfig, run_model_selection
from repro.core.trials import SuccessiveHalving, grid_search
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.layers import ModelOptions


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--trials", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--n-microbatches", type=int, default=4)
    ap.add_argument("--n-data", type=int, default=1)
    ap.add_argument("--n-model", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--asha", action="store_true",
                    help="successive halving instead of full grid")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    n_needed = args.n_data * args.n_model
    if jax.device_count() < n_needed:
        raise SystemExit(
            f"need {n_needed} devices, have {jax.device_count()} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    mesh = make_test_mesh(args.n_data, args.n_model)

    cfg = REGISTRY[args.arch]
    if args.smoke:
        cfg = cfg.reduced()
    opts = ModelOptions(remat=True)
    eng = pl.EngineConfig(
        n_trials=args.trials, n_microbatches=args.n_microbatches,
        microbatch=args.microbatch, n_stages=args.n_model,
        data_size=args.n_data, fsdp=args.fsdp)
    hc = HydraConfig(seq_len=args.seq_len, steps=args.steps,
                     ckpt_dir=args.ckpt_dir)
    lrs = [3e-3 * (0.5 ** i) for i in range(args.trials)]
    trials = grid_search(cfg.name, lrs)[:args.trials]

    t0 = time.time()
    strategy = SuccessiveHalving(base_steps=max(args.steps // 4, 1)) \
        if args.asha else None
    out = run_model_selection(cfg, opts, mesh, hc, trials, eng,
                              strategy=strategy)
    dt = time.time() - t0
    print(json.dumps({
        "best_trial": out["best"].spec.tag,
        "best_val_loss": out["best"].val_loss,
        "results": [{"tag": r.spec.tag, "lr": r.spec.lr,
                     "train_loss": r.train_loss, "val_loss": r.val_loss}
                    for r in out["all"]],
        "wall_s": round(dt, 1),
    }, indent=1))


if __name__ == "__main__":
    main()
