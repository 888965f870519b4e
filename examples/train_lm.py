"""End-to-end training driver: ~100M-param llama-style model, synthetic data.

Exercises the full substrate on one host: model init -> sharded train step
(remat, AdamW, cosine LR) -> checkpoint/resume -> loss curve. The same loop
scales to the production mesh via --production-mesh on a pod.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
(a CPU step at this size takes seconds; use --steps 10 for a smoke run)
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_reduced
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import init_params, param_count
from repro.train.optim import adamw_init
from repro.train.step import make_train_step


def config_100m():
    return dataclasses.replace(
        get_reduced("llama3.2-1b"),
        n_layers=10, d_model=768, n_heads=12, n_kv=6, head_dim=64,
        d_ff=3072, vocab=32000,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = config_100m()
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(0))
        print(f"model: {param_count(params)/1e6:.1f}M params")
        opt = adamw_init(params)
        data = SyntheticLM(cfg, args.global_batch, args.seq_len)
        step = make_train_step(
            cfg, mesh, peak_lr=3e-4, warmup=20, total_steps=args.steps,
            example_params=params, example_opt=opt, example_batch=data.batch(0),
        )
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.ckpt_dir)
        start = 0
        if (last := mgr.latest_step()) is not None:
            params, opt, man = mgr.restore(last, params, opt)
            start = man["step"] + 1
            print(f"resumed from step {last}")
        import time

        for s in range(start, args.steps):
            t0 = time.perf_counter()
            params, opt, metr = step(params, opt, data.batch(s), np.int32(s))
            if s % 10 == 0 or s == args.steps - 1:
                print(f"step {s:4d}  loss {float(metr['loss']):.4f}  "
                      f"lr {float(metr['lr']):.2e}  {time.perf_counter()-t0:.2f}s")
            if (s + 1) % 50 == 0:
                mgr.save(s, params, opt, {"arch": "train_lm_100m"})


if __name__ == "__main__":
    main()
