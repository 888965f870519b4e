"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Fault-tolerance loop (DESIGN.md §3):
* resume from the last committed checkpoint (``CheckpointManager.latest_step``),
* checkpoint every ``--ckpt-every`` steps with atomic commit,
* per-step wall-time budget -> straggler flag in the heartbeat file,
* step retry: a failed step (device error) reloads the last checkpoint and
  continues — exercised by tests/test_train_loop.py via fault injection,
* elastic: restoring onto a different mesh re-shards automatically.

On this CPU container use ``--reduced`` for a runnable ~seconds/step config;
the full configs are exercised through the dry-run instead.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_cache, init_params, param_count
from repro.train.optim import adamw_init
from repro.train.step import make_train_step


def run(
    arch: str, *, steps: int = 20, reduced: bool = True, global_batch: int = 8,
    seq_len: int = 64, ckpt_dir: str | None = None, ckpt_every: int = 10,
    microbatches: int = 1, step_budget_s: float = 0.0, mesh=None, quiet: bool = False,
    peak_lr: float = 3e-4,
):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    mesh = mesh or make_host_mesh()
    with jax.set_mesh(mesh):  # ambient mesh for activation sharding constraints
        return _run_under_mesh(
            cfg, arch, mesh, steps=steps, global_batch=global_batch,
            seq_len=seq_len, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
            microbatches=microbatches, step_budget_s=step_budget_s,
            quiet=quiet, peak_lr=peak_lr,
        )


def _run_under_mesh(cfg, arch, mesh, *, steps, global_batch, seq_len, ckpt_dir,
                    ckpt_every, microbatches, step_budget_s, quiet, peak_lr):
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params)
    data = SyntheticLM(cfg, global_batch, seq_len)

    step_fn = make_train_step(
        cfg, mesh, microbatches=microbatches, peak_lr=peak_lr,
        example_params=params, example_opt=opt, example_batch=data.batch(0),
        donate=True,
    )

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr is not None and (last := mgr.latest_step()) is not None:
        params, opt, manifest = mgr.restore(last, params, opt)
        start = manifest["step"] + 1
        if not quiet:
            print(f"[train] resumed from step {last}")

    if not quiet:
        print(f"[train] {cfg.name}: {param_count(params):,} params, mesh {dict(mesh.shape)}")
    hb_path = os.path.join(ckpt_dir, "heartbeat.json") if ckpt_dir else None
    losses = []
    for step in range(start, steps):
        t0 = time.perf_counter()
        batch = data.batch(step)
        params, opt, metrics = step_fn(params, opt, batch, np.int32(step))
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        straggler = bool(step_budget_s and dt > step_budget_s)
        if hb_path:
            with open(hb_path, "w") as f:
                json.dump({"step": step, "loss": loss, "sec": dt,
                           "straggler": straggler}, f)
        if not quiet:
            print(f"[train] step {step:4d} loss {loss:.4f} ({dt*1e3:.0f} ms)"
                  + (" STRAGGLER" if straggler else ""))
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step, params, opt, {"arch": arch, "mesh": list(mesh.devices.shape)})
    return losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--full", action="store_true", help="full config (needs a pod)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    mesh = make_production_mesh() if args.production_mesh else None
    run(
        args.arch, steps=args.steps, reduced=not args.full,
        global_batch=args.global_batch, seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        microbatches=args.microbatches, mesh=mesh,
    )


if __name__ == "__main__":
    main()
