"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the production step function against
ShapeDtypeStruct inputs (no allocation), compiles it for the 16×16 single-pod
mesh and the 2×16×16 multi-pod mesh, prints ``memory_analysis()`` (proves the
cell fits HBM) and ``cost_analysis()`` (FLOPs/bytes for §Roofline), parses
per-device collective payload bytes out of the partitioned HLO, and writes a
JSON artifact per cell to ``experiments/dryrun/``.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all          # every applicable cell
"""
import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, cell_applicable, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    BF16_OPT, input_specs, model_flops, train_microbatches,
)
from repro.models.model import forward, loss_fn
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.step import make_train_step

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective payload bytes by op kind, from partitioned HLO."""
    out: dict[str, int] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, op, _ = m.groups()
        b = _shape_bytes(shape_str)
        if op.endswith("-start"):
            op = op[: -len("-start")]
        out[op] = out.get(op, 0) + b
        counts[op] = counts.get(op, 0) + 1
    out["total"] = sum(out.values())
    return {"bytes": out, "counts": counts}


def build_step(arch: str, shape: str, mesh):
    """Returns (fn, args_tuple_of_SDS, donate) for the cell's step function."""
    spec = input_specs(arch, shape, mesh)
    cfg, cell = spec["cfg"], spec["cell"]
    if cell.step == "train":
        fn = make_train_step(
            cfg, mesh, remat=True, fsdp=True,
            microbatches=train_microbatches(arch),
        )
        args = (spec["params"], spec["opt"], spec["batch"],
                jax.ShapeDtypeStruct((), jnp.int32))
        return fn, args, (0, 1)
    if cell.step == "prefill":
        fn = make_prefill_step(cfg, mesh)
        return fn, (spec["params"], spec["batch"], spec["cache"]), (2,)
    fn = make_decode_step(cfg, mesh)
    args = (spec["params"], spec["batch"], spec["cache"],
            jax.ShapeDtypeStruct((), jnp.int32))
    return fn, args, (2,)


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str, *, verbose=True):
    ok, why = cell_applicable(arch, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind}
    if not ok:
        rec.update(status="skipped", reason=why)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json"), "w") as f:
                json.dump(rec, f, indent=1)
        print(f"[dryrun] {arch} × {shape} × {mesh_kind}: skipped ({why})", flush=True)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cell = SHAPES[shape]
    cfg = get_config(arch)
    t0 = time.perf_counter()
    try:
        with jax.set_mesh(mesh):  # ambient mesh: activation constraints resolve
            fn, args, donate = build_step(arch, shape, mesh)
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)
        cost = compiled.cost_analysis()
        print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
        # loop-aware re-analysis: XLA's cost_analysis counts while bodies once;
        # hlo_cost multiplies through known_trip_count (see repro.launch.hlo_cost)
        from repro.launch.hlo_cost import analyze

        hc = analyze(compiled.as_text())
        n_dev = int(mesh.devices.size)
        rec.update(
            status="ok",
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_per_device=float(hc["flops"]),
            bytes_per_device=float(hc["dot_bytes"]),
            collectives={"bytes": hc["collective_bytes"],
                         "counts": hc["collective_counts"]},
            raw_cost={"flops": float(cost.get("flops", -1.0)),
                      "bytes_accessed": float(cost.get("bytes accessed", -1.0))},
            model_flops=model_flops(cfg, cell.seq_len, cell.global_batch, cell.step),
            bf16_opt=cfg.name in BF16_OPT,
            memory={
                k: int(getattr(mem, k))
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes", "alias_size_in_bytes")
                if hasattr(mem, k)
            },
        )
    except Exception as e:  # a failing cell is a bug — record it loudly
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        msg = rec["status"]
        if msg == "ok":
            msg += (f" lower {rec['lower_s']}s compile {rec['compile_s']}s "
                    f"flops/dev {rec['flops_per_device']:.3e} "
                    f"coll/dev {rec['collectives']['bytes']['total']:.3e}B")
        elif msg == "error":
            msg += " " + rec["error"]
        print(f"[dryrun] {arch} × {shape} × {mesh_kind}: {msg}", flush=True)
    return rec


def main() -> None:
    # before the first device query: jax fixes the device count when its
    # backend starts (set here, not at import, so importing this module's
    # helpers leaves the importer's devices alone)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s, m) for a in ARCH_IDS for s in SHAPES for m in meshes]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, m) for m in meshes]
    failed = 0
    for a, s, m in cells:
        rec = run_cell(a, s, m, args.out)
        failed += rec["status"] == "error"
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
