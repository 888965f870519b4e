"""Roofline accounting: flops calibration + HLO collective parser."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.roofline import FMA_FACTOR, roofline_row
from repro.launch.dryrun import collective_bytes


def test_xla_cpu_flops_convention():
    """cost_analysis counts 2NMK for a matmul — FMA_FACTOR must match."""
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(lambda x, y: x @ y).lower(a, a).compile()
    flops = c.cost_analysis()["flops"]
    assert abs(flops * FMA_FACTOR - 2 * 256**3) / (2 * 256**3) < 0.05


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %all-reduce.1 = f32[1024,512]{1,0} all-reduce(%x), replica_groups={}
  %ag = bf16[2048]{0} all-gather(%y), dimensions={0}
  %rs.5 = (f32[64,64]{1,0}, f32[64,64]{1,0}) reduce-scatter(%a, %b), dims={0}
  %cp = u32[16]{0} collective-permute-start(%c), pairs={{0,1}}
  %notacoll = f32[8,8]{1,0} add(%p, %q)
"""
    out = collective_bytes(hlo)
    b = out["bytes"]
    assert b["all-reduce"] == 1024 * 512 * 4
    assert b["all-gather"] == 2048 * 2
    assert b["reduce-scatter"] == 2 * 64 * 64 * 4
    assert b["collective-permute"] == 16 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")


def test_roofline_row_math():
    ag = 50e9 / 4  # payload; wire = payload * 15/16 for all-gather
    rec = {
        "status": "ok", "arch": "a", "shape": "s", "mesh": "single",
        "n_devices": 256,
        "flops_per_device": 197e12,  # exactly 1s of compute
        "bytes_per_device": 819e9 / 2,  # 0.5s of HBM
        "collectives": {"bytes": {"all-gather": ag, "total": ag}},
        "model_flops": 197e12 * 256 * FMA_FACTOR * 0.5,
        "memory": {"temp_size_in_bytes": 0},
    }
    r = roofline_row(rec)
    assert r["bottleneck"] == "compute"
    assert abs(r["compute_s"] - 1.0) < 1e-6
    assert abs(r["memory_s"] - 0.5) < 1e-6
    assert abs(r["collective_s"] - 0.25 * 15 / 16) < 1e-6
    assert abs(r["useful_flops_ratio"] - 0.5) < 1e-6
    assert r["roofline_fraction"] == 1.0


def test_wire_bytes_factors():
    from benchmarks.roofline import wire_bytes

    coll = {"all-reduce": 16.0, "all-gather": 16.0, "total": 32.0}
    # AR: 2*(15/16)*16 = 30; AG: (15/16)*16 = 15
    assert abs(wire_bytes(coll, ring=16) - 45.0) < 1e-9
