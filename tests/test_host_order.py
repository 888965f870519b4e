"""A numpy right-hand side crosses to the device flat, in the caller's order:
the compiled program pads it, reverses it for a transpose plan, lays it out
as the executor's carries and undoes all three on the answer. Its answers
are bitwise those of ``solve_blocks`` on the blocks the host would have
built, and ``pad_rhs``/``unpad_x`` are never called on the way.

The four-device cases run in one subprocess with four virtual CPU devices
(the main test process keeps one)."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import DistributedSolver, SolverConfig, blocking, build_plan
from repro.core.solver import AXIS
from repro.sparse import suite

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(os.path.abspath(__file__))
CASES = [(R, transpose) for R in (1, 8) for transpose in (False, True)]


def compare(n_devices: int, R: int, transpose: bool) -> dict:
    """The numpy path against ``solve_blocks`` on one (R, transpose) case,
    on a matrix whose n is not a multiple of the block size."""
    import jax

    a = suite.random_levelled(300, 10, 4.0, seed=3)
    mesh = compat.make_mesh((n_devices,), (AXIS,),
                            devices=jax.devices()[:n_devices])
    solver = DistributedSolver(build_plan(a, n_devices, SolverConfig(block_size=16),
                                          transpose=transpose), mesh)
    bs = solver.plan.bs
    shape = (a.n,) if R == 1 else (a.n, R)
    b = np.random.default_rng(R).uniform(-1, 1, shape).astype(np.float32)
    blocks = blocking.pad_rhs(b[::-1] if transpose else b, bs)
    want = blocking.unpad_x(np.asarray(solver.solve_blocks(jnp.asarray(blocks))), bs)
    if transpose:
        want = want[::-1]

    def refuse(*args, **kwargs):
        raise AssertionError("DistributedSolver.solve staged on the host")

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(blocking, "pad_rhs", refuse)
        mp.setattr(blocking, "unpad_x", refuse)
        got = solver.solve(b)
    return {"padded": bs.nb * bs.B > a.n, "shape": list(got.shape) == list(shape),
            "bitwise": bool(np.array_equal(got, want))}


@pytest.fixture(scope="module")
def four_devices():
    """Every case on a four-device mesh, from one subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([SRC, TESTS, env.get("PYTHONPATH", "")])
    code = ("import json, test_host_order as t\n"
            "print(json.dumps([t.compare(4, R, tr) for R, tr in t.CASES]))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return dict(zip(CASES, json.loads(out.stdout.strip().splitlines()[-1])))


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("R, transpose", CASES,
                         ids=[f"R{R}-{'transpose' if t else 'forward'}" for R, t in CASES])
def test_numpy_path_matches_solve_blocks_bitwise(request, n_devices, R, transpose):
    if n_devices == 1:
        got = compare(1, R, transpose)
    else:
        got = request.getfixturevalue("four_devices")[R, transpose]
    assert got == {"padded": True, "shape": True, "bitwise": True}
