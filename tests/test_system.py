"""End-to-end behaviour tests for the paper's system."""
import numpy as np
import pytest

from repro import compat
from repro.api import SpTRSVContext
from repro.core import SolverConfig
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


def _mesh1():
    import jax

    return compat.make_mesh((1,), ("x",))


def test_paper_pipeline_analyse_plan_solve():
    """The paper's workflow: load CSC -> in-degree analysis -> solve 100x."""
    from repro.core import DistributedSolver, build_plan
    from repro.sparse.matrix import csr_to_csc, csc_to_csr

    a = suite.random_levelled(500, 16, 3.5, seed=9)
    csc = csr_to_csc(a)  # the paper's input format
    csc.validate()
    a2 = csc_to_csr(csc)
    plan = build_plan(a2, 1, SolverConfig(block_size=16))
    solver = DistributedSolver(plan, _mesh1())
    rng = np.random.default_rng(0)
    for _ in range(3):
        b = rng.uniform(-1, 1, a.n)
        np.testing.assert_allclose(
            solver.solve(b), reference_solve(a, b), rtol=2e-4, atol=2e-4
        )


def test_table1_suite_solves_end_to_end():
    """Every Table-I matrix class solves correctly under the zero-copy config."""
    rng = np.random.default_rng(1)
    for entry in suite.table1_suite(scale=0.02):
        a = entry.build()
        b = rng.uniform(-1, 1, a.n)
        ctx = SpTRSVContext(mesh=_mesh1(), options=SolverConfig(
            block_size=16, partition="taskpool"))
        x = ctx.solve(ctx.analyse(a), b)
        err = np.abs(x - reference_solve(a, b)).max() / max(1e-9, np.abs(x).max())
        assert err < 1e-4, (entry.name, err)


def test_end_to_end_training_loss_decreases():
    """~100M-class example config, a handful of steps, loss must trend down
    on a learnable synthetic task (repeated batch)."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.models.model import loss_fn
    from repro.train.optim import adamw_init, adamw_update

    cfg = dataclasses.replace(get_reduced("llama3.2-1b"), dtype="float32",
                              param_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (4, 33))
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    @jax.jit
    def step(params, opt):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch["tokens"], batch["labels"], remat=False)
        )(params)
        params, opt, _ = adamw_update(params, g, opt, lr=3e-3, weight_decay=0.0)
        return params, opt, loss

    losses = []
    for _ in range(12):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses  # memorizes the repeated batch


def test_serve_roundtrip():
    from repro.launch.serve import run as serve_run

    toks = serve_run("llama3.2-1b", batch=2, prompt_len=8, new_tokens=4, quiet=True)
    assert toks.shape == (2, 4)
    assert int(toks.max()) < 256  # reduced vocab


def test_dryrun_cell_applicability_count():
    from repro.configs import ARCH_IDS, SHAPES, cell_applicable

    total = len(ARCH_IDS) * len(SHAPES)
    runnable = sum(cell_applicable(a, s)[0] for a in ARCH_IDS for s in SHAPES)
    assert total == 40
    assert runnable == 32  # 30 non-long cells + zamba2/falcon long_500k
