"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Batched prefill + greedy decode on the reduced config (CPU-runnable); the
full configs exercise the same engine through the dry-run decode cells.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.launch.mesh import make_host_mesh
from repro.models import init_cache, init_params
from repro.serve.engine import make_decode_step, make_prefill_step


def run(arch: str, *, batch: int = 4, prompt_len: int = 32, new_tokens: int = 16,
        mesh=None, quiet: bool = False):
    cfg = get_reduced(arch)
    mesh = mesh or make_host_mesh()
    with jax.set_mesh(mesh):
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        max_seq = prompt_len + new_tokens
        cache = init_cache(cfg, batch, max_seq)
        prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
        pf_batch = {"tokens": prompts}
        prefill = make_prefill_step(cfg, mesh, example_params=params,
                                    example_cache=cache, example_batch=pf_batch)
        logits, cache = prefill(params, pf_batch, cache)
        next_tok = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1).astype(jnp.int32)
        dec_batch = {"tokens": next_tok[:, None]}
        decode = make_decode_step(cfg, mesh, example_params=params,
                                  example_cache=cache, example_batch=dec_batch)
        out = [next_tok]
        t0 = time.perf_counter()
        for t in range(new_tokens - 1):
            next_tok, cache = decode(params, {"tokens": next_tok[:, None]},
                                     cache, jnp.int32(prompt_len + t))
            out.append(next_tok)
        dt = time.perf_counter() - t0
        toks = jnp.stack(out, axis=1)
        if not quiet:
            print(f"[serve] {arch}: {toks.shape} tokens in {dt:.2f}s "
                  f"({batch*(new_tokens-1)/max(dt,1e-9):.1f} tok/s)")
        return toks


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    run(args.arch, batch=args.batch, prompt_len=args.prompt_len,
        new_tokens=args.new_tokens)


if __name__ == "__main__":
    main()
