"""Batched serving: prefill a request batch, then greedy-decode new tokens.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch gemma2-2b --new-tokens 32
Uses the reduced config on CPU; the same engine lowers at full config in the
dry-run (decode_32k / long_500k cells).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.launch.mesh import make_host_mesh
from repro.models import init_cache, init_params
from repro.serve.engine import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        B, P, T = args.batch, args.prompt_len, args.new_tokens
        max_seq = P + T
        cache = init_cache(cfg, B, max_seq)
        prompts = jax.random.randint(key, (B, P), 0, cfg.vocab)
        batch = {"tokens": prompts}

        prefill = make_prefill_step(cfg, mesh, example_params=params,
                                    example_cache=cache, example_batch=batch)
        logits, cache = prefill(params, batch, cache)
        next_tok = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1).astype(jnp.int32)

        dec_batch = {"tokens": next_tok[:, None]}
        decode = make_decode_step(cfg, mesh, example_params=params,
                                  example_cache=cache, example_batch=dec_batch)
        out = [next_tok]
        t0 = time.perf_counter()
        for t in range(T - 1):
            next_tok, cache = decode(params, {"tokens": next_tok[:, None]},
                                     cache, jnp.int32(P + t))
            out.append(next_tok)
        dt = time.perf_counter() - t0
        toks = jnp.stack(out, axis=1)
        print(f"{args.arch}: decoded {toks.shape} in {dt:.2f}s "
              f"({B*(T-1)/max(dt,1e-9):.1f} tok/s)")
        print("first sequence:", toks[0, :16].tolist())


if __name__ == "__main__":
    main()
