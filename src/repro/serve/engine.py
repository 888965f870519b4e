"""Serving steps: batched prefill and single-token decode with sharded caches.

KV caches shard batch over DP and the cache sequence dim over the model axis
(decode sequence-parallelism); SSM states shard channels over model — see
``repro.distributed.sharding.cache_specs``. Greedy sampling keeps the step
deterministic; the launcher wraps these into a request loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.meshutil import dp_axes as _dp_axes
from repro.distributed.sharding import batch_specs, cache_specs, param_specs
from repro.models.config import ModelConfig
from repro.models.layers import vocab_pad_mask
from repro.models.model import forward


def _shard(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def make_prefill_step(cfg: ModelConfig, mesh, *, example_params=None,
                      example_cache=None, example_batch=None, fsdp: bool = False):
    dp = _dp_axes(mesh)

    def prefill(params, batch, cache):
        with jax.named_scope("serve.prefill"):
            logits, cache = forward(
                params, cfg, batch.get("tokens"), embeds=batch.get("embeds"),
                cache=cache, pos_offset=0, enc_out=batch.get("enc_out"),
                last_only=True,
            )
        return logits, cache

    if example_params is None:
        return prefill
    pspecs = _shard(mesh, param_specs(example_params, mesh, fsdp_axes=dp if fsdp else ()))
    cspecs = _shard(mesh, cache_specs(example_cache, mesh, dp_axes=dp))
    bspecs = _shard(mesh, batch_specs(example_batch, mesh, dp_axes=dp))
    jitted = jax.jit(
        prefill,
        in_shardings=(pspecs, bspecs, cspecs),
        out_shardings=(_shard(mesh, P(dp if len(dp) > 1 else dp[0], None, None)), cspecs),
        donate_argnums=(2,),
    )

    def stepper(params, batch, cache):
        return jitted(jax.device_put(params, pspecs),
                      jax.device_put(batch, bspecs),
                      jax.device_put(cache, cspecs))

    return stepper


def make_decode_step(cfg: ModelConfig, mesh, *, example_params=None,
                     example_cache=None, example_batch=None, fsdp: bool = False):
    """One token for every sequence in the batch; greedy argmax sampling."""
    dp = _dp_axes(mesh)

    def decode(params, batch, cache, pos):
        with jax.named_scope("serve.decode"):
            logits, cache = forward(
                params, cfg, batch.get("tokens"), embeds=batch.get("embeds"),
                cache=cache, pos_offset=pos,
            )
            logits = vocab_pad_mask(logits[:, -1].astype(jnp.float32), cfg.vocab)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    if example_params is None:
        return decode
    pspecs = _shard(mesh, param_specs(example_params, mesh, fsdp_axes=dp if fsdp else ()))
    cspecs = _shard(mesh, cache_specs(example_cache, mesh, dp_axes=dp))
    bspecs = _shard(mesh, batch_specs(example_batch, mesh, dp_axes=dp))
    jitted = jax.jit(
        decode,
        in_shardings=(pspecs, bspecs, cspecs, NamedSharding(mesh, P())),
        out_shardings=(None, cspecs),
        donate_argnums=(2,),
    )

    def stepper(params, batch, cache, pos):
        return jitted(jax.device_put(params, pspecs),
                      jax.device_put(batch, bspecs),
                      jax.device_put(cache, cspecs), pos)

    return stepper
