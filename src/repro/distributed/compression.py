"""Gradient compression for the data-parallel all-reduce (int8 + error feedback).

Wire format: per-leaf int8 mantissa + one f32 scale per leaf.  The all-reduce
runs over the int8 payload widened to int32 (sum of n shards of ±127 fits
easily), cutting DP gradient bytes 4× vs f32 / 2× vs bf16.  Quantization
error is fed back into the next step's gradient (error-feedback/EF-SGD), which
keeps convergence — ``tests/test_compression.py`` trains a model both ways
and checks loss parity.

Used by ``make_compressed_dp_step``: a ``shard_map`` data-parallel step with
explicit ``psum`` over the compressed payload — the pattern a 1000-node DP
ring would run; composes with the uncompressed pjit path which stays default.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(g)).astype(jnp.float32) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_tree(grads):
    flat, treedef = jax.tree_util.tree_flatten(grads)
    qs, scales = zip(*[quantize(g) for g in flat]) if flat else ((), ())
    return list(qs), list(scales), treedef


def make_compressed_dp_step(model, opt_cfg, mesh, axis: str = "data"):
    """Pure-DP train step: grads int8-compressed + psum'd inside shard_map."""
    from repro.train import optimizer as opt_mod

    n = 1
    for a, s in zip(mesh.axis_names, mesh.devices.shape):
        if a == axis:
            n = s

    def local_step(params, opt_state, err, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)

        def comm(g, e):
            g32 = g.astype(jnp.float32) + e
            q, scale = quantize(g32)
            summed = jax.lax.psum(q.astype(jnp.int32), axis)
            scale_sum = jax.lax.psum(scale, axis)
            g_hat = summed.astype(jnp.float32) * (scale_sum / n) / n
            new_err = g32 - dequantize(q, scale)  # local quantization residual
            return g_hat.astype(g.dtype), new_err

        pairs = jax.tree_util.tree_map(comm, grads, err)
        g_hat = jax.tree_util.tree_map(lambda t: t[0], pairs,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_err = jax.tree_util.tree_map(lambda t: t[1], pairs,
                                         is_leaf=lambda x: isinstance(x, tuple))
        params, opt_state, om = opt_mod.apply_updates(params, g_hat, opt_state, opt_cfg)
        metrics = dict(metrics)
        metrics.update(om)
        metrics = jax.tree_util.tree_map(lambda m: jax.lax.pmean(m, axis), metrics)
        return params, opt_state, new_err, metrics

    pspec = P()          # params replicated (pure DP)
    bspec = P(axis)      # batch sharded
    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, pspec, pspec, bspec),
        out_specs=(pspec, pspec, pspec, pspec),
        check_vma=False,
    ))


def init_error_state(params):
    return jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
