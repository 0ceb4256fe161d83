"""Gradient compression for the cross-pod (DCI) data-parallel axis.

The pod axis all-reduce crosses the slowest links in the system
(data-center interconnect, ~10x slower than ICI).  ``quantized_psum``
replaces the fp32 all-reduce with int8 block-quantized all-gather +
local reduction: 4x less DCI traffic per direction, with per-tensor fp32
scales so the quantization error is bounded by max|g|/127 per element
(empirically <1% relative on gradient norms — verified in
tests/test_distributed.py).

Usage: inside a ``shard_map`` over the pod axis,
    g = quantized_psum(g_local, 'pod') / n_pods
or wrap a whole gradient pytree with ``quantized_psum_tree``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def quantized_psum(x, axis_name: str):
    """Sum ``x`` over ``axis_name`` with int8 on-the-wire representation."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    qs = jax.lax.all_gather(q, axis_name)            # int8 on the wire
    ss = jax.lax.all_gather(scale, axis_name)
    return jnp.tensordot(ss.astype(jnp.float32),
                         qs.astype(jnp.float32), axes=([0], [0]))


def quantized_psum_tree(tree, axis_name: str):
    return jax.tree.map(lambda g: quantized_psum(g, axis_name), tree)


def make_dp_compressed_grad(loss_fn, mesh, axis: str = 'pod'):
    """Data-parallel gradient with compressed cross-pod reduction.

    loss_fn(params, batch) -> scalar.  Params replicated over ``axis``;
    batch sharded over ``axis`` on dim 0.  Returns (loss_mean, grads_mean)
    with the gradient reduction quantized to int8 over ``axis``.
    """
    from functools import partial
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(), P(axis)),
             out_specs=(P(), P()),
             check_vma=False)
    def fn(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        grads = jax.tree.map(
            lambda g: quantized_psum(g, axis) / n, grads)
        return loss, grads

    return fn
