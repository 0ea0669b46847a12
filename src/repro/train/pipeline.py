"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages are laid out over a mesh axis; microbatches stream through the
classic (M + S - 1)-tick schedule. Differentiable end-to-end (ppermute has
a transpose), so the same construct serves training. This is the PP option
of the parallelism suite (DP/TP/EP/SP live in launch.shardings via GSPMD;
PP is explicit because GSPMD cannot infer a schedule).

The dry-run production mesh keeps TP on the "model" axis - PP is most
useful when a pod boundary (the "pod" axis) has thin interconnect; see
README §Parallelism for when to prefer which.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, n_stages: int, mesh: Mesh,
                   axis: str = "pipe") -> Callable:
    """Build pipelined_fn(stage_params, x_microbatches) -> outputs.

    stage_params leaves: (n_stages, ...) - sharded one stage per device
    along ``axis``. x_microbatches: (M, mb, ...) - replicated in, outputs
    (M, mb, ...) replicated out.
    """

    def per_device(params_local, x_all):
        # params_local leaves: (1, ...) local stage slice
        params_stage = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        m = x_all.shape[0]
        n_ticks = m + n_stages - 1
        mb_shape = x_all.shape[1:]
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            state = carry  # activation arriving from the previous stage
            feed = jax.lax.dynamic_index_in_dim(
                x_all, jnp.minimum(t, m - 1), axis=0, keepdims=False
            )
            inp = jnp.where(sid == 0, feed, state)
            out = stage_fn(params_stage, inp)
            nxt = jax.lax.ppermute(out, axis, perm)
            emit = jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out))
            return nxt, emit

        zeros = jnp.zeros(mb_shape, x_all.dtype)
        _, emits = jax.lax.scan(tick, zeros, jnp.arange(n_ticks))
        # outputs for microbatch j leave the last stage at tick j+n_stages-1
        outs = jax.lax.dynamic_slice_in_dim(emits, n_stages - 1, m, axis=0)
        # replicate to every device so the loss is computable anywhere
        outs = jax.lax.psum(outs, axis)
        return outs

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def stack_stages(layer_params, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""
    def r(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])

    return jax.tree.map(r, layer_params)


def scan_stage(layer_fn: Callable) -> Callable:
    """stage_fn that scans layer_fn over the stage's layer slice."""

    def stage(params_stage, x):
        def body(x, p):
            return layer_fn(p, x), None

        x, _ = jax.lax.scan(body, x, params_stage)
        return x

    return stage
