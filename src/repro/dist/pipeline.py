"""Pipeline parallelism over a mesh axis (GPipe-style skewed schedule).

All stages execute the same tick in lockstep over a stage-stacked buffer:
stage ``s`` processes microbatch ``t - s`` at tick ``t``.  The stage dim of
the buffer is sharded on the pipeline mesh axis, so the per-tick
``vmap(stage_fn)`` is one SPMD program whose collectives are the
stage-to-stage shifts (a collective-permute under the hood) — the standard
TPU pipelining formulation.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """(B, ...) -> (n_micro, B // n_micro, ...)."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def pipeline_apply(
    mesh,
    axis: Optional[str],
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_weights: jax.Array,     # (n_stages, ...) stacked per-stage params
    xm: jax.Array,                # (n_micro, mb, ...) microbatched input
) -> jax.Array:
    """Run every microbatch through all stages; returns (n_micro, mb, ...).

    ``stage_fn(w, x) -> y`` must be shape-preserving (uniform stage width),
    which is what lets one stacked buffer carry all in-flight activations.
    Total ticks = n_micro + n_stages - 1; the first n_stages - 1 outputs are
    bubble and are dropped.
    """
    n_stages = stage_weights.shape[0]
    n_micro = xm.shape[0]
    mb_shape = xm.shape[1:]

    def shard_stages(buf):
        if mesh is None or axis is None or axis not in mesh.shape:
            return buf
        spec = P(axis, *([None] * (buf.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            buf, NamedSharding(auto_mesh, spec))

    # the schedule is a layout hint the compiler propagates through the
    # shifts, which only Auto axes take (`jax.make_mesh` defaults to
    # Explicit ones): view the same devices with every axis Auto
    auto_mesh = None if mesh is None else Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(AxisType.Auto,) * len(mesh.axis_names))

    buf = shard_stages(jnp.zeros((n_stages,) + mb_shape, xm.dtype))
    outs = []
    for t in range(n_micro + n_stages - 1):
        feed = xm[t] if t < n_micro else jnp.zeros(mb_shape, xm.dtype)
        # shift-in: stage 0 takes the next microbatch, stage s takes stage
        # s-1's previous output (the inter-stage permute).
        buf = shard_stages(jnp.concatenate([feed[None], buf[:-1]], axis=0))
        buf = shard_stages(jax.vmap(stage_fn)(stage_weights, buf))
        if t >= n_stages - 1:
            outs.append(buf[-1])
    return jnp.stack(outs, axis=0)
