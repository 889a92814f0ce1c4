"""Pipeline parallelism: GPipe-style layer staging over a `pipe` mesh
axis (SURVEY.md §2.12's PP entry; the reference's engine is an empty
submodule, so the TPU-native design is ours to define).

Mechanism: the stacked layer parameters (leading ``L`` dim) are sharded
over the pipe axis — stage *s* holds layers ``[s*L/P, (s+1)*L/P)``. The
batch is split into microbatches; on schedule tick *t*, stage *s* runs
microbatch ``t - s`` through its local layer block, then the activations
``ppermute`` one hop down the ring. After ``P + M - 1`` ticks every
microbatch has passed through every stage; outputs are collected on the
last stage and ``psum``-broadcast (the off-stage contribution is zero).

All control flow is static (fixed tick count, masked inactivity) — the
compiler-friendly schedule shape (same trade as ring attention's masked
hops). Latency note: PP adds pipeline-fill bubbles and is the *capacity*
axis of the mesh taxonomy; TP/SP remain the latency axes. It exists so
models deeper than one slice's HBM can still serve.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_local(local_layers, x_mb, layer_fn: Callable,
                    axis_name: str) -> jax.Array:
    """Per-stage body. local_layers: this stage's layer-param shard
    (leading dim L/P); x_mb: [M, mb, ...] microbatched input, replicated
    across stages."""
    n_stage = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]

    def run_block(x):
        def one(x, lp):
            return layer_fn(x, lp), None
        y, _ = jax.lax.scan(one, x, local_layers)
        return y

    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def _vary(v):
        return jax.lax.pcast(v, axis_name, to="varying")

    state = _vary(jnp.zeros_like(x_mb[0]))          # in-flight activation
    outputs = _vary(jnp.zeros_like(x_mb))

    def tick(carry, t):
        state, outputs = carry
        mb = t - stage                               # my microbatch index
        active = (mb >= 0) & (mb < M)
        # Stage 0 injects fresh microbatches; others consume the permuted
        # activation from the previous stage.
        inject = jnp.clip(t, 0, M - 1)
        cur = jnp.where(stage == 0, x_mb[inject], state)
        y = run_block(cur)
        y = jnp.where(active, y, cur)
        # Last stage records its finished microbatch.
        out_idx = jnp.clip(mb, 0, M - 1)
        take = active & (stage == n_stage - 1)
        outputs = outputs.at[out_idx].set(
            jnp.where(take, y, outputs[out_idx]))
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state, outputs), jnp.arange(n_stage + M - 1))
    # Only the last stage holds real outputs; zero elsewhere -> psum is a
    # broadcast of the result to every stage (out_specs replicate).
    outputs = jnp.where(stage == n_stage - 1, outputs,
                        jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, axis_name)


def pipeline_forward(layer_fn: Callable, stacked_layers, x: jax.Array,
                     mesh: Mesh, n_microbatches: int,
                     pipe_axis: str = "pipe") -> jax.Array:
    """Run ``x`` through the stacked layers, pipelined over `pipe_axis`.

    layer_fn(x, layer_params) -> x      (one transformer layer)
    stacked_layers: pytree with leading L dim divisible by the stage count
    x: [B, ...] with B divisible by n_microbatches.
    """
    B = x.shape[0]
    assert B % n_microbatches == 0, "batch not divisible by microbatches"
    x_mb = x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])
    layer_spec = P(pipe_axis)        # shard leading L dim into stages
    fn = shard_map(
        functools.partial(_pipeline_local, layer_fn=layer_fn,
                          axis_name=pipe_axis),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: layer_spec, stacked_layers),
                  P()),
        out_specs=P(),
    )
    out = fn(stacked_layers, x_mb)
    return out.reshape(B, *out.shape[2:])
