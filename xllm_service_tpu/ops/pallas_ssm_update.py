"""Pallas TPU kernel: one decode step of the selective state update, over
the live slots only, in place.

The per-slot recurrent state of every state-space layer is ONE donated
buffer ``state: [L, B, N, K]`` float32 (layer, batch slot, state size,
heads x head dim). One call updates layer ``layer`` of it:

    S[b] <- decay[b] * S[b] + B[b] (outer) dtx[b]        [N, K]
    y[b]  = C[b] . S[b]                                   [K]

for the slots ``b`` that are live, and touches no other slot: a dead
slot's [N, K] tile is neither read nor written, so a step moves
2 x N x K x 4 bytes per LIVE slot and layer whatever the batch size (a
masked ``where`` over the buffer would move every slot's, which at 32
slots is as much as the weights).

How a dead slot is skipped with plain BlockSpecs: the wrapper compacts the
live slots to the front of ``order`` (scalar prefetch) and repeats the last
live one behind them. Grid step i maps every block to slot ``order[i]``;
behind the last live slot the block index no longer changes, so the
pipeline fetches nothing and writes nothing back until the grid ends, and
the body does nothing there. The layer is a scalar-prefetch operand too,
as the KV pool's layer is, so the buffer is never sliced into a temporary.

Layout: the state is state-major ([N, K], not [heads, head dim, N]) so
that the reduction over N runs down the sublanes (vector adds) and
``decay``/``dtx`` arrive as lane rows in the layout the projection
produces them in; B and C are turned from lane rows into sublane columns
once per slot with one aligned (N, 128) transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _column(row, n: int):
    """[1, n] lane row -> [n, LANES]: element i in every lane of row i."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, n)))


def _kernel(layer_ref, order_ref, n_live_ref,      # scalar prefetch
            decay_ref, dtx_ref, b_ref, c_ref,      # [1, 1, K] x2, [1, 1, N] x2
            s_ref,                                 # [1, 1, N, K] (aliased)
            o_ref, y_ref):                         # [1, 1, N, K], [1, 1, K]
    del layer_ref, order_ref
    i = pl.program_id(0)
    n_live = n_live_ref[0]
    n, k = s_ref.shape[2], s_ref.shape[3]

    @pl.when(i < n_live)
    def _update():
        b_col = _column(b_ref[0], n)
        c_col = _column(c_ref[0], n)
        for lo in range(0, k, LANES):
            cols = slice(lo, lo + LANES)
            new = (s_ref[0, 0, :, cols] * decay_ref[0, :, cols]
                   + b_col * dtx_ref[0, :, cols])
            o_ref[0, 0, :, cols] = new
            y_ref[0, :, cols] = jnp.sum(new * c_col, axis=0, keepdims=True)

    @pl.when((n_live == 0) & (i == 0))
    def _nothing_live():
        # Every block then maps to slot 0 and is written back once at the
        # end of the grid: hand it back as it came.
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def ssm_update_pallas(state: jax.Array, layer: jax.Array, live: jax.Array,
                      decay: jax.Array, dtx: jax.Array, b: jax.Array,
                      c: jax.Array, interpret: bool = False,
                      ) -> tuple[jax.Array, jax.Array]:
    """state: [L, B, N, K] f32 (donate it: updated in place); layer: [] or
    [1] i32; live: [B] bool; decay, dtx: [B, K] f32; b, c: [B, N] f32.
    Returns (y [B, K] f32, rows of dead slots zero; state)."""
    live = live.astype(jnp.bool_)
    B = live.shape[0]
    n_live = jnp.sum(live, dtype=jnp.int32)
    # Live slots first, in slot order (a stable sort of "dead" flags);
    # behind them the last live slot again.
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = order[jnp.minimum(jnp.arange(B), jnp.maximum(n_live - 1, 0))]
    order = jnp.where(n_live > 0, order, 0)
    state, y = _ssm_update_impl(
        jnp.reshape(layer, (1,)).astype(jnp.int32), order,
        jnp.reshape(n_live, (1,)), decay[:, None, :], dtx[:, None, :],
        b[:, None, :], c[:, None, :], state, interpret=interpret)
    return jnp.where(live[:, None], y[:, 0], 0.0), state


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("state",))
def _ssm_update_impl(layer, order, n_live, decay, dtx, b, c, state, *,
                     interpret: bool = False):
    L, B, N, K = state.shape

    def slot(i, ly, od, nl):
        return (od[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, K), slot),
            pl.BlockSpec((1, 1, K), slot),
            pl.BlockSpec((1, 1, N), slot),
            pl.BlockSpec((1, 1, N), slot),
            pl.BlockSpec((1, 1, N, K),
                         lambda i, ly, od, nl: (ly[0], od[i], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, N, K),
                         lambda i, ly, od, nl: (ly[0], od[i], 0, 0)),
            pl.BlockSpec((1, 1, K), slot),
        ],
    )
    tile = N * K * 4
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, 1, K), jnp.float32)],
        # operand 7 (after the three scalar-prefetch ones) is the state
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the state tile in and out, double-buffered, and the rows
            vmem_limit_bytes=max(32 << 20, 6 * tile)),
        interpret=interpret,
    )(layer, order, n_live, decay, dtx, b, c, state)
