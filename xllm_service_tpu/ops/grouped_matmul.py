"""Grouped matrix product for routed experts: rows sorted by expert, one
product per projection over the experts that got a row.

An expert layer plans its dispatch ONCE (`dispatch_plan`, a dozen device
ops over the layer's (token, expert) pairs: the sort order and its
inverse, the groups' sizes and offsets, and the kernel's visit list) and
hands the plan to its three products:

`grouped_matmul(x, stack, layer, plan)`: `x [M, K]` holds the rows of
group 0, then group 1, ... (`plan.sizes [G]`; rows past their sum belong
to no group); the result's row i is `x[i] @ stack[layer, g(i)]`. Rows of
no group come back undefined: the caller masks them.

On the TPU it is this module's own Pallas kernel (`_moe_experts_impl`;
the body is the grouped product of `jax.experimental.pallas.ops.tpu.
megablox`, Apache-2.0, which builds its visit list inside itself, over
every group of the stack, at every call) over the WHOLE stack
`[L, G, K, N]` seen as `[L*G, K, N]` (a bitcast): the visit list names
(group, row tile) pairs of `layer` alone and the weight block's index adds
`layer * G`, so an expert nobody chose is never read from HBM and no layer
is sliced out of the stack for the kernel (a Pallas operand needs a buffer
of its own: a sliced layer would be a copy of it, every step). A weight
block is a whole `[K, N]` expert where that is at most 3 MiB (one DMA an
expert and projection; a group that straddles two row tiles finds its
block still in VMEM), else a column strip of it. The row tile is about one
group's mean share of the rows, between 16 (bfloat16's sublane tile) and
256.

On the CPU backend it is `jax.lax.ragged_dot` over the sliced layer with
the plan's sizes (the same rows, groups and result), so tier-1 tests and
the benchmark's rehearsal walk the caller's sort, plan and un-sort;
`XLLM_PALLAS_INTERPRET=1` runs the Pallas kernel there in interpret mode
(toy shapes only).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_BYTES = 3 * 2 ** 20


def grouped_path(backend: str, interpret: bool) -> str:
    """Which product runs, from what the code sees while it traces; the
    string is `/stats`.attention_paths["moe_experts"]."""
    if backend != "cpu" or interpret:
        return "grouped (pallas, the repo's kernel over a planned visit list)"
    return "grouped (ragged_dot, cpu backend)"


def row_tile(m: int, groups: int) -> int:
    """The row tile for `m` sorted rows over `groups` groups: the power of
    two at or above a group's mean share, within [16, 256]."""
    mean = max(1, -(-m // groups))
    return min(256, max(16, 1 << (mean - 1).bit_length()))


def _tiling(m: int, k: int, n: int, groups: int, itemsize: int):
    tk = k if k <= 2048 else 2048
    tn = n
    if tk * tn * itemsize > _BLOCK_BYTES:
        tn = max(128, _BLOCK_BYTES // (tk * itemsize) // 128 * 128)
    return row_tile(m, groups), tk, tn


class DispatchPlan(NamedTuple):
    """One expert layer's dispatch, all int32. `P` pairs, `E` groups,
    `V = ceil(P / tm) + E - 1` (the most visits there can be)."""
    order: jax.Array        # [P] sorted position -> pair
    inverse: jax.Array      # [P] pair -> sorted position
    sizes: jax.Array        # [E] rows of each group
    offsets: jax.Array      # [E + 1] first sorted row of each group
    group_ids: jax.Array    # [V] the group of each visit
    m_tile_ids: jax.Array   # [V] the row tile of each visit
    num_visits: jax.Array   # [] visits that are real


def dispatch_plan(pair_expert: jax.Array, num_experts: int,
                  tm: int) -> DispatchPlan:
    """The plan of one expert layer from its pairs' experts `[P]` (a dead
    row's pairs carry `num_experts`, sort behind every expert and belong
    to no group), for row tiles of `tm`.

    The visit list is the kernel's grid, in order: every (group, row tile)
    pair in which the group has a row, by group and then by tile. A tile
    shared by two groups is visited twice in a row (the output block stays
    in VMEM between them); a group that straddles tiles is visited once a
    tile (its weight block stays). Entries past `num_visits` repeat the
    last real visit, so nothing the pipeline may look at ahead of the
    grid's end names a block that was not fetched already. It is the list
    `megablox.gmm.make_group_metadata(visit_empty_groups=False)` builds
    from the sizes, in closed form over `[P, E]`, `[E, E]` and `[V, E]`
    comparisons: no loop, no cumulative sum, one sort."""
    P, E = pair_expert.shape[0], num_experts
    V = -(-P // tm) + E - 1
    pair_expert = pair_expert.astype(jnp.int32)
    order = jnp.argsort(pair_expert, stable=True).astype(jnp.int32)
    inverse = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.arange(P, dtype=jnp.int32), unique_indices=True,
        mode="promise_in_bounds")
    # the pairs of groups before e: where group e starts among the sorted
    # rows (e = E: where the dead rows' pairs start)
    offsets = (pair_expert[:, None] < jnp.arange(E + 1, dtype=jnp.int32)
               ).sum(0, dtype=jnp.int32)
    starts, ends = offsets[:-1], offsets[1:]
    sizes = ends - starts
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    experts = jnp.arange(E, dtype=jnp.int32)
    visit_end = jnp.where(experts[None, :] <= experts[:, None],
                          tiles[None, :], 0).sum(1, dtype=jnp.int32)
    last = jnp.maximum(visit_end[E - 1:] - 1, 0)
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), last)[:, None]
    # the groups whose visits all lie before v (the last group's never do)
    group_ids = (visit_end[None, :E - 1] <= v).sum(1, dtype=jnp.int32)
    # a visit opens a new tile unless it is the first of a group that
    # starts inside the tile its predecessor's last visit named
    shares = (sizes > 0) & (starts % tm != 0)
    m_tile_ids = v[:, 0] - (
        shares[None, :] & (visit_end[None, :] <= v + tiles[None, :])
    ).sum(1, dtype=jnp.int32)
    num_visits = visit_end[E - 1]
    return DispatchPlan(order, inverse, sizes, offsets, group_ids,
                        m_tile_ids, num_visits)


def grouped_matmul(x: jax.Array, stack: jax.Array, layer: int,
                   plan: DispatchPlan, *, backend: str,
                   interpret: bool = False) -> jax.Array:
    """x [M, K] (rows sorted by group, as `plan.order` sorts them) x stack
    [L, G, K, N] at `layer` -> [M, N] in x's dtype."""
    if backend == "cpu" and not interpret:
        return jax.lax.ragged_dot(x, stack[layer], plan.sizes)
    return _moe_experts_impl(
        x, stack, jnp.full((1,), layer, jnp.int32), plan.offsets,
        plan.group_ids, plan.m_tile_ids, plan.num_visits,
        interpret=interpret)


def _kernel(offsets, group_ids, m_tile_ids, layer, x, w, out, acc, *,
            tm, tn, tiles_k, k_rem, dtype):
    """One (column strip, visit, K tile) of the grid; the body of
    megablox's `gmm` kernel: a float32 accumulator zeroed at the first K
    tile, the product, and at the last K tile a store of the rows of this
    tile that belong to the visit's group."""
    del layer
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    def mask_k_rem(a, dim):
        iota = jax.lax.broadcasted_iota(jnp.int32, a.shape, dim)
        return jnp.where(iota < k_rem, a.astype(jnp.float32), 0).astype(
            a.dtype)

    def accumulate(last_k: bool):
        xa, wa = x[...], w[...]
        if last_k and k_rem:
            xa, wa = mask_k_rem(xa, 1), mask_k_rem(wa, 0)
        acc[...] += jax.lax.dot_general(
            xa.astype(dtype), wa.astype(dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if last_k:
            g = group_ids[visit]
            rows = m_tile_ids[visit] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
            out[...] = jax.lax.select(
                mine, acc[...], out[...].astype(jnp.float32)).astype(
                    out.dtype)

    jax.lax.cond(k_i == tiles_k - 1, functools.partial(accumulate, True),
                 functools.partial(accumulate, False))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_experts_impl(x, stack, layer, offsets, group_ids, m_tile_ids,
                      num_visits, *, interpret):
    """The kernel under a name of its own: the op's name in a device
    trace (chipbench/layers/kernel.moe_experts_*.py read it)."""
    L, G, K, N = stack.shape
    M = x.shape[0]
    tm, tk, tn = _tiling(M, K, N, G, stack.dtype.itemsize)
    tiles_m, tiles_k, tiles_n = -(-M // tm), -(-K // tk), -(-N // tn)
    if group_ids.shape[0] != tiles_m + G - 1:
        raise ValueError(
            f"a plan of {group_ids.shape[0]} visits for {M} rows in tiles "
            f"of {tm} over {G} groups: want {tiles_m + G - 1}")
    pad = -M % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    both_bf16 = x.dtype == stack.dtype == jnp.bfloat16
    kernel = functools.partial(
        _kernel, tm=tm, tn=tn, tiles_k=tiles_k, k_rem=K % tk,
        dtype=jnp.bfloat16 if both_bf16 else jnp.float32)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M + pad, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, offs, gids,
                             mids, layer: (mids[v], k_i)),
                pl.BlockSpec((None, tk, tn), lambda n_i, v, k_i, offs,
                             gids, mids, layer: (layer[0] * G + gids[v],
                                                 k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, offs,
                                   gids, mids, layer: (mids[v], n_i)),
            grid=(tiles_n, num_visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N, transcendentals=0,
            bytes_accessed=(x.size * x.dtype.itemsize * tiles_n
                            + K * N * stack.dtype.itemsize
                            * group_ids.shape[0]
                            + M * N * x.dtype.itemsize)),
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, layer, x, stack.reshape(L * G, K, N))
    return out[:M] if pad else out
