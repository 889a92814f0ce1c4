"""Grouped matrix product for routed experts: rows sorted by expert, one
product per projection over the experts that got a row.

`grouped_matmul(x, stack, layer, group_sizes)`: `x [M, K]` holds the rows
of group 0, then group 1, ... (`group_sizes [G]` int32; rows past their sum
belong to no group); the result's row i is `x[i] @ stack[layer, g(i)]`.
Rows of no group come back undefined: the caller masks them.

On the TPU it is the Pallas grouped product of
`jax.experimental.pallas.ops.tpu.megablox` over the WHOLE stack
`[L, G, K, N]` seen as `[L*G, K, N]` (a bitcast) with the sizes of `layer`'s
groups set and every other group empty: the kernel visits only groups that
hold a row, so an expert nobody chose is never read from HBM, and no layer
is sliced out of the stack for the kernel (a Pallas operand needs a buffer
of its own: a sliced layer would be a copy of it, every step). A weight
block is a whole `[K, N]` expert where that is at most 3 MiB (one DMA an
expert and projection; a group that straddles two row tiles finds its
block still in VMEM), else a column strip of it. The row tile is about one
group's mean share of the rows, between 16 (bfloat16's sublane tile) and
256.

On the CPU backend it is `jax.lax.ragged_dot` over the sliced layer (the
same rows, groups and result), so tier-1 tests and the benchmark's
rehearsal walk the caller's sort, sizes and un-sort; `XLLM_PALLAS_INTERPRET=1`
runs the Pallas kernel there in interpret mode (toy shapes only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_BLOCK_BYTES = 3 * 2 ** 20


def grouped_path(backend: str, interpret: bool) -> str:
    """Which product runs, from what the code sees while it traces; the
    string is `/stats`.attention_paths["moe_experts"]."""
    if backend != "cpu" or interpret:
        return "grouped (pallas megablox gmm)"
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


def grouped_matmul(x: jax.Array, stack: jax.Array, layer: int,
                   group_sizes: jax.Array, *, backend: str,
                   interpret: bool = False) -> jax.Array:
    """x [M, K] (rows sorted by group) x stack [L, G, K, N] at `layer` ->
    [M, N] in x's dtype."""
    if backend == "cpu" and not interpret:
        return jax.lax.ragged_dot(x, stack[layer],
                                  group_sizes.astype(jnp.int32))
    return _moe_experts_impl(x, stack, jnp.full((1,), layer, jnp.int32),
                             group_sizes.astype(jnp.int32),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_experts_impl(x, stack, layer, group_sizes, *, interpret):
    """The kernel under a name of its own: the op's name in a device
    trace (chipbench/layers/kernel.moe_experts_*.py read it)."""
    import importlib

    # the package's `gmm` name is its differentiable wrapper; the module
    # of that name holds the kernel's builder, jitted under its own name:
    # `__wrapped__` is the builder without that jit, so that the op keeps
    # THIS function's name in a device trace
    _gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    L, G, K, N = stack.shape
    M = x.shape[0]
    tm, tk, tn = _tiling(M, K, N, G, stack.dtype.itemsize)
    pad = -M % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * G,), jnp.int32), group_sizes, layer * G)
    out = _gmm.gmm.__wrapped__(
        x, stack.reshape(L * G, K, N), sizes,
        preferred_element_type=x.dtype, tiling=(tm, tk, tn),
        interpret=interpret)
    return out[:M] if pad else out
