"""Pallas TPU kernels of power retention (`ops/retention.py` has the
mathematics and the state's layout): the decode update over the live slots
only, in place, and the work between the sub-chunks of a chunked prefill.

Both walk the M = d/2 + 1 slabs ``S[m]: [d, d]`` (value dim down the
sublanes, a along the lanes) of one KV head's state, and build row m of
phi where they need it, in VMEM: ``x * roll(x, m)`` times the slab's weight,
a lane rotation and a product. phi never reaches HBM.

*Decode* (`_retention_update_impl`), per live slot and KV head, for each m:

    S[m] <- g S[m] + v (outer) phi(k)[m];   z[m] <- g z[m] + phi(k)[m]
    num  += phi(q)[m] S[m]^T   (the group's query heads at once, on the MXU)
    den  += phi(q)[m] . z[m]

and o = num / (den + eps). A dead slot's state is neither read nor written:
the wrapper compacts the live slots to the front of ``order`` (scalar
prefetch) and repeats the last live one behind them, as
`pallas_ssm_update.py` does; behind the last live slot's last head the block
index no longer changes, so the pipeline moves nothing more. The layer is a
scalar-prefetch operand, so the buffers are never sliced into a temporary.
A step moves 2 x Hk x (M x d x d + Mz x d) x 4 bytes a live slot and layer.

*Prefill* (`_retention_prefill_impl_c<C>`), per KV head over one sub-chunk
of C tokens, from the state the sub-chunk starts with, five slabs a grid
step, phi's rows side by side along the lanes (a product of depth 5 d):

    num += phi(Q g)[m..m+4] S0[m..m+4]^T      [G C, d]  (MXU, depth 640)
    S1[m..m+4] = dec S0[m..m+4] + V^T phi(K w)[m..m+4]  (MXU, depth C)

with g the decay from the sub-chunk's start to the token, w the decay from
the token to its end, dec the whole sub-chunk's. The normaliser and z need
no walk over the slabs and stay outside the kernel (`retention_cross_pallas`).
The MXU takes its operands in the type the model computes in and
accumulates in float32; the state is float32 in HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .retention import SUBLANES, phi_weights, slabs

_NT = (((1,), (1,)), ((), ()))      # a [m, k] x b [n, k] -> [m, n]


def _weight(m, M: int, d: int):
    """phi's weight of slab m (ops/retention.phi_weights), m traced."""
    return jnp.where((m == 0) | (m == M - 1), 1.0,
                     math.sqrt(2.0)).astype(jnp.float32) * d ** -0.5


# ------------------------------------------------------------------ decode
def _update_kernel(layer_ref, order_ref, n_live_ref,     # scalar prefetch
                   q_ref,                     # [1, 1, Gp, d]
                   k_ref, v_ref, g_ref,       # [1, 1, 1, d] each
                   s_ref, z_ref,              # [1,1,1,M,d,d], [1,1,1,Mz,d]
                   so_ref, zo_ref, o_ref,     # the same two, [1, 1, Gp, d]
                   *, eps: float, mx):
    del layer_ref, order_ref
    i = pl.program_id(0)
    n_live = n_live_ref[0]
    M, d = s_ref.shape[3], s_ref.shape[4]

    @pl.when(i < n_live)
    def _update():
        q = q_ref[0, 0]                                   # [Gp, d]
        k = k_ref[0, 0, 0:1]                              # [1, d]
        g = g_ref[0, 0, 0:1]                              # the gate, every lane
        # v down the sublanes: element i in every lane of row i
        v_col = jnp.transpose(jnp.broadcast_to(v_ref[0, 0, 0:1], (d, d)))
        if z_ref.shape[3] > M:                            # rows past M
            zo_ref[0, 0, 0, M:, :] = z_ref[0, 0, 0, M:, :]

        def slab(m, carry):
            num, den = carry
            w = _weight(m, M, d)
            pk = k * pltpu.roll(k, m, 1) * w              # [1, d]
            s_new = s_ref[0, 0, 0, m] * g + v_col * pk
            so_ref[0, 0, 0, m] = s_new
            z_new = z_ref[0, 0, 0, pl.ds(m, 1), :] * g + pk
            zo_ref[0, 0, 0, pl.ds(m, 1), :] = z_new
            pq = q * pltpu.roll(q, m, 1) * w              # [Gp, d]
            num = num + jax.lax.dot_general(
                pq.astype(mx), s_new.astype(mx), _NT,
                preferred_element_type=jnp.float32)
            return num, den + pq * z_new

        zero = jnp.zeros(q.shape, jnp.float32)
        num, den = jax.lax.fori_loop(0, M, slab, (zero, zero))
        o_ref[0, 0] = num / (jnp.sum(den, axis=1, keepdims=True) + eps)

    @pl.when((n_live == 0) & (i == 0) & (pl.program_id(1) == 0))
    def _nothing_live():
        # Every block then maps to slot 0's last head and is written back
        # once at the end of the grid: hand it back as it came.
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def retention_update_pallas(state_s, state_z, layer, live, q, k, v, log_g,
                            eps: float, interpret: bool = False):
    """state_s [L, B, Hk, M, d, d], state_z [L, B, Hk, Mz, d] f32 (donate
    them: updated in place); layer [] or [1] i32; live [B] bool; q
    [B, Hq, d]; k, v [B, Hk, d]; log_g [B, Hk]. Returns (o [B, Hq, d] f32,
    rows of dead slots zero; state_s; state_z)."""
    live = live.astype(jnp.bool_)
    B, Hq, d = q.shape
    Hk = k.shape[1]
    G = Hq // Hk
    Gp = -(-G // SUBLANES) * SUBLANES
    f32 = jnp.float32
    n_live = jnp.sum(live, dtype=jnp.int32)
    # Live slots first, in slot order (a stable sort of "dead" flags);
    # behind them the last live slot again.
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = order[jnp.minimum(jnp.arange(B), jnp.maximum(n_live - 1, 0))]
    order = jnp.where(n_live > 0, order, 0)
    qg = jnp.pad(q.astype(f32).reshape(B, Hk, G, d),
                 ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    gate = jnp.broadcast_to(jnp.exp(log_g.astype(f32))[:, :, None, None],
                            (B, Hk, 1, d))
    state_s, state_z, o = _retention_update_impl(
        jnp.reshape(layer, (1,)).astype(jnp.int32), order,
        jnp.reshape(n_live, (1,)), qg, k.astype(f32)[:, :, None],
        v.astype(f32)[:, :, None], gate, state_s, state_z, eps=eps,
        mx=jnp.dtype(q.dtype).name, interpret=interpret)
    o = o[:, :, :G].reshape(B, Hq, d)
    return jnp.where(live[:, None, None], o, 0.0), state_s, state_z


@functools.partial(jax.jit, static_argnames=("eps", "mx", "interpret"),
                   donate_argnames=("state_s", "state_z"))
def _retention_update_impl(layer, order, n_live, q, k, v, gate, state_s,
                           state_z, *, eps: float, mx: str,
                           interpret: bool = False):
    L, B, Hk, M, d, _ = state_s.shape
    Mz = state_z.shape[3]
    Gp = q.shape[2]

    def head(i, j, nl):
        # behind the last live slot: its last head, so nothing moves
        return jnp.where(i < nl[0], j, Hk - 1)

    def row(i, j, ly, od, nl):
        return (od[i], head(i, j, nl), 0, 0)

    s_spec = pl.BlockSpec(
        (1, 1, 1, M, d, d),
        lambda i, j, ly, od, nl: (ly[0], od[i], head(i, j, nl), 0, 0, 0))
    z_spec = pl.BlockSpec(
        (1, 1, 1, Mz, d),
        lambda i, j, ly, od, nl: (ly[0], od[i], head(i, j, nl), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hk),
        in_specs=[pl.BlockSpec((1, 1, Gp, d), row),
                  pl.BlockSpec((1, 1, 1, d), row),
                  pl.BlockSpec((1, 1, 1, d), row),
                  pl.BlockSpec((1, 1, 1, d), row),
                  s_spec, z_spec],
        out_specs=[s_spec, z_spec, pl.BlockSpec((1, 1, Gp, d), row)],
    )
    slab_bytes = M * d * d * 4
    return pl.pallas_call(
        functools.partial(_update_kernel, eps=eps, mx=jnp.dtype(mx)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state_s.shape, state_s.dtype),
                   jax.ShapeDtypeStruct(state_z.shape, state_z.dtype),
                   jax.ShapeDtypeStruct((B, Hk, Gp, d), jnp.float32)],
        # operands 7 and 8 (after the three scalar-prefetch ones): the state
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # one head's slabs in and out, double-buffered, and the rows
            vmem_limit_bytes=max(32 << 20, 5 * slab_bytes)),
        interpret=interpret,
    )(layer, order, n_live, q, k, v, gate, state_s, state_z)


# ----------------------------------------------------------------- prefill
def _slab_block(M: int) -> int:
    """Slabs a grid step takes: the largest divisor of M up to 5, so that
    the product phi(Q) S runs at a depth of up to 5 d (640 at d = 128: the
    MXU's products of depth 128 run at a fifth of that rate)."""
    return max(b for b in range(1, 6) if M % b == 0)


def _cross_kernel(q_ref, qg_ref,             # [1, G C, d] f32
                  k_ref, kd_ref,             # [1, C, d] f32
                  vt_ref,                    # [1, d, C] mx
                  dec_ref,                   # [1, 1, d] f32
                  s0_ref,                    # [1, mb, d, d]
                  num_ref,                   # [1, G C, d] f32
                  s1_ref,                    # [1, mb, d, d]
                  *, mx):
    step = pl.program_id(1)
    mb, d = s0_ref.shape[1], s0_ref.shape[2]
    M = mb * pl.num_programs(1)
    dec = dec_ref[0]                                       # [1, d]
    q, qg, k, kd = q_ref[0], qg_ref[0], k_ref[0], kd_ref[0]
    # phi's rows m0 .. m0 + mb - 1 side by side along the lanes; the slab's
    # weight goes on the state's side, (d, d) values and not (G C, d).
    pq, pk, s0w = [], [], []
    for i in range(mb):
        m = step * mb + i
        w = _weight(m, M, d)
        pq.append((qg * pltpu.roll(q, m, 1)).astype(mx))
        pk.append((kd * pltpu.roll(k, m, 1)).astype(mx))
        s0w.append((s0_ref[0, i] * w).astype(mx))
    num = jax.lax.dot_general(
        jnp.concatenate(pq, axis=1), jnp.concatenate(s0w, axis=1), _NT,
        preferred_element_type=jnp.float32)                # [G C, d]

    @pl.when(step == 0)
    def _first():
        num_ref[0] = num

    @pl.when(step > 0)
    def _rest():
        num_ref[0] += num

    add = jnp.dot(vt_ref[0], jnp.concatenate(pk, axis=1),
                  preferred_element_type=jnp.float32)      # [d, mb d]
    for i in range(mb):
        w = _weight(step * mb + i, M, d)
        s1_ref[0, i] = s0_ref[0, i] * dec + add[:, i * d:(i + 1) * d] * w


def _folded(z0: jax.Array) -> jax.Array:
    """z [Hk, Mz, d] -> Z [Hk, d, d] with phi(x) . z = x^T Z y for
    phi(x, y)[m, a] = w_m x_a y_(a - m): Z[a, b] = w_m z[m, a] at
    b = (a - m) mod d, m = 0 .. d/2, zero elsewhere."""
    d = z0.shape[-1]
    a = jnp.arange(d)[:, None]
    m = (a - jnp.arange(d)[None, :]) % d                  # [a, b]
    held = m <= d // 2
    z = z0[:, jnp.where(held, m, 0), a] * phi_weights(d)[jnp.where(held, m, 0)]
    return jnp.where(held, z, 0.0)


def _unfolded(gram: jax.Array, Mz: int) -> jax.Array:
    """The inverse reading: gram [Hk, d, d] (sum over tokens of x_a y_b) ->
    [Hk, Mz, d] with row m, column a = w_m gram[a, (a - m) mod d]."""
    d = gram.shape[-1]
    M = slabs(d)
    m = jnp.arange(M)[:, None]
    a = jnp.arange(d)[None, :]
    rows = gram[:, a, (a - m) % d] * phi_weights(d)[:, None]
    return jnp.pad(rows, ((0, 0), (0, Mz - M), (0, 0)))


def retention_cross_pallas(q, qg, k, kd, v, dec, s0, z0, mx,
                           interpret: bool = False):
    """`ops/retention._cross_plain` with the state's products as the kernel:
    q, qg [C, Hk, G, d]; k, kd, v [C, Hk, d]; dec [Hk]; s0 [Hk, M, d, d]; z0
    [Hk, Mz, d]. Returns (numerator [C, Hk, G, d], normaliser [C, Hk, G],
    s1, z1) float32. The normaliser and z need no walk over the slabs:
    phi(q) . z0 = qg^T Z q with z0 folded into one (d, d) matrix a head, and
    the sum of phi(k) over the tokens is a reading of the Gram matrix
    Kd^T K along its diagonals; both are plain float32 products here."""
    C, Hk, G, d = q.shape
    f32 = jnp.float32

    def rows(x):            # [C, Hk, ..., d] -> [Hk, (G) C, d], head-major
        x = jnp.moveaxis(x.astype(f32), 0, -2)
        return x.reshape(Hk, -1, d)

    num, s1 = _retention_prefill_impl(
        rows(q), rows(qg), rows(k), rows(kd),
        jnp.transpose(v.astype(mx), (1, 2, 0)),
        jnp.broadcast_to(dec.astype(f32)[:, None, None], (Hk, 1, d)),
        s0, mx=jnp.dtype(mx).name, interpret=interpret)
    num = jnp.moveaxis(num.reshape(Hk, G, C, d), 2, 0)
    hi = jax.lax.Precision.HIGHEST
    den = jnp.einsum("tjga,jab,tjgb->tjg", qg.astype(f32), _folded(z0),
                     q.astype(f32), precision=hi)
    gram = jnp.einsum("sja,sjb->jab", kd.astype(f32), k.astype(f32),
                      precision=hi)
    z1 = dec.astype(f32)[:, None, None] * z0 + _unfolded(gram, z0.shape[1])
    return num, den, s1, z1


def _retention_prefill_impl(q, qg, k, kd, vt, dec, s0, *, mx: str,
                            interpret: bool = False):
    """The kernel call for a sub-chunk of C = k.shape[1] tokens, jitted
    under the name `_retention_prefill_impl_c<C>`: the name is the op's in a
    device trace, and a reader of the trace prices each call from it (the
    tokens it held) without a second clock."""
    return _prefill_impl(k.shape[1])(q, qg, k, kd, vt, dec, s0, mx=mx,
                                     interpret=interpret)


@functools.lru_cache(maxsize=None)
def _prefill_impl(C: int):
    def impl(q, qg, k, kd, vt, dec, s0, *, mx, interpret):
        return _prefill_call(q, qg, k, kd, vt, dec, s0, mx, interpret)

    impl.__name__ = impl.__qualname__ = f"_retention_prefill_impl_c{C}"
    return jax.jit(impl, static_argnames=("mx", "interpret"))


def _prefill_call(q, qg, k, kd, vt, dec, s0, mx: str, interpret: bool):
    Hk, GC, d = q.shape
    C = k.shape[1]
    M = slabs(d)
    mb = _slab_block(M)

    def head(j, m):
        return (j, 0, 0)

    wide = pl.BlockSpec((1, GC, d), head)
    narrow = pl.BlockSpec((1, C, d), head)
    s_spec = pl.BlockSpec((1, mb, d, d), lambda j, m: (j, m, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(Hk, M // mb),
        in_specs=[wide, wide, narrow, narrow,
                  pl.BlockSpec((1, d, C), head),
                  pl.BlockSpec((1, 1, d), head), s_spec],
        out_specs=[wide, s_spec],
    )
    item = jnp.dtype(mx).itemsize
    resident = (4 * (3 * GC + 2 * C) * d + item * d * C
                + item * mb * (GC + C) * d)        # and phi's rows, in mx
    return pl.pallas_call(
        functools.partial(_cross_kernel, mx=jnp.dtype(mx)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Hk, GC, d), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the sub-chunk's rows and sums resident and double-buffered,
            # and as much again for the body's own values
            vmem_limit_bytes=min(100 << 20, max(32 << 20, 3 * resident))),
        interpret=interpret,
    )(q, qg, k, kd, vt, dec, s0)
