"""Power retention of degree 2 (Buckman, Gelada & Zhang, "Scaling Context
Requires Rethinking Attention", arXiv 2507.04239) for the paged engine:
the plain forms (attention, one recurrent step, chunked), and the dispatch
of the decode update and of the chunked prefill to their Pallas kernels
(`ops/pallas_retention.py`).

Shapes: ``q: [..., Hq, d]``, ``k, v: [..., Hk, d]`` (query head i reads KV
head i // (Hq // Hk)), ``log_g: [..., Hk]`` float32 (log of the gate, <= 0,
one a KV head). With G_t the running sum of log_g, per query head:

    a_ts = (q_t . k_s)^2 / d * exp(G_t - G_s),   s <= t
    o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

The same as a recurrence over a state, for any phi with
phi(a) . phi(b) = (a . b)^2 / d:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T;   z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

The phi held here is the symmetric square folded along its diagonals:

    phi(x)[m, a] = w_m x_a x_{(a - m) mod d},   m = 0 .. d/2
    w_0 = w_{d/2} = d^-1/2,  w_m = (2/d)^1/2 otherwise

(diagonal m of x x^T and diagonal d - m hold the same products; m = d/2
meets itself, so it is held once at weight 1): D = (d/2 + 1) x d entries,
8320 at d = 128 against the least possible 8256, and row m is
``x * roll(x, m)``, a lane rotation and a product, with no gather and no
unaligned slice. The state of one sequence, layer and KV head is
``S: [M, d, d]`` (m, value dim, a) and ``z: [Mz, d]`` (Mz = M rounded up to
the sublane tile, rows past M zero), float32.

The engine's per-slot buffers are ``[L, B, Hk, M, d, d]`` and
``[L, B, Hk, Mz, d]`` (`retention_update` works on layer `layer` of them, in
place, over the live slots only).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import attention as _attention
from .attention import note_path

LANES = 128
SUBLANES = 8
# The tokens the chunked prefill takes at a time: inside such a sub-chunk the
# masked (Q K^T)^2, between them the state.
SUB_CHUNK = 1024
# Added to the normaliser (`eps` of every form below): part of the mechanism
# as this repository reads it, so a constant and no field of a configuration.
RETENTION_EPS = 1e-6


def slabs(d: int) -> int:
    """M: the diagonals of the folded symmetric square."""
    return d // 2 + 1


def z_rows(d: int) -> int:
    """Mz: M rounded up to the sublane tile."""
    return -(-slabs(d) // SUBLANES) * SUBLANES


def phi_weights(d: int) -> jax.Array:
    m = jnp.arange(slabs(d))
    return jnp.where((m == 0) | (m == d // 2), 1.0,
                     math.sqrt(2.0)).astype(jnp.float32) * d ** -0.5


def phi(x: jax.Array, y: jax.Array | None = None) -> jax.Array:
    """[..., d] -> [..., M, d] float32 with phi(a) . phi(b) = (a . b)^2 / d.
    With `y`, row m is w_m x_a y_(a - m): x and y the same vector up to a
    factor a token (a decay), which then multiplies the whole of phi."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    y = x if y is None else y.astype(jnp.float32)
    rolled = jnp.stack([jnp.roll(y, m, axis=-1) for m in range(slabs(d))],
                       axis=-2)
    return x[..., None, :] * rolled * phi_weights(d)[:, None]


def empty_state(n_kv: int, d: int, lead: tuple = ()) -> tuple:
    """(S, z) zeroed, with `lead` dimensions in front."""
    return (jnp.zeros((*lead, n_kv, slabs(d), d, d), jnp.float32),
            jnp.zeros((*lead, n_kv, z_rows(d), d), jnp.float32))


def _grouped(q: jax.Array, n_kv: int) -> jax.Array:
    """[..., Hq, d] -> [..., Hk, Hq // Hk, d]."""
    return q.reshape(*q.shape[:-2], n_kv, q.shape[-2] // n_kv, q.shape[-1])


# ------------------------------------------------------------ plain forms
def retention_attention(q, k, v, log_g, eps: float) -> jax.Array:
    """The attention form over one sequence from an empty state, float32:
    q [S, Hq, d]; k, v [S, Hk, d]; log_g [S, Hk]. Returns o [S, Hq, d]. No
    phi and no state: what the other two forms must equal."""
    S, Hq, d = q.shape
    f32 = jnp.float32
    G = jnp.cumsum(log_g.astype(f32), axis=0)                     # [S, Hk]
    s = jnp.einsum("tjgd,sjd->jgts", _grouped(q.astype(f32), k.shape[1]),
                   k.astype(f32), precision="highest")
    seen = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(seen, G.T[:, :, None] - G.T[:, None, :],
                              -jnp.inf))                          # [Hk, t, s]
    a = s * s / d * decay[:, None]
    num = jnp.einsum("jgts,sjd->tjgd", a, v.astype(f32), precision="highest")
    den = jnp.moveaxis(a.sum(-1), -1, 0)                          # [t, j, g]
    return (num / (den[..., None] + eps)).reshape(S, Hq, d)


def retention_step(s, z, q, k, v, log_g, eps: float):
    """One token of the recurrence, float32: s [Hk, M, d, d]; z [Hk, Mz, d];
    q [Hq, d]; k, v [Hk, d]; log_g [Hk]. Returns (o [Hq, d], s, z)."""
    Hk, d = k.shape
    M = slabs(d)
    g = jnp.exp(log_g.astype(jnp.float32))
    pk = phi(k)                                                   # [Hk, M, d]
    s = (g[:, None, None, None] * s
         + pk[:, :, None, :] * v.astype(jnp.float32)[:, None, :, None])
    z = (g[:, None, None] * z).at[:, :M].add(pk)
    pq = phi(_grouped(q, Hk))                                     # [Hk, G, M, d]
    num = jnp.einsum("jgma,jmva->jgv", pq, s, precision="highest")
    den = jnp.einsum("jgma,jma->jg", pq, z[:, :M], precision="highest")
    return (num / (den[..., None] + eps)).reshape(q.shape), s, z


def _intra(q, k, v, lg, mx):
    """Inside one sub-chunk: q [C, Hk, G, d]; k, v [C, Hk, d]; lg [C, Hk]
    the sub-chunk's own running sum of log_g. Returns (numerator
    [C, Hk, G, d], normaliser [C, Hk, G]) float32, products on operands of
    type `mx`."""
    C, d = q.shape[0], q.shape[-1]
    f32 = jnp.float32
    s = jnp.einsum("tjgd,sjd->jgts", q.astype(mx), k.astype(mx),
                   preferred_element_type=f32)
    seen = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(seen, lg.T[:, :, None] - lg.T[:, None, :],
                              -jnp.inf))
    a = s * s * (1.0 / d) * decay[:, None]
    num = jnp.einsum("jgts,sjd->tjgd", a.astype(mx), v.astype(mx),
                     preferred_element_type=f32)
    return num, jnp.moveaxis(a.sum(-1), -1, 0)


def _cross_plain(q, qg, k, kd, v, dec, s0, z0, mx):
    """Between sub-chunks, in plain `jax.numpy`: what `retention_cross_
    pallas` computes. q, qg (q times exp of the running log gate)
    [C, Hk, G, d]; k, kd (k times the decay to the sub-chunk's end)
    [C, Hk, d]; v [C, Hk, d]; dec [Hk] the whole sub-chunk's decay. Returns
    (numerator, normaliser, s1, z1)."""
    M = slabs(q.shape[-1])
    f32 = jnp.float32
    pq, pk = phi(qg, q), phi(kd, k)         # [C, Hk, G, M, d], [C, Hk, M, d]
    num = jnp.einsum("tjgma,jmva->tjgv", pq.astype(mx), s0.astype(mx),
                     preferred_element_type=f32)
    den = jnp.einsum("tjgma,jma->tjg", pq, z0[:, :M])
    s1 = dec[:, None, None, None] * s0 + jnp.einsum(
        "sjma,sjv->jmva", pk.astype(mx), v.astype(mx),
        preferred_element_type=f32)
    z1 = (dec[:, None, None] * z0).at[:, :M].add(pk.sum(0))
    return num, den, s1, z1


def retention_chunked(q, k, v, log_g, s0, z0, eps: float, *,
                      sub: int = SUB_CHUNK, cross=None, mx=None):
    """The chunked form over one sequence, state in and state out: inside a
    sub-chunk of `sub` tokens the masked (Q K^T)^2 with the decay, between
    sub-chunks phi(Q) S and the state's update (`cross`: `_cross_plain` or
    the Pallas kernel). q [S, Hq, d]; k, v [S, Hk, d]; log_g [S, Hk] float32;
    s0 [Hk, M, d, d]; z0 [Hk, Mz, d]. A position to be left out of the
    state (a bucket's padding) comes with k = 0 and log_g = 0. Returns
    (o [S, Hq, d] in q's type, s1, z1). Products take operands of type
    `mx` (q's own unless given) and accumulate in float32."""
    S, Hq, d = q.shape
    Hk = k.shape[1]
    f32 = jnp.float32
    mx = mx or q.dtype
    cross = cross or _cross_plain
    C = min(sub, S)
    pad = -S % C
    if pad:
        q, k, v, log_g = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                          for x in (q, k, v, log_g))
    n = (S + pad) // C
    qs = _grouped(q, Hk).reshape(n, C, Hk, Hq // Hk, d)
    ks, vs = k.reshape(n, C, Hk, d), v.reshape(n, C, Hk, d)
    lgs = log_g.astype(f32).reshape(n, C, Hk)

    def one(carry, t):
        s, z = carry
        qc, kc, vc, lg = t
        lg = jnp.cumsum(lg, axis=0)                               # [C, Hk]
        num_i, den_i = _intra(qc, kc, vc, lg, mx)
        qg = qc.astype(f32) * jnp.exp(lg)[:, :, None, None]
        kd = kc.astype(f32) * jnp.exp(lg[-1] - lg)[:, :, None]
        num_x, den_x, s, z = cross(qc, qg, kc, kd, vc, jnp.exp(lg[-1]),
                                   s, z, mx)
        o = (num_i + num_x) / ((den_i + den_x)[..., None] + eps)
        return (s, z), o

    if n == 1:
        (s1, z1), o = one((s0, z0), (qs[0], ks[0], vs[0], lgs[0]))
    else:
        (s1, z1), o = jax.lax.scan(one, (s0, z0), (qs, ks, vs, lgs))
    return o.reshape(n * C, Hq, d)[:S].astype(q.dtype), s1, z1


# --------------------------------------------------------------- the paths
def retention_path(backend: str, interpret: bool, head_dim: int) -> str:
    """The path `retention_update` and `retention_prefill` take, from what
    the code can observe while it traces (`attention_path`'s rule: backend
    and shape decide, nothing else): the kernels move whole (d, d) float32
    slabs and rotate along lanes, so the head must be one lane width; the
    CPU backend runs them in interpret mode only."""
    if interpret and backend == "cpu":
        return "pallas"
    if head_dim != LANES:
        return f"xla (shape outside the kernel's tiling: head_dim={head_dim})"
    if backend == "cpu":
        return "xla (cpu backend)"
    return "pallas"


def _path(op: str, head_dim: int) -> tuple[str, bool]:
    # (through the module: the described-chip compile steers `_backend`)
    interpret = _attention._pallas_interpret()
    path = retention_path(_attention._backend(), interpret, head_dim)
    note_path(op, path)
    return path, interpret


# ------------------------------------------------------------------ decode
def retention_update_plain(state_s, state_z, layer, live, q, k, v, log_g,
                           eps: float):
    """`retention_update` in plain `jax.numpy` (reads and rewrites the whole
    layer): the CPU's path, and what the kernel is compared with."""
    s_old = jax.lax.dynamic_index_in_dim(state_s, layer, 0, keepdims=False)
    z_old = jax.lax.dynamic_index_in_dim(state_z, layer, 0, keepdims=False)
    o, s_new, z_new = jax.vmap(
        lambda s, z, qq, kk, vv, lg: retention_step(s, z, qq, kk, vv, lg,
                                                    eps))(
        s_old, z_old, q, k, v, log_g)
    s_new = jnp.where(live[:, None, None, None, None], s_new, s_old)
    z_new = jnp.where(live[:, None, None, None], z_new, z_old)
    state_s = jax.lax.dynamic_update_index_in_dim(state_s, s_new, layer, 0)
    state_z = jax.lax.dynamic_update_index_in_dim(state_z, z_new, layer, 0)
    return jnp.where(live[:, None, None], o, 0.0), state_s, state_z


def retention_update(state_s: jax.Array, state_z: jax.Array, layer,
                     live: jax.Array, q: jax.Array, k: jax.Array,
                     v: jax.Array, log_g: jax.Array, eps: float):
    """One token of the recurrence for every LIVE slot, on layer `layer` of
    the per-slot buffers ``state_s: [L, B, Hk, M, d, d]`` and ``state_z:
    [L, B, Hk, Mz, d]`` float32 (donated through the program: updated in
    place). q [B, Hq, d]; k, v [B, Hk, d]; log_g [B, Hk] float32; live [B]
    bool. Returns (o [B, Hq, d] float32, zero for dead slots, whose state is
    left as it was; state_s; state_z)."""
    path, interpret = _path("retention_update", q.shape[-1])
    layer = jnp.asarray(layer, jnp.int32)
    if path == "pallas":
        from .pallas_retention import retention_update_pallas

        return retention_update_pallas(state_s, state_z, layer, live, q, k,
                                       v, log_g, eps, interpret=interpret)
    return retention_update_plain(state_s, state_z, layer, live, q, k, v,
                                  log_g.astype(jnp.float32), eps)


# ----------------------------------------------------------------- prefill
def retention_prefill(q, k, v, log_g, s0, z0, eps: float, *,
                      sub: int = SUB_CHUNK):
    """`retention_chunked` on the path the backend allows: on the chip the
    work between sub-chunks (phi(Q) S, the normaliser and the state's
    update, on the MXU with phi built tile by tile in VMEM) is
    `_retention_prefill_impl`."""
    path, interpret = _path("retention_prefill", q.shape[-1])
    cross = None
    if path == "pallas":
        from .pallas_retention import retention_cross_pallas

        def cross(*args):
            return retention_cross_pallas(*args, interpret=interpret)
    return retention_chunked(q, k, v, log_g, s0, z0, eps, sub=sub,
                             cross=cross)
