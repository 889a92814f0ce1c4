"""Ring attention: causal self-attention with the sequence sharded over a
mesh axis (context parallelism for long-context prefill; SURVEY.md §5.7).

Mechanism (blockwise ring, flash-style): each device holds one contiguous
sequence chunk of Q/K/V. K/V chunks rotate around the ring with
`lax.ppermute` over ICI; every hop each device accumulates its local Q's
attention over the visiting K/V chunk with an online-softmax merge. Causal
structure across chunks: a visiting chunk earlier in the sequence is fully
attended, the device's own chunk gets the intra-chunk causal mask, and
later chunks are skipped (their contribution is masked to zero weight).

FLOP note: all n ring hops run the same einsum shape (static shapes for
XLA); later-chunk hops are masked rather than skipped — the usual tradeoff
for compiler-friendly control flow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

_NEG_INF = -1e30


def _ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array,
                          axis_name: str,
                          scale: float | None = None) -> jax.Array:
    """Per-device body. q: [B, S_loc, H, hd]; k/v: [B, S_loc, H_kv, hd]
    (GQA: H_kv may divide H — K/V rotate around the ring at their small
    head count and are repeated only at use, so ICI traffic stays at the
    KV size, not the query size)."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, S, H, hd = q.shape
    n_rep = H // k.shape[2]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    qf = q.astype(jnp.float32) * scale

    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]

    def hop(carry, step):
        k_cur, v_cur, m, l, acc = carry
        src = (my_idx - step) % n        # which chunk is visiting
        k_use = jnp.repeat(k_cur, n_rep, axis=2) if n_rep > 1 else k_cur
        v_use = jnp.repeat(v_cur, n_rep, axis=2) if n_rep > 1 else v_cur
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_use.astype(jnp.float32))
        # Causal structure across chunks.
        intra = jnp.where(cols <= rows, 0.0, _NEG_INF)       # same chunk
        full = jnp.zeros((S, S), jnp.float32)                # earlier chunk
        none = jnp.full((S, S), _NEG_INF)                    # later chunk
        mask = jnp.where(src == my_idx, intra,
                         jnp.where(src < my_idx, full, none))
        s = s + mask[None, None, :, :]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # Guard fully-masked hops (exp(-inf - -inf)).
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(m <= _NEG_INF / 2, 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # acc: [B, S, H, hd]; alpha: [B, H, S, 1] -> align axes.
        alpha_b = jnp.swapaxes(alpha[..., 0], 1, 2)[..., None]  # [B, S, H, 1]
        acc_new = acc * alpha_b + jnp.swapaxes(
            jnp.einsum("bhqk,bkhd->bhqd", p, v_use.astype(jnp.float32)),
            1, 2)
        # Rotate K/V to the next device on the ring.
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    # Mark the constant initial carries as device-varying so the scan
    # carry types line up with the ring-permuted outputs.
    def _vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    m0 = _vary(jnp.full((B, H, S, 1), _NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, S, 1), jnp.float32))
    acc0 = _vary(jnp.zeros((B, S, H, hd), jnp.float32))
    (k_f, v_f, m, l, acc), _ = jax.lax.scan(
        hop, (k, v, m0, l0, acc0), jnp.arange(n))
    l_b = jnp.swapaxes(l[..., 0], 1, 2)[..., None]          # [B, S, H, 1]
    out = acc / jnp.maximum(l_b, 1e-9)
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Mesh, seq_axis: str = "seq",
                   scale: float | None = None) -> jax.Array:
    """q: [B, S, H, hd], k/v: [B, S, H_kv, hd] (H_kv | H for GQA) with S
    divisible by the seq-axis size; returns causal self-attention output,
    sequence-parallel over `seq_axis`."""
    spec = P(None, seq_axis, None, None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=seq_axis,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
