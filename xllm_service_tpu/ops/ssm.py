"""State-space (Mamba-2) primitives for the paged engine: the causal
depthwise convolution, the selective scan in its chunked form (prefill) and
the one-token state update (decode), the latter over one donated per-slot
buffer as `ops/pallas_ssm_update.py` describes it.

Shapes: H heads of P channels (K = H x P), one group of state size N.
``x: [..., H, P]``, ``dt: [..., H]`` (after softplus), ``a: [H]`` (negative),
``b, c: [..., N]``. The recurrence, per head:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) b_t        [P, N]
    y_t = S_t c_t

The D skip term, the gate and the norm belong to the model.

The per-slot state buffer is ``[L, B, N, K]`` float32, state-major (see the
kernel's docstring for why); `ssm_chunked_scan` returns its final state in
that layout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as _attention
from .attention import note_path

LANES = 128


# ------------------------------------------------------------ convolution
def causal_conv(xbc: jax.Array, kernel: jax.Array, bias: jax.Array,
                seq_lens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over time from an empty history.

    xbc: [B, S, C]; kernel: [W, C] (tap j multiplies the input W-1-j steps
    back); bias: [C]; seq_lens: [B] valid lengths. Returns (out [B, S, C]
    before the activation, window [B, W-1, C]: the last W-1 VALID input
    rows, zeros before the sequence: what the next token's convolution
    needs)."""
    W = kernel.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    S = xbc.shape[1]
    out = bias.astype(jnp.float32) + sum(
        padded[:, j:j + S].astype(jnp.float32)
        * kernel[j].astype(jnp.float32) for j in range(W))
    # Row t of the input sits at t + W - 1 of `padded`: rows
    # seq_len - (W-1) .. seq_len - 1 start at seq_len.
    window = jax.vmap(lambda p, n: jax.lax.dynamic_slice_in_dim(
        p, n, W - 1, axis=0))(padded, seq_lens)
    return out, window


def conv_step(window: jax.Array, xbc: jax.Array, kernel: jax.Array,
              bias: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token. window: [B, W-1, C] (the last W-1 inputs); xbc: [B, C].
    Returns (out [B, C] before the activation, the next window)."""
    full = jnp.concatenate([window, xbc[:, None].astype(window.dtype)], 1)
    out = bias.astype(jnp.float32) + jnp.einsum(
        "bwc,wc->bc", full.astype(jnp.float32), kernel.astype(jnp.float32))
    return out, full[:, 1:]


# ----------------------------------------------------------------- prefill
def ssm_sequential_scan(x, dt, a, b, c):
    """The recurrence token by token (a `lax.scan` over time), float32:
    what `ssm_chunked_scan` must equal. Returns (y [B, S, H, P],
    final state [B, N, H*P])."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    f32 = jnp.float32

    def step(s, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a)                          # [B, H]
        s = (s * decay[:, None, :, None]
             + jnp.einsum("bn,bhp->bnhp", bt, dtt[..., None] * xt))
        return s, jnp.einsum("bnhp,bn->bhp", s, ct)

    s0 = jnp.zeros((B, N, H, P), f32)
    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    s, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s.reshape(B, N, H * P)


def ssm_chunked_scan(x, dt, a, b, c, chunk: int):
    """The selective scan from a zero state, chunked: inside a chunk of Q
    tokens a masked [Q, Q] matrix product, between chunks the state
    recurrence (a `lax.scan` over chunks). Positions whose ``dt`` is 0 (a
    bucket's padding) decay nothing and add nothing, so the final state is
    the state after the last valid token.

    x: [B, S, H, P]; dt: [B, S, H]; a: [H]; b, c: [B, S, N]. Returns
    (y [B, S, H, P] f32, final state [B, N, H*P] f32)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    n = (S + pad) // Q
    f32 = jnp.float32
    xs = tuple(jnp.moveaxis(v.astype(f32).reshape(B, n, Q, *v.shape[2:]), 1, 0)
               for v in (x, dt, b, c))
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def one_chunk(s, t):
        xq, dtq, bq, cq = t                       # [B, Q, ...]
        la = jnp.cumsum(dtq * a, axis=1)          # [B, Q, H], <= 0, falling
        dtx = dtq[..., None] * xq                 # [B, Q, H, P]
        # inside the chunk: y_t += sum_{s<=t} exp(la_t - la_s) (c_t.b_s) dtx_s
        seg = la[:, :, None, :] - la[:, None, :, :]            # [B, Q, Q, H]
        decay = jnp.where(tri[None, :, :, None], jnp.exp(
            jnp.where(tri[None, :, :, None], seg, 0.0)), 0.0)
        cb = jnp.einsum("bqn,bsn->bqs", cq, bq)
        y = jnp.einsum("bqs,bqsh,bshp->bqhp", cb, decay, dtx)
        # from the chunks before: y_t += exp(la_t) c_t . S
        y = y + jnp.einsum("bqn,bnhp,bqh->bqhp", cq, s, jnp.exp(la))
        # the state at the chunk's end
        tail = jnp.exp(la[:, -1:, :] - la)                     # [B, Q, H]
        s = (s * jnp.exp(la[:, -1])[:, None, :, None]
             + jnp.einsum("bsn,bsh,bshp->bnhp", bq, tail, dtx))
        return s, y

    s, ys = jax.lax.scan(one_chunk, jnp.zeros((B, N, H, P), f32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S + pad, H, P)[:, :S]
    return y, s.reshape(B, N, H * P)


# ------------------------------------------------------------------ decode
def ssm_update_path(backend: str, interpret: bool, n_state: int,
                    width: int) -> str:
    """The path `ssm_update` takes, from what the code can observe while
    it traces (`attention_path`'s rule: backend and shape decide, nothing
    else): the kernel moves whole (128, 128) float32 tiles, so the state
    size and heads x head dim must be lane-width multiples; the CPU
    backend runs it in interpret mode only."""
    if n_state % LANES or width % LANES:
        return (f"xla (shape outside the kernel's tiling: state={n_state} "
                f"width={width})")
    if backend == "cpu" and not interpret:
        return "xla (cpu backend)"
    return "pallas"


def ssm_update_plain(state, layer, live, decay, dtx, b, c):
    """`ssm_update_pallas` in plain `jax.numpy` (reads and rewrites the
    whole layer): the CPU's path, and what the kernel is compared with."""
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new = old * decay[:, None, :] + b[:, :, None] * dtx[:, None, :]
    y = jnp.einsum("bnk,bn->bk", new, c)
    new = jnp.where(live[:, None, None], new, old)
    state = jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)
    return jnp.where(live[:, None], y, 0.0), state


def ssm_update(state: jax.Array, layer, live: jax.Array, x: jax.Array,
               dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token of the recurrence for every LIVE slot, on layer `layer`
    of the per-slot buffer ``state: [L, B, N, H*P]`` float32 (donated
    through the program: updated in place). x: [B, H, P]; dt: [B, H]; a:
    [H]; b, c: [B, N]; live: [B] bool. Returns (y [B, H, P] f32, zero for
    dead slots, whose state is left as it was; state)."""
    B, H, P = x.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.repeat(jnp.exp(dt * a.astype(f32)), P, axis=-1)   # [B, K]
    dtx = (dt[..., None] * x.astype(f32)).reshape(B, H * P)
    # (through the module: the described-chip compile steers `_backend`)
    interpret = _attention._pallas_interpret()
    path = ssm_update_path(_attention._backend(), interpret,
                           state.shape[2], state.shape[3])
    note_path("ssm_update", path)
    layer = jnp.asarray(layer, jnp.int32)
    if path == "pallas":
        from .pallas_ssm_update import ssm_update_pallas

        y, state = ssm_update_pallas(state, layer, live, decay, dtx,
                                     b.astype(f32), c.astype(f32),
                                     interpret=interpret)
    else:
        y, state = ssm_update_plain(state, layer, live, decay, dtx,
                                    b.astype(f32), c.astype(f32))
    return y.reshape(B, H, P), state
