"""The power-retention family's plain reference: the Brumby decoder (HF
`brumby`) in float32 `jax.numpy` at `highest` matmul precision, written
from the published description of power retention (Buckman, Gelada & Zhang,
"Scaling Context Requires Rethinking Attention", arXiv 2507.04239) in its
ATTENTION form: no feature map phi, no state, no recurrence and no chunks, so
it shares no algorithm with the program's decode update or its chunked
prefill. No kernels, no cache, no paging, no batching. It imports nothing
of the program; weights come from weights.py beside this file, a layer at a
time, from the same seed.

Per layer, x the residual stream, d the head size, query head i reading KV
head j = i // (heads / kv heads):

    h = RMSNorm(x);  q = RMSNorm_head(W_q h);  k = RMSNorm_head(W_k h);
    v = W_v h;       q, k <- rotary(q, k; half-split, rope_theta)
    log g_t = log sigmoid(W_g h_t + b_g)   [kv heads];  G_t = sum_{r<=t} log g_r
    a_ts = (q_t . k_s)^2 / d * exp(G_t - G_s),   s <= t
    o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
    x <- x + W_o concat_i(o);   x <- x + W_down(silu(W_gate h') * W_up h'),
    h' = RMSNorm(x)
h_0 = E[tok]; logits = RMSNorm(h) W_head (untied).

Departures from the published description, each because the config.json
(Qwen3's keys) states nothing of it and the published code is not in the
repository (`assumed` in the configuration's file says the same): the
degree is 2; the gate is one sigmoid a KV head with a bias, shared by the
head's group of query heads; the normaliser is the sum of the weights plus
`eps` = 1e-6; q and k are normed a head and rotated as Qwen3 does; and
activations stay float32 throughout (a served model rounds them to
bfloat16 between ops; the reference is the yardstick for that rounding).
The t x t weights are computed ROW_BLOCK rows at a time, so that a 16.9k-token
sequence fits one chip. With `lower` it is the control: the same forward
with every kernel, and the head, rounded to that type.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import _kernel, _pad, _rms_norm, _rope

weights = harness.load_file(Path(__file__).with_name("weights.py"))

RETENTION_EPS = 1e-6
ROW_BLOCK = 128
_KEEP = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "vocab_size", "rms_norm_eps", "rope_theta")


def _hf_static(hf: dict) -> tuple:
    return tuple(sorted((k, hf[k]) for k in _KEEP if hf.get(k) is not None))


def _retention(q, k, v, log_g):
    """q [S, n_kv, g, hd]; k, v [S, n_kv, hd]; log_g [S, n_kv]. The
    attention form, ROW_BLOCK query rows at a time. Returns [S, n_kv, g,
    hd]."""
    S, hd = q.shape[0], q.shape[-1]
    G = jnp.cumsum(log_g, axis=0).T                        # [n_kv, S]
    cols = jnp.arange(S)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, ROW_BLOCK, 0)
        Gb = jax.lax.dynamic_slice_in_dim(G, start, ROW_BLOCK, 1)
        t = start + jnp.arange(ROW_BLOCK)
        seen = cols[None, :] <= t[:, None]                 # [R, S]
        decay = jnp.exp(jnp.where(seen[None], Gb[:, :, None] - G[:, None, :],
                                  -jnp.inf))               # [n_kv, R, S]
        s = jnp.einsum("tngh,snh->ngts", qb, k)
        a = s * s / hd * decay[:, None]
        num = jnp.einsum("ngts,snh->tngh", a, v)
        den = jnp.moveaxis(a.sum(-1), -1, 0)               # [R, n_kv, g]
        return num / (den[..., None] + RETENTION_EPS)

    out = jax.lax.map(rows, jnp.arange(0, S, ROW_BLOCK))
    return out.reshape(S, *q.shape[1:])


def _layer_one(x, lw, kern, hf):
    """x [S, D] float32 -> [S, D]; one sequence through one layer whose
    kernels `kern` are already float32."""
    s = weights.shapes(hf)
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    n_q, n_kv, hd, S = s["n_q"], s["n_kv"], s["hd"], x.shape[0]
    h = _rms_norm(x, lw["input_norm"]["scale"], eps)
    q = _rms_norm((h @ kern["q_proj"]).reshape(S, n_q, hd),
                  lw["q_norm"]["scale"], eps)
    k = _rms_norm((h @ kern["k_proj"]).reshape(S, n_kv, hd),
                  lw["k_norm"]["scale"], eps)
    v = (h @ kern["v_proj"]).reshape(S, n_kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    log_g = jax.nn.log_sigmoid(h @ kern["g_proj"] + lw["g_proj"]["bias"])
    o = _retention(q.reshape(S, n_kv, n_q // n_kv, hd), k, v, log_g)
    x = x + o.reshape(S, n_q * hd) @ kern["o_proj"]
    h = _rms_norm(x, lw["post_attn_norm"]["scale"], eps)
    return x + (jax.nn.silu(h @ kern["gate_proj"])
                * (h @ kern["up_proj"])) @ kern["down_proj"]


@functools.partial(jax.jit, static_argnames=("hf_t", "served", "lower"))
def _layer(xs, root, layer, hf_t, served, lower):
    """xs [N, S, D]: every sequence through layer `layer`, one after the
    other (lax.map), with that layer's weights made here from its key."""
    hf = dict(hf_t)
    lw = weights.layer_leaves(weights.layer_key(root, layer), hf, served)
    kern = {n: _kernel(v["kernel"], lower) for n, v in lw.items()
            if "kernel" in v}
    return jax.lax.map(lambda x: _layer_one(x, lw, kern, hf), xs)


@functools.partial(jax.jit, static_argnames=("hf_t", "served"))
def _top(root, hf_t, served):
    return weights.top_leaves(root, dict(hf_t), served)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head_one(x, final_scale, head, eps, lower):
    """x [T, D] -> logits [T, V] through the untied head."""
    return _rms_norm(x, final_scale, eps) @ _kernel(head, lower)


def logits_at(seed: int, hf: dict, served: str, sequences, positions,
              lower="", pad_len: int = 0, pad_pos: int = 0):
    """For each sequence, float32 logits [len(positions[i]), V] at the given
    positions (position p predicts token p+1), as host arrays. Sequences
    are right-padded to one length, the longest's or `pad_len`, positions to
    `pad_pos`, so that every run of a cell compiles the same shapes (the
    padding lies behind every real token and the model is causal)."""
    hf_t = _hf_static(hf)
    root = weights.root_key(seed)
    S = _pad(max(len(s) for s in sequences), pad_len)
    T = _pad(max(len(p) for p in positions), pad_pos)
    toks = np.zeros((len(sequences), S), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s
    out = []
    with jax.default_matmul_precision("highest"):
        top = _top(root, hf_t, served)
        xs = top["embed"]["embedding"][jnp.asarray(toks)].astype(jnp.float32)
        for layer in range(hf["num_hidden_layers"]):
            xs = _layer(xs, root, jnp.int32(layer), hf_t, served, lower)
        for i, pos in enumerate(positions):
            idx = np.zeros((T,), np.int32)
            idx[:len(pos)] = pos
            lg = _head_one(xs[i][jnp.asarray(idx)],
                           top["final_norm"]["scale"],
                           top["lm_head"]["kernel"],
                           float(hf["rms_norm_eps"]), lower)
            out.append(np.asarray(lg[:len(pos)]))
    return out
