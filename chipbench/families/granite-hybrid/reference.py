"""The granite-hybrid family's plain reference: the Granite-4.0-H decoder
(HF `granitemoehybrid`, dense) in float32 `jax.numpy` at `highest` matmul
precision, written from the published description (the model's
config.json; Mamba-2, Dao & Gu 2024, for the mixer; HF
`modeling_granitemoehybrid.py` for the conventions). No kernels, no cache,
no paging, no batching, and the state-space mixer as the SEQUENTIAL
recurrence, one token after another (a `lax.scan` over time), never the
chunked form the program's prefill uses: it shares no algorithm with what
it checks. It imports nothing of the program; weights come from weights.py
beside this file, a layer at a time, from the same seed.

Per layer, kind from `layer_types`:
    h = h + r * mixer(RMSNorm(h));  h = h + r * W_out(silu(g) * u),
    [g | u] = W_in RMSNorm(h);      r = residual_multiplier
h_0 = E[tok] * embedding_multiplier; logits = RMSNorm(h) E^T /
logits_scaling (tied).
  attention: q/k/v/o without bias, grouped-query, causal, scores scaled by
    attention_multiplier (NOT head_dim**-0.5), and no rotary embedding of
    any kind (`position_embedding_type` "nope").
  mamba: [z | xBC | dt] = W_in x; xBC' = silu(b + causal depthwise conv of
    width `mamba_d_conv`, zeros before the sequence); x [H, P], B [N],
    C [N] = split(xBC'); dt = softplus(dt + dt_bias); A = -exp(A_log);
    S_t = exp(dt A) S_{t-1} + dt x (outer) B;  y = S_t C + D x;
    out = W_out RMSNorm(y * silu(z); w), the norm over all H x P (one
    group).

Departures from the published code, none in mathematics: one group of B
and C only (`mamba_n_groups` 1, as the model has); activations stay
float32 throughout (the published code rounds them to bfloat16 between
ops; the reference is the yardstick for that rounding, not a copy of it).
With `lower` it is the control: the same forward with every kernel, and
the tied head, rounded to that type.
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import _kernel, _pad, _rms_norm, _round_to

weights = harness.load_file(Path(__file__).with_name("weights.py"))

_KEEP = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps",
         "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
         "shared_intermediate_size", "attention_multiplier",
         "embedding_multiplier", "residual_multiplier", "logits_scaling")


def _hf_static(hf: dict) -> tuple:
    return (tuple(sorted((k, hf[k]) for k in _KEEP if hf.get(k) is not None))
            + (("layer_types", tuple(hf["layer_types"])),))


def _attention(x, lw, kern, hf):
    """x [S, D] (already normed) -> [S, D]."""
    s = weights.shapes(hf)
    n_q, n_kv, hd, S = s["n_q"], s["n_kv"], s["hd"], x.shape[0]
    q = (x @ kern["q_proj"]).reshape(S, n_kv, n_q // n_kv, hd)
    k = (x @ kern["k_proj"]).reshape(S, n_kv, hd)
    v = (x @ kern["v_proj"]).reshape(S, n_kv, hd)
    scores = jnp.einsum("sngh,tnh->ngst", q, k) * hf["attention_multiplier"]
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None],
                       scores, -jnp.inf)
    a = jnp.einsum("ngst,tnh->sngh", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(S, n_q * hd) @ kern["o_proj"]


def _mamba(x, lw, kern, hf):
    """x [S, D] (already normed) -> [S, D]: the recurrence, token by
    token."""
    s = weights.shapes(hf)
    H, P, N, K, W, S = s["H"], s["P"], s["N"], s["K"], s["W"], x.shape[0]
    # W_in's columns [z | xBC | dt]; weights.py makes dt's apart
    z, xbc = jnp.split(x @ kern["in_proj"], [K], axis=-1)
    dt = x @ kern["dt_proj"]
    w = lw["conv"]["kernel"].astype(jnp.float32)                  # [W, C]
    past = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lw["conv"]["bias"].astype(jnp.float32) + sum(
        w[j] * past[j:j + S] for j in range(W)))
    xs, b, c = jnp.split(xbc, [K, K + N], axis=-1)
    xs = xs.reshape(S, H, P)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                      # [S, H]
    a = -jnp.exp(lw["A_log"])                                     # [H]

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, state @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, b, c, dt))
    y = (y + lw["D"][None, :, None] * xs).reshape(S, K)
    y = _rms_norm(y * jax.nn.silu(z), lw["gate_norm"]["scale"],
                  hf["rms_norm_eps"])
    return y @ kern["out_proj"]


def _float32(leaves: dict, lower: str) -> dict:
    return {n: _kernel(v["kernel"], lower) for n, v in leaves.items()
            if isinstance(v, dict) and "kernel" in v and n != "conv"}


@functools.partial(jax.jit,
                   static_argnames=("kind", "hf_t", "served", "lower"))
def _layer(xs, root, layer, kind, hf_t, served, lower):
    """xs [N, S, D]: every sequence through layer `layer`, whose mixer is
    of `kind` (one program a kind), one after the other, with that layer's
    weights made here from its key."""
    hf = dict(hf_t)
    key = weights.layer_key(root, layer)
    mamba = kind == "mamba"
    mix = (weights.mamba_leaves if mamba else weights.attn_leaves)(
        key, hf, served)
    mlp = weights.mlp_leaves(key, hf, served)
    mix_k, mlp_k = _float32(mix, lower), _float32(mlp, lower)
    r, eps = hf["residual_multiplier"], hf["rms_norm_eps"]

    def one(x):
        h = _rms_norm(x, mix["norm"]["scale"], eps)
        x = x + r * (_mamba if mamba else _attention)(h, mix, mix_k, hf)
        h = _rms_norm(x, mlp["norm"]["scale"], eps)
        g, u = jnp.split(h @ mlp_k["in_proj"], 2, axis=-1)
        return x + r * ((jax.nn.silu(g) * u) @ mlp_k["out_proj"])

    return jax.lax.map(one, xs)


@functools.partial(jax.jit, static_argnames=("hf_t", "served"))
def _top(root, hf_t, served):
    return weights.top_leaves(root, dict(hf_t), served)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "lower"))
def _head_one(x, final_scale, embedding, eps, scaling, lower):
    """x [T, D] -> logits [T, V] through the tied head."""
    w = embedding.astype(jnp.float32).T
    w = _round_to(w, lower) if lower else w
    return (_rms_norm(x, final_scale, eps) @ w) / scaling


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
        xs = (top["embed"]["embedding"][jnp.asarray(toks)].astype(jnp.float32)
              * hf["embedding_multiplier"])
        for layer, kind in enumerate(hf["layer_types"]):
            xs = _layer(xs, root, jnp.int32(layer), kind, hf_t, served,
                        lower)
        for i, pos in enumerate(positions):
            idx = np.zeros((T,), np.int32)
            idx[:len(pos)] = pos
            lg = _head_one(xs[i][jnp.asarray(idx)], top["final_norm"]["scale"],
                           top["embed"]["embedding"],
                           float(hf["rms_norm_eps"]),
                           float(hf["logits_scaling"]), lower)
            out.append(np.asarray(lg[:len(pos)]))
    return out
