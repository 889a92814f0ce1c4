"""The toy sparse-experts family's plain reference: a Mixtral-shaped
decoder in float32 `jax.numpy` at `highest` matmul precision, written from
the published description (Mixtral of Experts, Jiang et al. 2024; HF
`modeling_mixtral.py` for the conventions: pre-norm RMSNorm, no biases,
half-split rotary embedding, grouped-query causal attention, then per token
the router's logits over all experts, the `num_experts_per_tok` largest,
a softmax over those alone, and the so-weighted sum of those experts'
SwiGLU). No kernels, no cache, no paging, no dispatch: every expert runs on
every token and the gate is zero where it was not chosen. It imports nothing
of the program; weights come from weights.py beside this file, a layer at a
time, from the same seed. With `lower` it is the control: the same forward
with every kernel rounded to that type.
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


def _layer_one(x, lw, kern, hf_t):
    """x [S, D] float32 -> [S, D]: one sequence through one layer whose
    kernels `kern` are already float32."""
    hf = dict(hf_t)
    s = weights.shapes(hf)
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    n_q, n_kv, hd, S = s["n_q"], s["n_kv"], s["hd"], x.shape[0]
    h = _rms_norm(x, lw["attn"]["input_norm"]["scale"], eps)
    q = _rope((h @ kern["q_proj"]).reshape(S, n_q, hd), theta)
    k = _rope((h @ kern["k_proj"]).reshape(S, n_kv, hd), theta)
    v = (h @ kern["v_proj"]).reshape(S, n_kv, hd)
    q = q.reshape(S, n_kv, n_q // n_kv, hd)
    scores = jnp.einsum("sngh,tnh->ngst", q, k) * (hd ** -0.5)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None],
                       scores, -jnp.inf)
    a = jnp.einsum("ngst,tnh->sngh", jax.nn.softmax(scores, axis=-1),
                   v).reshape(S, n_q * hd)
    x = x + a @ kern["o_proj"]
    h = _rms_norm(x, lw["attn"]["post_attn_norm"]["scale"], eps)
    logits = h @ lw["moe"]["router"]["kernel"]                    # [S, E]
    top, _ = jax.lax.top_k(logits, s["K"])
    chosen = logits >= top[:, -1:]
    gate = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    m = jax.nn.silu(jnp.einsum("sd,edf->esf", h, kern["gate_proj"])) \
        * jnp.einsum("sd,edf->esf", h, kern["up_proj"])
    return x + jnp.einsum("esf,efd,se->sd", m, kern["down_proj"], gate)


def _float32(kern, lower: str):
    """A served kernel, or a stack of experts' kernels, in float32."""
    if (kern["q8"] if isinstance(kern, dict) else kern).ndim == 3:
        return jax.vmap(lambda k: _kernel(k, lower))(kern)
    return _kernel(kern, lower)


@functools.partial(jax.jit, static_argnames=("hf_t", "served", "lower"))
def _layer(xs, root, layer, hf_t, served, lower):
    """xs [N, S, D]: every sequence through layer `layer`, one after the
    other, with that layer's weights made here from the key."""
    lw = weights.layer_leaves(weights.layer_key(root, layer), dict(hf_t),
                              served)
    kern = {n: _float32(v["kernel"], lower)
            for part in (lw["attn"], lw["moe"]["experts"])
            for n, v in part.items() if "kernel" in v}
    return jax.lax.map(lambda x: _layer_one(x, lw, kern, hf_t), xs)


@functools.partial(jax.jit, static_argnames=("hf_t", "served"))
def _top(root, hf_t, served):
    return weights.top_leaves(root, dict(hf_t), served)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head_one(x, final_scale, head, eps, lower):
    """x [T, D] -> logits [T, V]."""
    return _rms_norm(x, final_scale, eps) @ _kernel(head, lower)


def logits_at(seed: int, hf: dict, served: str, sequences, positions,
              lower="", pad_len: int = 0, pad_pos: int = 0):
    """For each sequence, float32 logits [len(positions[i]), V] at the given
    positions (position p predicts token p+1), as host arrays. Sequences
    are right-padded to one length, the longest's or `pad_len`, positions to
    `pad_pos`, so that every run of a cell compiles the same shapes."""
    hf_t = tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool))))
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
        for layer in range(weights.shapes(hf)["L"]):
            xs = _layer(xs, root, jnp.int32(layer), hf_t, served, lower)
        for i, pos in enumerate(positions):
            idx = np.zeros((T,), np.int32)
            idx[:len(pos)] = pos
            lg = _head_one(xs[i][jnp.asarray(idx)], top["final_norm"]["scale"],
                           top["lm_head"]["kernel"],
                           float(hf["rms_norm_eps"]), lower)
            out.append(np.asarray(lg[:len(pos)]))
    return out
