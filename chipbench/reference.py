"""The plain reference: the Qwen2 decoder's forward pass in float32
`jax.numpy` at `highest` matmul precision. No kernels, no cache, no paging,
no continuous batching; written from the published description (Qwen2
technical report; HF `modeling_qwen2.py` for the conventions: pre-norm
RMSNorm, q/k/v biases, half-split rotary embedding with `rope_theta`,
grouped-query causal attention scaled by head_dim**-0.5, SwiGLU, tied or
untied head). It imports nothing of the program. Its weights come from
weights.py, one layer at a time, from the same seed.

What it answers: for sequences prompt+served tokens, at each served token's
position, how far the served token's logit lies below the reference's best
(`gap`), and how far the served top-k log-probabilities lie from the
reference's of the same tokens (`logprob_errors`). With `lower` set to "int8"
or "int4" `logits_at` computes the control: the same forward with weights
rounded to that type, whose first tokens and top-k log-probabilities are then
read in the served ones' place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

PAD_TO = 128


def _dequant(kern):
    if isinstance(kern, dict):
        return kern["q8"].astype(jnp.float32) * kern["scale"][None, :]
    return kern.astype(jnp.float32)


def _round_to(w, lower: str):
    """Per-output-channel absmax rounding of a float32 [in, out] kernel to
    the type `lower` (int8 or int4), returned dequantised."""
    top = {"int8": 127.0, "int4": 7.0}[lower]
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-12) / top
    return jnp.clip(jnp.round(w / scale[None, :]), -top, top) * scale[None, :]


def _kernel(kern, lower: str):
    w = _dequant(kern)
    return _round_to(w, lower) if lower else w


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [S, heads, hd]; half-split rotation by position."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer_one(x, lw, kern, hf_t):
    """x [S, D] float32 -> [S, D]; one sequence through one layer whose
    kernels `kern` are already float32."""
    hf = dict(hf_t)
    s = weights.shapes(hf)
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    n_q, n_kv, hd = s["Hq"] // s["hd"], s["Hkv"] // s["hd"], s["hd"]
    S = x.shape[0]
    h = _rms_norm(x, lw["input_norm"]["scale"], eps)

    def proj(name, v):
        y = v @ kern[name]
        if "bias" in lw[name]:
            y = y + lw[name]["bias"].astype(jnp.float32)
        return y

    q = _rope(proj("q_proj", h).reshape(S, n_q, hd), theta)
    k = _rope(proj("k_proj", h).reshape(S, n_kv, hd), theta)
    v = proj("v_proj", h).reshape(S, n_kv, hd)
    g = n_q // n_kv
    q = q.reshape(S, n_kv, g, hd)
    scores = jnp.einsum("sngh,tnh->ngst", q, k) * (hd ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("ngst,tnh->sngh", p, v).reshape(S, n_q * hd)
    x = x + proj("o_proj", a)
    h = _rms_norm(x, lw["post_attn_norm"]["scale"], eps)
    m = jax.nn.silu(proj("gate_proj", h)) * proj("up_proj", h)
    return x + proj("down_proj", m)


@functools.partial(jax.jit, static_argnames=("hf_t", "served", "lower"))
def _layer(xs, root, layer, hf_t, served, lower):
    """xs [N, S, D]: every sequence through layer `layer`, one after the
    other (lax.map), with that layer's weights made here from the key."""
    lw = weights.layer_leaves(weights.layer_key(root, layer), dict(hf_t),
                              served)
    kern = {n: _kernel(v["kernel"], lower) for n, v in lw.items()
            if "kernel" in v}
    return jax.lax.map(lambda x: _layer_one(x, lw, kern, hf_t), xs)


@functools.partial(jax.jit, static_argnames=("hf_t", "served"))
def _top(root, hf_t, served):
    return weights.top_leaves(root, dict(hf_t), served)


@functools.partial(jax.jit, static_argnames=("eps", "lower", "tied"))
def _head_one(x, final_scale, head, eps, lower, tied):
    """x [T, D] -> logits [T, V]."""
    h = _rms_norm(x, final_scale, eps)
    if tied:
        w = head.astype(jnp.float32).T
        w = _round_to(w, lower) if lower else w
    else:
        w = _kernel(head, lower)
    return h @ w


def _hf_static(hf: dict) -> tuple:
    keep = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "head_dim", "tie_word_embeddings", "rms_norm_eps", "rope_theta")
    return tuple(sorted((k, hf[k]) for k in keep if k in hf))


def _pad(n: int, floor: int = 0) -> int:
    return -(-max(n, floor) // PAD_TO) * PAD_TO


def forward_hidden(seed: int, hf: dict, served: str, sequences, lower="",
                   pad_len: int = 0):
    """Final-layer hidden states [N, S, D] (float32, before the last norm)
    of `sequences` (lists of token ids), right-padded to one length: the
    longest's, or `pad_len` if that is longer (a cell passes its mix's
    longest request, so that every run of the cell compiles the same shapes
    and finds them in the compile cache)."""
    hf_t = _hf_static(hf)
    root = weights.root_key(seed)
    S = _pad(max(len(s) for s in sequences), pad_len)
    toks = np.zeros((len(sequences), S), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        top = _top(root, hf_t, served)
        xs = top["embed"]["embedding"][jnp.asarray(toks)].astype(jnp.float32)
        for layer in range(weights.shapes(hf)["L"]):
            xs = _layer(xs, root, jnp.int32(layer), hf_t, served, lower)
    return xs, top


def logits_at(seed: int, hf: dict, served: str, sequences, positions,
              lower="", pad_len: int = 0, pad_pos: int = 0):
    """For each sequence, float32 logits [len(positions[i]), V] at the given
    positions (position p predicts token p+1). Host arrays."""
    xs, top = forward_hidden(seed, hf, served, sequences, lower, pad_len)
    tied = weights.shapes(hf)["tied"]
    head = (top["embed"]["embedding"] if tied
            else top["lm_head"]["kernel"])
    T = _pad(max(len(p) for p in positions), pad_pos)
    out = []
    with jax.default_matmul_precision("highest"):
        for i, pos in enumerate(positions):
            idx = np.zeros((T,), np.int32)
            idx[:len(pos)] = pos
            lg = _head_one(xs[i][jnp.asarray(idx)],
                           top["final_norm"]["scale"], head,
                           float(hf["rms_norm_eps"]), lower, tied)
            out.append(np.asarray(lg[:len(pos)]))
    return out


def teacher_forced(prompts, outputs):
    """(sequences, positions): prompt + served tokens, and the positions
    whose logits predict the served tokens."""
    seqs = [list(p) + list(o) for p, o in zip(prompts, outputs)]
    pos = [list(range(len(p) - 1, len(p) + len(o) - 1))
           for p, o in zip(prompts, outputs)]
    return seqs, pos


def gap(logits, chosen):
    """logits [T, V], chosen [T] -> the best logit minus the chosen
    token's (>= 0)."""
    chosen = np.asarray(chosen, np.int64)
    return logits.max(-1) - logits[np.arange(len(chosen)), chosen]


def log_softmax(logits):
    x = logits.astype(np.float64)
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


def top_logprobs(logits, k: int):
    """What a server returns with `logprobs: k`: (ids [T, k], values [T, k])
    of the k most likely tokens."""
    lp = log_softmax(logits)
    ids = np.argpartition(-lp, k, axis=-1)[:, :k]
    vals = np.take_along_axis(lp, ids, -1)
    order = np.argsort(-vals, axis=-1, kind="stable")
    return (np.take_along_axis(ids, order, -1),
            np.take_along_axis(vals, order, -1))


def logprob_errors(logits, ids, values):
    """Served (or control) top-k log-probabilities minus the reference's of
    the same tokens: [T, k]."""
    return np.asarray(values, np.float64) - np.take_along_axis(
        log_softmax(logits), np.asarray(ids, np.int64), -1)
