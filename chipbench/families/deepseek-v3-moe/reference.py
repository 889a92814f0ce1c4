"""The deepseek-v3-moe family's plain reference: the DeepSeek-V3 decoder
without a low-rank query (HF `deepseek_v3`, `q_lora_rank` null, `n_group`
= `topk_group` = 1, no rope scaling) in float32 `jax.numpy` at `highest`
matmul precision, written from the published description (DeepSeek-V3
technical report, sections 2.1.1 and 2.1.2; HF `modeling_deepseek_v3.py`
for the conventions). No kernels, no cache, no paging, no batching, no
dispatch, and the attention in its UN-ABSORBED form: every head's key and
value are made from the latent, where the program folds the key's
up-projection into the query and attends over the latent itself. It
imports nothing of the program; weights come from weights.py beside this
file, a layer at a time, from the same seed.

Per layer (x a token's hidden vector, eps `rms_norm_eps`):
    x += o_proj(MLA(rms(x)));  x += MLP(rms(x));  logits = lm_head(rms(x))
  MLA: c = rms(x W_kv_down) [kv_lora_rank]; k_r = rope(x W_k_rope), one
    head shared by all; q = x W_q as heads of [nope | rope], q_r =
    rope(q[rope]); key of head h = [c W_k_up[h] | k_r], value = c W_v_up[h];
    scores scaled by (nope + rope)**-0.5, causal softmax, heads
    concatenated into o_proj. rope rotates the pairs (2i, 2i+1) by
    pos * theta**(-2i/rope) (`rope_interleave`: the published code
    de-interleaves q and k and rotates halves: the same products).
  MLP: SwiGLU of `intermediate_size` in the first `first_k_dense_replace`
    layers; after them the expert block: s = sigmoid(x_f32 W_router) over
    all experts; chosen = the `num_experts_per_tok` largest of s + b (b the
    `e_score_correction_bias`: it enters the choice only); w = s[chosen] /
    (sum s[chosen] + 1e-20) * `routed_scaling_factor` (`norm_topk_prob`);
    out = sum_e w_e down_e(silu(gate_e x) * up_e x) + the shared expert's
    SwiGLU of width `n_shared_experts` x `moe_intermediate_size`.
The reference routes for itself: its choice of experts is never forced to
the program's.

Departures from the published code, none in mathematics: the group step of
the choice is left out (`n_group` = `topk_group` = 1 make it the identity;
another value is refused); activations stay float32 throughout (the
published code rounds them to bfloat16 between ops; the reference is the
yardstick for that rounding); every expert's output is weighed by a gate
that is zero where it was not chosen, one expert after another. With
`lower` it is the control: the same forward with every kernel and the head
rounded to that type (router and bias stay float32, as the program keeps
them).
"""

from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import _kernel, _pad, _rms_norm

weights = harness.load_file(Path(__file__).with_name("weights.py"))

_KEEP = ("hidden_size", "num_hidden_layers", "num_attention_heads",
         "first_k_dense_replace", "qk_nope_head_dim", "qk_rope_head_dim",
         "kv_lora_rank", "v_head_dim", "intermediate_size",
         "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
         "n_shared_experts", "vocab_size", "rms_norm_eps", "rope_theta",
         "routed_scaling_factor", "norm_topk_prob", "n_group", "topk_group")


def _hf_static(hf: dict) -> tuple:
    for key in ("n_group", "topk_group"):
        if (hf.get(key) or 1) != 1:
            raise ValueError(f"{key}={hf[key]}: the reference leaves the "
                             "group step out")
    if hf.get("q_lora_rank") is not None or hf.get("rope_scaling") is not None:
        raise ValueError("a low-rank query or a rope scaling is not in "
                         "this reference")
    if hf.get("scoring_func") != "sigmoid" or hf.get("topk_method") != "noaux_tc":
        raise ValueError("this reference scores with a sigmoid and chooses "
                         "on score + bias (noaux_tc)")
    return tuple(sorted((k, hf[k]) for k in _KEEP if hf.get(k) is not None))


def _rope_pairs(x, theta):
    """x [S, heads, d]: the pairs (2i, 2i+1) rotated by pos *
    theta**(-2i/d), in place."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(x, lw, kern, hf):
    """x [S, D] (already normed) -> [S, D]."""
    s = weights.shapes(hf)
    H, dn, dr, dv, S = s["H"], s["dn"], s["dr"], s["dv"], x.shape[0]
    theta = hf["rope_theta"]
    c = _rms_norm(x @ kern["kv_down"], lw["kv_norm"]["scale"],
                  hf["rms_norm_eps"])                             # [S, dc]
    k_r = _rope_pairs((x @ kern["k_rope"])[:, None, :], theta)    # [S, 1, dr]
    q = (x @ kern["q_proj"]).reshape(S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], theta)], -1)
    # weights.py holds k_up as [H, dn, dc]: key_nope[h] = c @ k_up[h].T
    k = jnp.concatenate([jnp.einsum("sc,hdc->shd", c, kern["k_up"]),
                         jnp.broadcast_to(k_r, (S, H, dr))], -1)
    v = jnp.einsum("sc,hcv->shv", c, kern["v_up"])
    scores = jnp.einsum("shd,thd->hst", q, k) * ((dn + dr) ** -0.5)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    a = jnp.einsum("hst,thv->shv", jax.nn.softmax(scores, axis=-1), v)
    return a.reshape(S, H * dv) @ kern["o_proj"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(x, moe, hf, lower):
    """x [S, D] (already normed) -> [S, D]: the expert block."""
    s = weights.shapes(hf)
    scores = jax.nn.sigmoid(x @ moe["router"]["kernel"])          # [S, E]
    choice = scores + moe["router"]["bias"][None, :]
    _, chosen = jax.lax.top_k(choice, s["K"])
    picked = jnp.zeros_like(scores, bool).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(True)
    w = jnp.where(picked, scores, 0.0)
    if hf.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * hf["routed_scaling_factor"]

    def one(acc, e):
        gate, up, down = (_kernel(moe["experts"][n]["kernel"][e], lower)
                          for n in ("gate_proj", "up_proj", "down_proj"))
        return acc + w[:, e, None] * _swiglu(x, gate, up, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(s["E"]))
    sh = {n: _kernel(moe["shared"][n]["kernel"], lower)
          for n in ("gate_proj", "up_proj", "down_proj")}
    return out + _swiglu(x, sh["gate_proj"], sh["up_proj"], sh["down_proj"])


def _float32(leaves: dict, lower: str) -> dict:
    out = {}
    for n, v in leaves.items():
        if "kernel" in v:
            kern = v["kernel"]
            out[n] = (jax.vmap(lambda k: _kernel(k, lower))(kern)
                      if kern.ndim == 3 else _kernel(kern, lower))
    return out


@functools.partial(jax.jit,
                   static_argnames=("dense", "hf_t", "served", "lower"))
def _layer(xs, root, layer, dense, hf_t, served, lower):
    """xs [N, S, D]: every sequence through layer `layer` (a leading dense
    layer or an expert layer: one program a kind), one after the other,
    with that layer's weights made here from its key."""
    hf = dict(hf_t)
    key = weights.layer_key(root, layer)
    attn = weights.attn_leaves(key, hf, served)
    attn_k = _float32(attn, lower)
    eps = hf["rms_norm_eps"]
    if dense:
        mlp_k = _float32(weights.dense_leaves(key, hf, served), lower)
    else:
        moe = weights.moe_leaves(key, hf, served)

    def one(x):
        x = x + _attention(_rms_norm(x, attn["input_norm"]["scale"], eps),
                           attn, attn_k, hf)
        h = _rms_norm(x, attn["post_attn_norm"]["scale"], eps)
        if dense:
            return x + _swiglu(h, mlp_k["gate_proj"], mlp_k["up_proj"],
                               mlp_k["down_proj"])
        return x + _experts(h, moe, hf, lower)

    return jax.lax.map(one, xs)


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
    `pad_pos`, so that every run of a cell compiles the same shapes (the
    padding lies behind every real token and the model is causal)."""
    hf_t = _hf_static(hf)
    root = weights.root_key(seed)
    s = weights.shapes(hf)
    S = _pad(max(len(q) for q in sequences), pad_len)
    T = _pad(max(len(p) for p in positions), pad_pos)
    toks = np.zeros((len(sequences), S), np.int32)
    for i, q in enumerate(sequences):
        toks[i, :len(q)] = q
    out = []
    with jax.default_matmul_precision("highest"):
        top = _top(root, hf_t, served)
        xs = top["embed"]["embedding"][jnp.asarray(toks)].astype(jnp.float32)
        for layer in range(s["L"]):
            xs = _layer(xs, root, jnp.int32(layer), layer < s["Ld"], hf_t,
                        served, lower)
        for i, pos in enumerate(positions):
            idx = np.zeros((T,), np.int32)
            idx[:len(pos)] = pos
            lg = _head_one(xs[i][jnp.asarray(idx)], top["final_norm"]["scale"],
                           top["lm_head"]["kernel"],
                           float(hf["rms_norm_eps"]), lower)
            out.append(np.asarray(lg[:len(pos)]))
    return out
