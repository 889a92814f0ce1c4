"""Seeded weights of the power-retention family (HF `brumby`): the tree the
program's `power_retention` family takes, layers stacked under `layers`,
which the default tree of `chipbench/weights.py` lacks three leaves of (the
per-head q and k norms and the gate). A pure function of (--seed,
config.json, served type), made on the device in one jitted call; a layer's
leaves are one function of one key, so the reference beside this file makes
the same numbers a layer at a time.

Kernels have variance 1/fan_in (`chipbench.weights._kernel`), norm scales
1 + 0.1 N(0, 1), embedding rows norm 1. The gate is drawn so that a seeded
model forgets as slowly as a trained one: its kernel W_g is a tenth of the
usual scale (the pre-activation moves by ~0.1 with the token) and its bias
b_g, float32, is uniform in [2.2, 6.9], so sigmoid(W_g h + b_g) lies in about
0.9 to 0.999 and the eight KV heads of a layer remember over 10 to 1000
tokens. With no bias a seeded gate sits at 0.5 and the state forgets in a
few tokens: no run would then test the carry over a long prompt.
The program's family serves bfloat16 alone; the int8 form of the tree is made
as the default family makes it (the gate's kernel stays bfloat16, as the norms
do), for the contract's sake, and `bytes.py` refuses to count it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import (  # noqa: F401
    _chunked_rows, _kernel, layer_key, root_key, top_leaves)

_LAYER_LEAF_IDS = {"q_proj": 1, "k_proj": 2, "v_proj": 3, "o_proj": 4,
                   "gate_proj": 5, "up_proj": 6, "down_proj": 7,
                   "input_norm": 8, "post_attn_norm": 9, "q_norm": 10,
                   "k_norm": 11, "g_proj": 12, "g_bias": 13}
GATE_KERNEL_SCALE = 0.1
GATE_BIAS = (2.2, 6.9)


def shapes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    return dict(D=hf["hidden_size"], L=hf["num_hidden_layers"], n_q=heads,
                n_kv=hf["num_key_value_heads"], hd=hd,
                F=hf["intermediate_size"], V=hf["vocab_size"])


def _norm(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def layer_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One decoder layer's leaves, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_LEAF_IDS.items()}
    Hq, Hkv = s["n_q"] * s["hd"], s["n_kv"] * s["hd"]

    def proj(name, n_in, n_out):
        return {"kernel": _kernel(k[name], (n_in, n_out), n_in, served)}

    gate = _kernel(k["g_proj"], (s["D"], s["n_kv"]), s["D"], "bfloat16")
    return {
        "input_norm": {"scale": _norm(k["input_norm"], s["D"])},
        "q_proj": proj("q_proj", s["D"], Hq),
        "k_proj": proj("k_proj", s["D"], Hkv),
        "v_proj": proj("v_proj", s["D"], Hkv),
        "q_norm": {"scale": _norm(k["q_norm"], s["hd"])},
        "k_norm": {"scale": _norm(k["k_norm"], s["hd"])},
        "g_proj": {"kernel": (gate.astype(jnp.float32) * GATE_KERNEL_SCALE
                              ).astype(jnp.bfloat16),
                   "bias": jax.random.uniform(
                       k["g_bias"], (s["n_kv"],), jnp.float32, *GATE_BIAS)},
        "o_proj": proj("o_proj", Hq, s["D"]),
        "post_attn_norm": {"scale": _norm(k["post_attn_norm"], s["D"])},
        "gate_proj": proj("gate_proj", s["D"], s["F"]),
        "up_proj": proj("up_proj", s["D"], s["F"]),
        "down_proj": proj("down_proj", s["F"], s["D"]),
    }


def _build(hf: dict, served: str):
    L = shapes(hf)["L"]

    def build(root):
        tree = top_leaves(root, hf, served)
        tree["layers"] = jax.lax.map(
            lambda l: layer_leaves(layer_key(root, l), hf, served),
            jnp.arange(L, dtype=jnp.int32))
        return tree

    return build


def make_params(seed: int, hf: dict, served: str, out_shardings=None) -> dict:
    """The whole tree in one jitted call, layers stacked on a leading axis."""
    fn = jax.jit(_build(hf, served), out_shardings=out_shardings)
    return jax.block_until_ready(fn(root_key(seed)))


def param_shapes(hf: dict, served: str):
    """ShapeDtypeStructs of make_params' tree (for shardings and sizing)."""
    return jax.eval_shape(_build(hf, served), jax.random.PRNGKey(0))
