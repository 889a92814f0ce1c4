"""Seeded weights of the toy sparse-experts family: the tree that the
program's `mixtral` family takes (`models/deepseek_moe.init_params` with no
latent attention, no shared expert and no dense layer: attention leaves
stacked under `layers`, router and `[L, E, in, out]` expert stacks under
`moe`), which the default tree of `chipbench/weights.py` cannot make. A
pure function of (--seed, config.json, served type), made on the device in
one jitted call; one layer's leaves are one function of one key, so the
reference beside this file makes the same numbers a layer at a time.

It proves that a family lands as files (README, "A family") and is no
benchmark configuration.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _kernel, layer_key, root_key  # noqa: F401

_LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3}
_LAYER_LEAF_IDS = {"q_proj": 1, "k_proj": 2, "v_proj": 3, "o_proj": 4,
                   "gate_proj": 5, "up_proj": 6, "down_proj": 7,
                   "input_norm": 8, "post_attn_norm": 9, "router": 10}


def shapes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    return dict(D=hf["hidden_size"], L=hf["num_hidden_layers"], hd=hd,
                n_q=heads, n_kv=hf["num_key_value_heads"],
                F=hf["intermediate_size"], E=hf["num_local_experts"],
                K=hf["num_experts_per_tok"], V=hf["vocab_size"])


def _norm(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def layer_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One layer's leaves, unstacked: `attn` goes under the tree's `layers`,
    `moe` under its `moe`."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_LEAF_IDS.items()}
    Hq, Hkv = s["n_q"] * s["hd"], s["n_kv"] * s["hd"]

    def proj(name, n_in, n_out):
        return {"kernel": _kernel(k[name], (n_in, n_out), n_in, served)}

    def experts(name, n_in, n_out):
        return {"kernel": jax.vmap(
            lambda kk: _kernel(kk, (n_in, n_out), n_in, served))(
                jax.random.split(k[name], s["E"]))}

    return {
        "attn": {
            "input_norm": {"scale": _norm(k["input_norm"], s["D"])},
            "q_proj": proj("q_proj", s["D"], Hq),
            "k_proj": proj("k_proj", s["D"], Hkv),
            "v_proj": proj("v_proj", s["D"], Hkv),
            "o_proj": proj("o_proj", Hq, s["D"]),
            "post_attn_norm": {"scale": _norm(k["post_attn_norm"], s["D"])},
        },
        "moe": {
            # float32 and never quantised, as the program keeps it
            "router": {"kernel": jax.random.normal(
                k["router"], (s["D"], s["E"]), jnp.float32)
                * (s["D"] ** -0.5)},
            "experts": {"gate_proj": experts("gate_proj", s["D"], s["F"]),
                        "up_proj": experts("up_proj", s["D"], s["F"]),
                        "down_proj": experts("down_proj", s["F"], s["D"])},
        },
    }


def top_leaves(root: jax.Array, hf: dict, served: str) -> dict:
    """Embedding, final norm and the (untied) output head."""
    s = shapes(hf)
    ke, kn, kh = (jax.random.fold_in(root, _LEAF_IDS[n])
                  for n in ("embed", "final_norm", "lm_head"))
    return {
        "embed": {"embedding": (
            jax.random.normal(ke, (s["V"], s["D"]), jnp.float32)
            * (s["D"] ** -0.5)).astype(jnp.bfloat16)},
        "final_norm": {"scale": _norm(kn, s["D"])},
        "lm_head": {"kernel": _kernel(kh, (s["D"], s["V"]), s["D"], served)},
    }


def _build(hf: dict, served: str):
    L = shapes(hf)["L"]

    def build(root):
        tree = top_leaves(root, hf, served)
        stacked = jax.lax.map(
            lambda l: layer_leaves(layer_key(root, l), hf, served),
            jnp.arange(L, dtype=jnp.int32))
        tree["layers"], tree["moe"] = stacked["attn"], stacked["moe"]
        return tree

    return build


def make_params(seed: int, hf: dict, served: str, out_shardings=None) -> dict:
    """The whole tree in one jitted call, layers stacked on a leading axis."""
    fn = jax.jit(_build(hf, served), out_shardings=out_shardings)
    return jax.block_until_ready(fn(root_key(seed)))


def param_shapes(hf: dict, served: str):
    """ShapeDtypeStructs of make_params' tree (for shardings and sizing)."""
    return jax.eval_shape(_build(hf, served), jax.random.PRNGKey(0))
