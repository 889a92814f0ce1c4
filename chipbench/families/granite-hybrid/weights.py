"""Seeded weights of the granite-hybrid family (HF `granitemoehybrid`,
dense): the tree the program's `granite_hybrid` family takes, layers of a
kind stacked (`mamba`, `attn`, `mlp`), which the default tree of
`chipbench/weights.py` cannot make. A pure function of (--seed,
config.json, served type), made on the device in one jitted call; a
layer's leaves are one function of one key (its mixer's and its MLP's), so
the reference beside this file makes the same numbers a layer at a time.

Kernels have variance 1/fan_in in their served form (`chipbench.weights.
_kernel`), norm scales 1 + 0.1 N(0, 1). The embedding's rows have norm
1 / `embedding_multiplier`, so that the hidden state a layer first sees,
E[tok] x `embedding_multiplier`, has the unit norm an embedding row has in
the default family. Rows of norm 1 would not do here: times 12 and through
the tied head they put each position's own input token six sigma clear of
every other logit, and reference, control and program all answer the input
token whatever the 40 layers compute. At this scale the 80 residual
branches, 36 of them the recurrence, decide the logits, whose spread is
then 1 / (`embedding_multiplier` x `logits_scaling`), about 0.01: the
cell's limits are that much smaller than the other cells'.

The recurrence's own parameters are drawn as the published initialisation
draws them, so that the seeded model remembers over a spread of lengths as
a trained one does: dt_bias is the inverse softplus of a step log-uniform
in [0.001, 0.1], A_log the log of a uniform in [1, 16], D = 1 + 0.1 N(0, 1);
all three float32, as the program keeps them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _chunked_rows, _kernel, layer_key, root_key  # noqa: F401

_LEAF_IDS = {"embed": 1, "final_norm": 3}
_MIXER_IDS = {"norm": 1, "in_proj": 2, "conv": 3, "conv_bias": 4,
              "dt_bias": 5, "A_log": 6, "D": 7, "gate_norm": 8,
              "out_proj": 9, "dt_proj": 14, "q_proj": 10, "k_proj": 11, "v_proj": 12,
              "o_proj": 13}
_MLP_IDS = {"norm": 20, "in_proj": 21, "out_proj": 22}


def shapes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    kinds = tuple(hf["layer_types"])
    return dict(D=hf["hidden_size"], L=hf["num_hidden_layers"], kinds=kinds,
                mamba=tuple(i for i, k in enumerate(kinds) if k == "mamba"),
                attn=tuple(i for i, k in enumerate(kinds)
                           if k == "attention"),
                n_q=heads, n_kv=hf["num_key_value_heads"], hd=hd,
                H=H, P=P, N=N, K=H * P, C=H * P + 2 * N,
                W=hf["mamba_d_conv"], F=hf["shared_intermediate_size"],
                V=hf["vocab_size"])


def _norm(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def mamba_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One Mamba-2 mixer's leaves, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _MIXER_IDS.items()}
    step = jnp.exp(jax.random.uniform(
        k["dt_bias"], (s["H"],), jnp.float32,
        jnp.log(0.001), jnp.log(0.1)))
    return {
        "norm": {"scale": _norm(k["norm"], s["D"])},
        # [z | xBC] and dt: the published in_proj's columns, as the
        # program holds them (two kernels)
        "in_proj": {"kernel": _kernel(
            k["in_proj"], (s["D"], s["K"] + s["C"]), s["D"], served)},
        "dt_proj": {"kernel": _kernel(
            k["dt_proj"], (s["D"], s["H"]), s["D"], served)},
        "conv": {"kernel": (jax.random.normal(
                     k["conv"], (s["W"], s["C"]), jnp.float32)
                     * (s["W"] ** -0.5)).astype(jnp.bfloat16),
                 "bias": (0.1 * jax.random.normal(
                     k["conv_bias"], (s["C"],), jnp.float32)
                     ).astype(jnp.bfloat16)},
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(
            k["A_log"], (s["H"],), jnp.float32, 1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(k["D"], (s["H"],), jnp.float32),
        "gate_norm": {"scale": _norm(k["gate_norm"], s["K"])},
        "out_proj": {"kernel": _kernel(k["out_proj"], (s["K"], s["D"]),
                                       s["K"], served)},
    }


def attn_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One attention mixer's leaves, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _MIXER_IDS.items()}
    Hq, Hkv = s["n_q"] * s["hd"], s["n_kv"] * s["hd"]

    def proj(name, n_in, n_out):
        return {"kernel": _kernel(k[name], (n_in, n_out), n_in, served)}

    return {"norm": {"scale": _norm(k["norm"], s["D"])},
            "q_proj": proj("q_proj", s["D"], Hq),
            "k_proj": proj("k_proj", s["D"], Hkv),
            "v_proj": proj("v_proj", s["D"], Hkv),
            "o_proj": proj("o_proj", Hq, s["D"])}


def mlp_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One layer's SwiGLU MLP, unstacked: [gate | up] in one kernel."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _MLP_IDS.items()}
    return {"norm": {"scale": _norm(k["norm"], s["D"])},
            "in_proj": {"kernel": _kernel(
                k["in_proj"], (s["D"], 2 * s["F"]), s["D"], served)},
            "out_proj": {"kernel": _kernel(
                k["out_proj"], (s["F"], s["D"]), s["F"], served)}}


def top_leaves(root: jax.Array, hf: dict, served: str) -> dict:
    """Embedding (the tied head) and final norm."""
    s = shapes(hf)
    ke, kn = (jax.random.fold_in(root, _LEAF_IDS[n])
              for n in ("embed", "final_norm"))
    return {
        "embed": {"embedding": _chunked_rows(
            ke, s["V"], s["D"],
            lambda kk, shp: (jax.random.normal(kk, shp, jnp.float32)
                             * (s["D"] ** -0.5 / hf["embedding_multiplier"])
                             ).astype(jnp.bfloat16))},
        "final_norm": {"scale": _norm(kn, s["D"])},
    }


def _build(hf: dict, served: str):
    s = shapes(hf)

    def stack(fn, layers):
        return jax.lax.map(fn, jnp.asarray(list(layers), jnp.int32))

    def build(root):
        tree = top_leaves(root, hf, served)
        tree["mamba"] = stack(lambda l: mamba_leaves(
            layer_key(root, l), hf, served), s["mamba"])
        tree["attn"] = stack(lambda l: attn_leaves(
            layer_key(root, l), hf, served), s["attn"])
        tree["mlp"] = stack(lambda l: mlp_leaves(
            layer_key(root, l), hf, served), range(s["L"]))
        return tree

    return build


def make_params(seed: int, hf: dict, served: str, out_shardings=None) -> dict:
    """The whole tree in one jitted call, layers of a kind stacked on a
    leading axis in layer order."""
    fn = jax.jit(_build(hf, served), out_shardings=out_shardings)
    return jax.block_until_ready(fn(root_key(seed)))


def param_shapes(hf: dict, served: str):
    """ShapeDtypeStructs of make_params' tree (for shardings and sizing)."""
    return jax.eval_shape(_build(hf, served), jax.random.PRNGKey(0))
