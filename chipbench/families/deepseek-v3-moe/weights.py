"""Seeded weights of the deepseek-v3-moe family (HF `deepseek_v3` without a
low-rank query): the tree the program's `deepseek_moe` family takes, which
the default tree of `chipbench/weights.py` cannot make: latent-attention
leaves stacked over every layer under `layers`, the leading dense layers'
SwiGLU under `dense_mlp`, and under `moe`, stacked over the expert layers,
the router (kernel and the choice-only bias, both float32), the
`[E, in, out]` expert stacks and the shared expert. A pure function of
(--seed, config.json, served type), made on the device in one jitted call;
a layer's leaves are one function of one key, so the reference beside this
file makes the same numbers a layer at a time.

Kernels have variance 1/fan_in in their served form (`chipbench.weights.
_kernel`), norm scales 1 + 0.1 N(0, 1), embedding rows norm 1. The router's
kernel is float32 with variance 1/hidden, so its logits are about N(0, 1)
and the sigmoid scores of the six chosen of 128 lie in 0.85-0.97, a few
hundredths apart. The bias (`e_score_correction_bias`) is BIAS_SIGMA N(0, 1)
in float32: of the size of those gaps, so that it changes some choices and
not all (zeros would let a program that ignores it pass; a tenth would make
the choice the bias's and the routing uneven). The benchmark serves this
family in bfloat16 (the program's grouped expert product takes no int8
stacks); the int8 form is made as the default family makes it, for the
contract's sake.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import _chunked_rows, _kernel, layer_key, root_key  # noqa: F401

BIAS_SIGMA = 0.01

_LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3}
_LAYER_IDS = {"input_norm": 1, "q_proj": 2, "kv_down": 3, "k_rope": 4,
              "kv_norm": 5, "k_up": 6, "v_up": 7, "o_proj": 8,
              "post_attn_norm": 9, "gate_proj": 10, "up_proj": 11,
              "down_proj": 12, "router": 13, "router_bias": 14,
              "shared_gate": 15, "shared_up": 16, "shared_down": 17}


def shapes(hf: dict) -> dict:
    return dict(
        D=hf["hidden_size"], L=hf["num_hidden_layers"],
        Ld=hf["first_k_dense_replace"], H=hf["num_attention_heads"],
        dn=hf["qk_nope_head_dim"], dr=hf["qk_rope_head_dim"],
        dc=hf["kv_lora_rank"], dv=hf["v_head_dim"],
        F=hf["intermediate_size"], E=hf["n_routed_experts"],
        K=hf["num_experts_per_tok"], Fe=hf["moe_intermediate_size"],
        Fs=hf["moe_intermediate_size"] * hf["n_shared_experts"],
        V=hf["vocab_size"])


def _norm(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def _stack(key, n: int, shape, fan_in: int, served: str):
    """`n` kernels of `shape`, one key each (experts, heads)."""
    return jax.vmap(lambda kk: _kernel(kk, shape, fan_in, served))(
        jax.random.split(key, n))


def attn_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One layer's latent attention and its two norms, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_IDS.items()}
    D, H, dn, dr, dc, dv = (s[x] for x in ("D", "H", "dn", "dr", "dc", "dv"))

    def proj(name, n_in, n_out):
        return {"kernel": _kernel(k[name], (n_in, n_out), n_in, served)}

    return {
        "input_norm": {"scale": _norm(k["input_norm"], D)},
        "q_proj": proj("q_proj", D, H * (dn + dr)),
        "kv_down": proj("kv_down", D, dc),
        "k_rope": proj("k_rope", D, dr),
        "kv_norm": {"scale": _norm(k["kv_norm"], dc)},
        # per head: key_nope = c @ k_up[h].T (held [dn, dc], as the
        # program absorbs it into the query; its fan-in is dc all the
        # same), value = c @ v_up[h]
        "k_up": {"kernel": _stack(k["k_up"], H, (dn, dc), dc, served)},
        "v_up": {"kernel": _stack(k["v_up"], H, (dc, dv), dc, served)},
        "o_proj": proj("o_proj", H * dv, D),
        "post_attn_norm": {"scale": _norm(k["post_attn_norm"], D)},
    }


def dense_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """A leading dense layer's SwiGLU, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_IDS.items()}
    return {
        "gate_proj": {"kernel": _kernel(k["gate_proj"], (s["D"], s["F"]),
                                        s["D"], served)},
        "up_proj": {"kernel": _kernel(k["up_proj"], (s["D"], s["F"]),
                                      s["D"], served)},
        "down_proj": {"kernel": _kernel(k["down_proj"], (s["F"], s["D"]),
                                        s["F"], served)}}


def moe_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """An expert layer's router, expert stacks and shared expert,
    unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_IDS.items()}
    D, E, Fe, Fs = s["D"], s["E"], s["Fe"], s["Fs"]
    return {
        # float32 and never quantised, as the program keeps them
        "router": {
            "kernel": jax.random.normal(k["router"], (D, E), jnp.float32)
            * (D ** -0.5),
            "bias": BIAS_SIGMA * jax.random.normal(
                k["router_bias"], (E,), jnp.float32)},
        "experts": {
            "gate_proj": {"kernel": _stack(k["gate_proj"], E, (D, Fe), D,
                                           served)},
            "up_proj": {"kernel": _stack(k["up_proj"], E, (D, Fe), D,
                                         served)},
            "down_proj": {"kernel": _stack(k["down_proj"], E, (Fe, D), Fe,
                                           served)}},
        "shared": {
            "gate_proj": {"kernel": _kernel(k["shared_gate"], (D, Fs), D,
                                            served)},
            "up_proj": {"kernel": _kernel(k["shared_up"], (D, Fs), D,
                                          served)},
            "down_proj": {"kernel": _kernel(k["shared_down"], (Fs, D), Fs,
                                            served)}},
    }


def top_leaves(root: jax.Array, hf: dict, served: str) -> dict:
    """Embedding, final norm and the untied output head."""
    s = shapes(hf)
    ke, kn, kh = (jax.random.fold_in(root, _LEAF_IDS[n])
                  for n in ("embed", "final_norm", "lm_head"))

    def rows(key, n_rows, n_cols):
        return _chunked_rows(
            key, n_rows, n_cols,
            lambda kk, shp: (jax.random.normal(kk, shp, jnp.float32)
                             * (s["D"] ** -0.5)).astype(jnp.bfloat16))

    return {"embed": {"embedding": rows(ke, s["V"], s["D"])},
            "final_norm": {"scale": _norm(kn, s["D"])},
            "lm_head": {"kernel": rows(kh, s["D"], s["V"])
                        if served == "bfloat16" else
                        _kernel(kh, (s["D"], s["V"]), s["D"], served)}}


def _build(hf: dict, served: str):
    s = shapes(hf)
    if hf.get("tie_word_embeddings"):
        raise ValueError("this family's head is untied")

    def build(root):
        def stack(fn, layers):
            return jax.lax.map(
                lambda l: fn(layer_key(root, l), hf, served),
                jnp.asarray(list(layers), jnp.int32))

        tree = top_leaves(root, hf, served)
        tree["layers"] = stack(attn_leaves, range(s["L"]))
        if s["Ld"]:
            tree["dense_mlp"] = stack(dense_leaves, range(s["Ld"]))
        tree["moe"] = stack(moe_leaves, range(s["Ld"], s["L"]))
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
