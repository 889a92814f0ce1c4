"""The benchmark's weights: a pure function of (--seed, config.json, served
type), made on the device.

The benchmark owns the weights and hands them to the program the way a
checkpoint loader would (`EngineAgent(params=...)`, the llama-family tree
of `models/llama.py`, weight-only int8 kernels as `{"q8", "scale"}`), so
the plain reference (reference.py) can make the very same numbers again
without taking anything the program has made.

One layer's leaves are one function of one key (`layer_leaves`): the
serving process maps it over the depth inside a single jitted call, the
reference calls it for one layer at a time.

int8 is made as int8: codes from a clipped normal and one float32 scale per
output channel, so that code * scale has the variance 1/fan_in that the
bfloat16 weights have. Nothing is rounded from a wider copy, so there is no
second place where the two sides could round differently.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Codes per standard deviation: the largest of a few thousand normals sits
# near 3.5 sigma, which this puts at the int8 range's end, as per-channel
# absmax quantisation of a trained matrix would.
INT8_CODES_PER_SIGMA = 36.0
ROW_CHUNKS = 64           # big 2-D leaves are made this many rows-blocks at a time

_LEAF_IDS = {"embed": 1, "lm_head": 2, "final_norm": 3, "layers": 4}
_LAYER_LEAF_IDS = {"q_proj": 1, "k_proj": 2, "v_proj": 3, "o_proj": 4,
                   "gate_proj": 5, "up_proj": 6, "down_proj": 7,
                   "input_norm": 8, "post_attn_norm": 9}


def root_key(seed: int) -> jax.Array:
    """--seed may exceed 31 bits; both halves go into the key."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    return dict(D=hf["hidden_size"], L=hf["num_hidden_layers"],
                Hq=heads * hd, Hkv=hf["num_key_value_heads"] * hd,
                F=hf["intermediate_size"], V=hf["vocab_size"], hd=hd,
                tied=bool(hf.get("tie_word_embeddings", False)))


def _kernel(key, shape, fan_in: int, served: str):
    """[in, out] kernel in its served form."""
    kz, ks = jax.random.split(key)
    z = jax.random.normal(kz, shape, jnp.float32)
    if served == "bfloat16":
        return (z * (fan_in ** -0.5)).astype(jnp.bfloat16)
    q8 = jnp.clip(jnp.round(z * INT8_CODES_PER_SIGMA), -127, 127)
    jitter = jax.random.uniform(ks, (shape[-1],), jnp.float32, 0.75, 1.25)
    scale = jitter * ((fan_in ** -0.5) / INT8_CODES_PER_SIGMA)
    return {"q8": q8.astype(jnp.int8), "scale": scale}


def _chunked_rows(key, rows: int, cols: int, fn):
    """[rows, cols] made ROW_CHUNKS blocks of rows at a time, so that the
    float32 normals behind a 152k-row matrix never exist all at once."""
    chunks = math.gcd(rows, ROW_CHUNKS)
    out = jax.lax.map(lambda k: fn(k, (rows // chunks, cols)),
                      jax.random.split(key, chunks))
    return out.reshape(rows, cols)


def layer_leaves(key: jax.Array, hf: dict, served: str) -> dict:
    """One decoder layer's leaves, unstacked."""
    s = shapes(hf)
    k = {n: jax.random.fold_in(key, i) for n, i in _LAYER_LEAF_IDS.items()}

    def norm(kk):
        return (1.0 + 0.1 * jax.random.normal(kk, (s["D"],), jnp.float32)
                ).astype(jnp.bfloat16)

    def proj(name, n_in, n_out, bias):
        out = {"kernel": _kernel(k[name], (n_in, n_out), n_in, served)}
        if bias:
            kb = jax.random.fold_in(k[name], 7)
            out["bias"] = (0.1 * jax.random.normal(kb, (n_out,), jnp.float32)
                           ).astype(jnp.bfloat16)
        return out

    return {
        "input_norm": {"scale": norm(k["input_norm"])},
        "q_proj": proj("q_proj", s["D"], s["Hq"], True),
        "k_proj": proj("k_proj", s["D"], s["Hkv"], True),
        "v_proj": proj("v_proj", s["D"], s["Hkv"], True),
        "o_proj": proj("o_proj", s["Hq"], s["D"], False),
        "post_attn_norm": {"scale": norm(k["post_attn_norm"])},
        "gate_proj": proj("gate_proj", s["D"], s["F"], False),
        "up_proj": proj("up_proj", s["D"], s["F"], False),
        "down_proj": proj("down_proj", s["F"], s["D"], False),
    }


def layer_key(root: jax.Array, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root, _LEAF_IDS["layers"]),
                              layer)


def top_leaves(root: jax.Array, hf: dict, served: str) -> dict:
    """Embedding, final norm and (untied) output head."""
    s = shapes(hf)
    ke = jax.random.fold_in(root, _LEAF_IDS["embed"])
    kn = jax.random.fold_in(root, _LEAF_IDS["final_norm"])
    out = {
        "embed": {"embedding": _chunked_rows(
            ke, s["V"], s["D"],
            lambda kk, shp: (jax.random.normal(kk, shp, jnp.float32)
                             * (s["D"] ** -0.5)).astype(jnp.bfloat16))},
        "final_norm": {"scale": (
            1.0 + 0.1 * jax.random.normal(kn, (s["D"],), jnp.float32)
        ).astype(jnp.bfloat16)},
    }
    if not s["tied"]:
        kh = jax.random.fold_in(root, _LEAF_IDS["lm_head"])
        kz, ks = jax.random.split(kh)
        if served == "bfloat16":
            kern = _chunked_rows(
                kz, s["D"], s["V"],
                lambda kk, shp: (jax.random.normal(kk, shp, jnp.float32)
                                 * (s["D"] ** -0.5)).astype(jnp.bfloat16))
        else:
            q8 = _chunked_rows(
                kz, s["D"], s["V"],
                lambda kk, shp: jnp.clip(jnp.round(
                    jax.random.normal(kk, shp, jnp.float32)
                    * INT8_CODES_PER_SIGMA), -127, 127).astype(jnp.int8))
            jitter = jax.random.uniform(ks, (s["V"],), jnp.float32,
                                        0.75, 1.25)
            kern = {"q8": q8, "scale": jitter * (
                (s["D"] ** -0.5) / INT8_CODES_PER_SIGMA)}
        out["lm_head"] = {"kernel": kern}
    return out


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
