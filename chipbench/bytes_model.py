"""Bytes a dense decoder must read from HBM for one decode step, from its
config.json alone: the default family's `bytes` (harness.Family). The
benchmark's own copy of the dense arithmetic of
`ModelConfig.decode_weight_stream_bytes` (the original is wrong for sparse
experts at batch > 1 and is listed in PERF.md for deletion)."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_weight_stream_bytes(hf: dict, served: str) -> int:
    """Every projection of every layer, the norms, and the [vocab, hidden]
    output head (read in full whether or not it is tied to the embedding),
    once per step whatever the batch. int8 kernels are one byte a weight
    plus a float32 scale per output channel; everything else bfloat16.
    The embedding lookup is a gather of a few rows and is left out, as are
    the keys and values: this is the floor the weights alone set."""
    if served not in ("int8", "bfloat16"):
        raise ValueError(f"served type {served!r}")
    D, L = hf["hidden_size"], hf["num_hidden_layers"]
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or D // heads
    q, kv = heads * hd, hf["num_key_value_heads"] * hd
    F, V = hf["intermediate_size"], hf["vocab_size"]
    tied = bool(hf.get("tie_word_embeddings", False))

    def kernel(n_in, n_out, quantised=True):
        if served == "int8" and quantised:
            return n_in * n_out + 4 * n_out
        return n_in * n_out * 2

    layer = (kernel(D, q) + 2 * kernel(D, kv) + kernel(q, D)
             + 2 * kernel(D, F) + kernel(F, D)
             + (q + 2 * kv) * 2            # q/k/v biases, bfloat16
             + 2 * D * 2)                  # two RMSNorm scales
    # A tied head is the bfloat16 embedding matrix itself.
    head = kernel(D, V, quantised=not tied)
    return L * layer + D * 2 + head


def kv_bytes_per_token(hf: dict) -> int | None:
    """Bytes of keys and values one token of context holds in the pool,
    which has the model's dtype: 2 x layers x kv heads x head dim x
    itemsize. None for a pool type whose size is not known here."""
    itemsize = ITEMSIZE.get(hf.get("torch_dtype"))
    if itemsize is None:
        return None
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * hd
            * itemsize)
