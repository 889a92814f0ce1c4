"""Bytes and operations the power-retention family must move and do, from
its config.json alone (README, "A family"), and the counts of its own two
kernels for the readers it brings (`chipbench/layers/kernel.retention_*.py`).

The state's layout is the program's (`xllm_service_tpu/ops/retention.py`,
restated here, not imported): per sequence, layer and KV head M = d/2 + 1
slabs of d x d float32 and a normaliser of Mz x d, Mz = M rounded up to 8."""

from __future__ import annotations


def _counts(hf: dict) -> dict:
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    M = hd // 2 + 1
    return dict(D=hf["hidden_size"], L=hf["num_hidden_layers"], hd=hd,
                n_q=heads, n_kv=hf["num_key_value_heads"], q=heads * hd,
                kv=hf["num_key_value_heads"] * hd,
                F=hf["intermediate_size"], V=hf["vocab_size"], M=M,
                Mz=-(-M // 8) * 8)


def decode_weight_stream_bytes(hf: dict, served: str) -> int:
    """Every kernel, norm and gate parameter of every layer, the final norm
    and the [hidden, vocab] head, once per step whatever the batch; the
    embedding lookup is a gather of a few rows and is left out. bfloat16
    only: the program's family serves no int8."""
    if served != "bfloat16":
        raise ValueError(f"served type {served!r}: this family is bfloat16")
    c = _counts(hf)
    layer = (2 * (c["D"] * (c["q"] + 2 * c["kv"]) + c["q"] * c["D"]
                  + c["D"] * c["n_kv"] + 3 * c["D"] * c["F"]
                  + 2 * c["D"] + 2 * c["hd"])
             + 4 * c["n_kv"])                           # the gate's bias
    return c["L"] * layer + 2 * c["D"] + 2 * c["D"] * c["V"]


def kv_bytes_per_token(hf: dict):
    """No layer holds keys: a token of context holds nothing in the pool."""
    return None


def retention_state_bytes(hf: dict) -> int:
    """The float32 state one sequence holds over all its layers: layers x
    KV heads x (M slabs of d x d, and the normaliser's Mz x d) x 4."""
    c = _counts(hf)
    return c["L"] * c["n_kv"] * (c["M"] * c["hd"] + c["Mz"]) * c["hd"] * 4


def retention_update_bytes(hf: dict, n_live: int) -> int:
    """What the `_retention_update_impl` calls of ONE decode step (one a
    layer) must move for `n_live` live slots: each slot's state read once
    and written once. Its operands (q, k, v and the gate: a few rows a
    head) are a five-thousandth of that and are left out."""
    return n_live * 2 * retention_state_bytes(hf)


def retention_prefill_flops(hf: dict, tokens: int) -> int:
    """The products the `_retention_prefill_impl` calls of one prefill
    program do on the MXU for `tokens` tokens (the caller says which it
    counts: the kernel multiplies a bucket's padding too), over all layers: per token, layer and KV
    head, phi(q) S for the group's query heads and the state's update
    phi(k) v^T, 2 x M x d x d each. What the kernel does on the vector unit
    (building phi, the decay) and what the program does outside it (the
    masked (Q K^T)^2 inside a sub-chunk; the normaliser and z, a (d, d)
    product a head each) is not counted."""
    c = _counts(hf)
    group = c["n_q"] // c["n_kv"]
    per_head = 2 * c["M"] * c["hd"] * c["hd"] * (group + 1)
    return tokens * c["L"] * c["n_kv"] * per_head
