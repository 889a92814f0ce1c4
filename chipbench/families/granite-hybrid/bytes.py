"""Bytes the granite-hybrid family must move, from its config.json alone
(README, "A family"), and the counts of its own kernel for the readers it
brings (`chipbench/layers/kernel.ssm_update_*.py`)."""

from __future__ import annotations

LANES = 128


def _counts(hf: dict) -> dict:
    kinds = hf["layer_types"]
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    return dict(D=hf["hidden_size"], Lm=kinds.count("mamba"),
                La=kinds.count("attention"), L=len(kinds), hd=hd,
                q=heads * hd, kv=hf["num_key_value_heads"] * hd,
                n_kv=hf["num_key_value_heads"], H=H, K=H * P, N=N,
                C=H * P + 2 * N, W=hf["mamba_d_conv"],
                F=hf["shared_intermediate_size"], V=hf["vocab_size"])


def decode_weight_stream_bytes(hf: dict, served: str) -> int:
    """Every kernel, norm and recurrence parameter of every layer and the
    [vocab, hidden] tied head, once per step whatever the batch; the
    embedding lookup is a gather of a few rows of the same matrix and is
    left out. bfloat16 only: the program's family serves no int8."""
    if served != "bfloat16":
        raise ValueError(f"served type {served!r}: this family is bfloat16")
    c = _counts(hf)
    mamba = (2 * (c["D"] * (c["K"] + c["C"] + c["H"]) + c["K"] * c["D"]
                  + c["W"] * c["C"] + c["C"] + c["D"] + c["K"])
             + 3 * 4 * c["H"])                  # dt_bias, A_log, D: float32
    attn = 2 * (c["D"] * (c["q"] + 2 * c["kv"]) + c["q"] * c["D"] + c["D"])
    mlp = 2 * (c["D"] * 2 * c["F"] + c["F"] * c["D"] + c["D"])
    return (c["Lm"] * mamba + c["La"] * attn + c["L"] * mlp
            + 2 * c["D"] + 2 * c["V"] * c["D"])


def kv_bytes_per_token(hf: dict) -> int:
    """What one token of context holds in the pool: keys and values of
    the ATTENTION layers alone, each held at the lane width (a 64-wide
    head is stored as 128 of which 64 are zeros), bfloat16. The zeros
    count: the paged-attention kernel reads them."""
    c = _counts(hf)
    held = -(-c["hd"] // LANES) * LANES
    return 2 * c["La"] * c["n_kv"] * held * 2


def ssm_state_bytes_per_slot(hf: dict) -> int:
    """The float32 recurrent state one sequence holds over all its Mamba
    layers: layers x state size x (heads x head dim) x 4."""
    c = _counts(hf)
    return c["Lm"] * c["N"] * c["K"] * 4


def ssm_update_bytes(hf: dict, n_live: int) -> int:
    """What the `_ssm_update_impl` kernel calls of ONE decode step (one
    call a Mamba layer) must move for `n_live` live slots: each slot's
    state read once and written once, and its operands (decay and dt*x
    rows, B and C, all float32) read and y written."""
    c = _counts(hf)
    operands = c["Lm"] * 4 * (3 * c["K"] + 2 * c["N"])
    return n_live * (2 * ssm_state_bytes_per_slot(hf) + operands)
