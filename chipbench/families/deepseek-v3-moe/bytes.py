"""Bytes and operations the deepseek-v3-moe family must move, from its
config.json alone (README, "A family"), and the counts of its grouped
expert product for the readers it brings
(`chipbench/layers/kernel.moe_experts_*.py`,
`engine.moe_experts_touched_mean.py`)."""

from __future__ import annotations

LANES = 128


def kv_bytes_per_token(hf: dict) -> int:
    """What one token of context holds in the pool: in every layer the
    latent [kv_lora_rank | rope] written once as the key plane and once as
    the value plane, each held at the next lane multiple (576 as 640, of
    which 64 are zeros), bfloat16. The zeros and the second plane count:
    the paged-attention kernel reads them."""
    latent = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    held = -(-latent // LANES) * LANES
    return hf["num_hidden_layers"] * 2 * held * 2


def _non_expert_bytes(hf: dict) -> int:
    """Every kernel and norm outside the routed experts, and the untied
    head: what a step reads whatever its batch. bfloat16 but the router's
    kernel and bias, which are float32; the embedding lookup is a gather of
    a few rows and is left out."""
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    dc, dv = hf["kv_lora_rank"], hf["v_head_dim"]
    attn = 2 * (D * H * (dn + dr) + D * dc + D * dr + H * dn * dc
                + H * dc * dv + H * dv * D + 2 * D + dc)
    dense = 2 * 3 * D * hf["intermediate_size"]
    shared = 2 * 3 * D * hf["moe_intermediate_size"] * hf["n_shared_experts"]
    router = 4 * (D + 1) * hf["n_routed_experts"]
    return (hf["num_hidden_layers"] * attn
            + hf["first_k_dense_replace"] * dense
            + moe_layers(hf) * (shared + router)
            + 2 * D + 2 * D * hf["vocab_size"])


def decode_weight_stream_bytes(hf: dict, served: str) -> int:
    """The FLOOR of what one decode step reads of the weights: everything
    outside the routed experts, and in each expert layer the
    `num_experts_per_tok` experts that one live row chooses. A step with
    more live rows reads more experts (`kernel.moe_experts_weight_bw_pct`
    prices the ones a call touched), so `device.decode_weight_bw_pct` is a
    lower bound here, further under the step's roofline share than in a
    dense family, and cannot pass 100."""
    return (_non_expert_bytes(hf) + moe_layers(hf)
            * hf["num_experts_per_tok"] * moe_expert_bytes(hf, served))


def moe_layers(hf: dict) -> int:
    """Layers that hold routed experts."""
    return hf["num_hidden_layers"] - hf["first_k_dense_replace"]


def moe_expert_bytes(hf: dict, served: str) -> int:
    """One routed expert's three kernels (gate, up, down), as served."""
    if served != "bfloat16":
        raise ValueError(f"served type {served!r}: this family is bfloat16")
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"] * 2


def moe_expert_flops(hf: dict, rows: int) -> int:
    """Operations of the grouped products of ONE expert layer for `rows`
    routed rows: each row through `num_experts_per_tok` experts' three
    kernels, two operations a weight."""
    return (2 * rows * hf["num_experts_per_tok"] * 3 * hf["hidden_size"]
            * hf["moe_intermediate_size"])
