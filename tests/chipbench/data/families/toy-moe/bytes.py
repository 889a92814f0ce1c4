"""Bytes the toy sparse-experts family must read from HBM, from its
config.json alone (README, "A family")."""

from __future__ import annotations

from chipbench.bytes_model import kv_bytes_per_token  # noqa: F401 (dense GQA)


def decode_weight_stream_bytes(hf: dict, served: str):
    """None: how many experts' kernels a step reads depends on the tokens
    of the batch (at most `num_local_experts`, at least
    `num_experts_per_tok`), so the weights set no one floor and the
    roofline reader leaves its metric out."""
    return None
