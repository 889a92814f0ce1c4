"""TPU compute ops: attention (dense prefill + paged decode, XLA and Pallas
paths), rotary embeddings, normalization.

The decode paged-attention kernel is the perf-critical op (SURVEY.md §7.3
item 2: "Pallas ragged paged-attention kernel quality drives the tok/s/chip
north star").
"""

from .attention import (
    rms_norm,
    apply_rope,
    prefill_attention,
    paged_attention_xla,
    write_kv,
    decode_attention_step,
)

__all__ = [
    "rms_norm", "apply_rope", "prefill_attention", "paged_attention_xla",
    "write_kv", "decode_attention_step",
]
