"""Engine runtime configuration."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..common.types import InstanceType
from ..models.base import ModelConfig, tiny_config
from ..parallel.mesh import MeshConfig


def prefill_bucket_ladder(max_seq_len: int) -> tuple[int, ...]:
    """The serving agent's prefill buckets for a context limit: a pow2
    ladder topped by the limit itself. A prompt pads to the next bucket,
    so a sparse ladder doubles typical prefill compute (a 256-token prompt
    in a 512 bucket runs 2x the positions); boot compiles amortize via the
    persistent compile cache."""
    return tuple(sorted({b for b in (128, 256, 512, 1024, 2048)
                         if b < max_seq_len} | {max_seq_len}))


@dataclass
class EngineConfig:
    model_id: str = "tiny-llama"
    model_family: str = "llama"
    model: ModelConfig = field(default_factory=tiny_config)
    mesh: Optional[MeshConfig] = None      # None = all local devices on TP
    # First device index for this engine's mesh: lets several instances
    # on one host (or one virtual test topology) occupy DISJOINT device
    # groups — e.g. a PD pair placed on separate sub-meshes of a pod
    # slice, the reference's engines-pinned-to-GPU-sets analog.
    mesh_device_offset: int = 0
    role: InstanceType = InstanceType.MIX
    # KV pool. Page 0 is reserved as the garbage page (inactive batch slots
    # write there), so usable pages = num_pages - 1.
    num_pages: int = 256
    page_size: int = 16
    # Prefix-cache block size for global-index hashing (must match the
    # service's block_size, reference `global_gflags.cpp:114-116`).
    hash_block_size: int = 128
    # Batching.
    max_batch_size: int = 8
    max_seq_len: int = 2048
    prefill_buckets: tuple[int, ...] = (128, 512, 2048)
    # Sampling.
    max_top_logprobs: int = 5
    seed: int = 0
    # Chunked prefill: prompts longer than this are written to the KV pool
    # in chunks of this many tokens across engine iterations, so running
    # decodes keep streaming while a long prompt prefills. 0 disables
    # (whole-suffix prefill in one program call). Must be page-aligned.
    prefill_chunk_tokens: int = 0
    # How many chunked prefills may be in flight at once (advanced
    # round-robin, one chunk per engine step): >1 keeps several long
    # prompts progressing fairly; short prompts always admit past them.
    max_concurrent_prefills: int = 2
    # Decode horizon: tokens generated per host roundtrip (lax.scan inside
    # one jit call). 1 = lowest streaming latency; larger values amortize
    # dispatch + transfer overhead. Tokens past a stop condition within a
    # horizon are discarded on the host.
    decode_horizon: int = 1
    # While requests are WAITING (or a chunked prefill is in flight) when a
    # decode call is dispatched, the call shrinks to this many tokens so
    # the next admission is not a whole decode_horizon away. What it can
    # do: bound the wait of a request that admission could NOT take this
    # iteration (no free slot, no pages, chunk capacity) and pace a
    # chunked prefill's rides. What it cannot do: shorten the wait of an
    # arrival on an instance with room. Admission runs before decode and
    # empties the queue, so the test finds nothing waiting, and the call
    # such an arrival waits for was dispatched before it arrived. That
    # wait is the rest of the running call (the pump no longer queues a
    # second call behind it: `look_ahead_pays`, engine.py). 0 disables;
    # pow2 (compile variants already exist).
    admission_horizon: int = 8
    # Pre-compile every power-of-two decode horizon (and the spec-verify
    # program) at engine start. The budget-bounded horizon's first use of
    # each value otherwise compiles mid-serving (~tens of seconds on TPU —
    # a latency spike for whoever is streaming at that moment). Off by
    # default to keep CPU test startup fast; the agent CLI enables it on
    # accelerator backends.
    warmup_programs: bool = False
    # Speculative decoding (prompt-lookup / n-gram drafts, verified in a
    # batched multi-token forward; greedy-exact). 0 disables. Eligibility
    # is PER SLOT, decided on device: plain-greedy slots (no penalties,
    # logprobs, or bias) verify drafts; every other slot takes a normal
    # sampled single-token step inside the SAME program, so one sampled
    # request no longer disables speculation for its greedy neighbors
    #. Draft proposal is also device-side (n-gram
    # match over the device-resident history buffer), and
    # `speculate_cycles` propose+verify cycles run per host roundtrip
    # under one lax.scan — the spec analog of decode_horizon.
    # The engine takes the speculative path whenever at least one running
    # slot is spec-eligible; with none, the plain decode horizon is used.
    speculate_k: int = 0
    speculate_ngram: int = 3
    speculate_cycles: int = 4
    # --- Tiered KV cache (engine/kv_tier.py) ---
    # Host-RAM tier capacity for evicted prefix blocks (bytes; 0 disables
    # tiering entirely). Evictions offload HBM→DRAM asynchronously and
    # prefix-matching admissions onload them back ahead of prefill.
    kv_tier_dram_bytes: int = 0
    # Disk spill tier (bytes; 0 = DRAM-only). DRAM overflow demotes
    # LRU-first into an mmap'd spill file with per-block checksums.
    # Requires kv_tier_dram_bytes > 0 — offloads land in the DRAM arena
    # first, SSD holds its overflow (SSD-only is ignored, with a warning).
    kv_tier_ssd_bytes: int = 0
    # Spill file path ("" = a tempfile owned, and unlinked, by the store).
    kv_tier_ssd_path: str = ""
    # Bounded transfer executor: worker threads moving blocks between
    # device and the host tiers, and the hard cap on in-flight offloads
    # (saturation DROPS further offloads — the decode loop never queues
    # behind tier I/O).
    kv_tier_threads: int = 2
    kv_tier_max_inflight: int = 8
    # Sequence/context parallelism (SURVEY.md §5.7): when the engine's mesh
    # has a `seq` axis of size > 1, uncached prompts whose suffix is at
    # least this many tokens prefill with ring attention sharded over that
    # axis (blockwise ring over ICI; ops/ring_attention.py). Shorter or
    # prefix-cached prompts use the standard path.
    seq_parallel_min_tokens: int = 1024

    @property
    def pages_per_seq(self) -> int:
        return self.max_seq_len // self.page_size

    def validate(self) -> None:
        if self.max_seq_len % self.page_size:
            raise ValueError("max_seq_len must be a multiple of page_size")
        if self.hash_block_size % self.page_size:
            raise ValueError("hash_block_size must be a multiple of page_size")
        if self.max_seq_len > self.model.max_context_len:
            raise ValueError("max_seq_len exceeds model max_context_len")
        if not all(b % self.page_size == 0 for b in self.prefill_buckets):
            raise ValueError("prefill buckets must be page-aligned")
        if self.prefill_buckets != tuple(sorted(self.prefill_buckets)):
            raise ValueError("prefill buckets must be ascending")
        if self.prefill_buckets[-1] < self.max_seq_len:
            raise ValueError("largest prefill bucket must cover max_seq_len")
        if self.prefill_chunk_tokens % self.page_size:
            raise ValueError("prefill_chunk_tokens must be page-aligned")
