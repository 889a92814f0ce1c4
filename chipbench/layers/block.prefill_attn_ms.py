"""Programs, by block: attention in a prefill = median over the traced
`prefill_install*` executions (all buckets pooled, as `prog.prefill_call_ms`
pools them) of the summed seconds of the device ops traced under `blk.attn`,
ms a call. Nothing where the trace names no block: a program without the
scopes, or an executable compiled before them (never 0). A prefill program
that holds no Pallas kernel has the same compile-cache key with and without
the scopes (they are metadata): served from a cache another tree filled it
names no block, and this reads nothing while the decode readers read."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.block_ms(ctx, "prefill_install", "attn", per_call=1)
