"""Programs, by block: the routed experts = median over the traced `decode_multi`
executions of the summed seconds of the device ops traced under `blk.moe` /
the configured horizon, ms a step (`layers/blocks.py`: the outermost `blk.*`
of the op_name the profiler wrote for the op's instruction). The block is a
sparse layer's norm, router, dispatch plan, gather, the three grouped products
(`kernel.moe_experts_ms` is their part), un-sort, weighing and residual; the
shared expert is `blk.mlp`'s. Nothing where the trace names no block: a
program without the scopes, or an executable compiled before them (never 0).
Nothing either where the family routes nothing."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, "moe")
