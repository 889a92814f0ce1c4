"""Programs, by block: attention = median over the traced `decode_multi`
executions of the summed seconds of the device ops traced under `blk.attn` /
the configured horizon, ms a step (`layers/blocks.py`: the outermost `blk.*`
of the op_name the profiler wrote for the op's instruction). The block is the
layer's input norm, the q/k/v projections, the rotary embedding, the page
write, the kernel (`kernel.paged_attn_ms` is its part), the output projection
and its residual; a latent family's down- and up-projections with them.
Nothing where the trace names no block: a program without the scopes, or an
executable compiled before them (never 0)."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, "attn")
