"""Programs, by block: the dense MLP = median over the traced `decode_multi`
executions of the summed seconds of the device ops traced under `blk.mlp` /
the configured horizon, ms a step (`layers/blocks.py`: the outermost `blk.*`
of the op_name the profiler wrote for the op's instruction). The block is the
norm, gate/up/down and the residual of a dense layer, and a sparse layer's
shared expert. Nothing where the trace names no block: a program without the
scopes, or an executable compiled before them (never 0)."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, "mlp")
