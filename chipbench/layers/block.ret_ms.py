"""Programs, by block: the retention mixer = median over the traced
`decode_multi` executions of the summed seconds of the device ops traced under
`blk.ret` / the configured horizon, ms a step (`layers/blocks.py`: the
outermost `blk.*` of the op_name the profiler wrote for the op's instruction).
The block is a power-retention layer's norm, q/k/v and gate projections, q/k
norms, rotary embedding, state update (`kernel.retention_update_ms` is its
part), output projection and residual. Nothing where the trace names no block:
a program without the scopes, or an executable compiled before them (never 0).
Nothing either where the family has no such layer."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, "ret")
