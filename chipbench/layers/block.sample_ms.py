"""Programs, by block: the step's tail = median over the traced `decode_multi`
executions of the summed seconds of the device ops traced under `blk.sample` /
the configured horizon, ms a step (`layers/blocks.py`: the outermost `blk.*`
of the op_name the profiler wrote for the op's instruction). The block is
sampling, penalties, log-probabilities, the stop/budget freeze (`engine.py`
`_post_decode_forward`) and the call's packed result. Nothing where the trace
names no block: a program without the scopes, or an executable compiled before
them (never 0)."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, "sample")
