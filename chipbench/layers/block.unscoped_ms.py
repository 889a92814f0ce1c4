"""Programs, by block: none of the named blocks = median over the traced
`decode_multi` executions of the summed seconds of the device ops whose
instruction the trace has a record for and whose op_name lies under no `blk.*`
scope (or is empty) / the configured horizon, ms a step: the embedding lookup,
the page-table arithmetic, the scan's stacking of its outputs, a state's
pinning, and what the compiler made itself (buffer allocations, copies).
Nothing (not 0) where the trace names no block at all: there every op would
land here."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.decode_block_ms(ctx, _blocks.UNSCOPED)
