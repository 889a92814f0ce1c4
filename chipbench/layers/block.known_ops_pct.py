"""Programs, by block: share of the device-op executions inside the traced
`decode_multi` executions whose instruction the trace has a record for
(`xplane.CONTAINERS` skipped, as everywhere): what the `block.*` sums rest on.
An op the source does not know lands in no block and lowers this. Nothing
where the trace names no block: a program without the scopes, or an executable
compiled before them (never 0)."""

from pathlib import Path

from chipbench import harness

_blocks = harness.load_file(Path(__file__).with_name("blocks.py"))


def read(ctx):
    return _blocks.known_ops_pct(ctx)
