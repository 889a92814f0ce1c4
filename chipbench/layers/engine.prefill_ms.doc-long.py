"""`engine.prefill_ms` (the reader beside this file) in the `doc-long` cells,
where the entry names `gap_ms.p95`: an arrival's prefill call is what
interrupts everyone's decode calls, and the 95th percentile of the gaps
between chunks is a decode call plus the prefills that ran in front of
it."""

from pathlib import Path

from chipbench import harness

read = harness.load_file(Path(__file__).with_name("engine.prefill_ms.py")).read
