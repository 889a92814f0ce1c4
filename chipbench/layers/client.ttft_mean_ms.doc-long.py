"""`client.ttft_mean_ms` (the reader beside this file) in the `doc-long` cells.
Informational there: the first token is what this traffic's users feel,
but `ttft_ms.mean` lists its cells and this one is not among them, so no
judged metric of the cell is made of this number; the entry names
`gap_ms.p95` because it has to name one the cell reports. The repair, a
`benchmark` PR's: `ttft_ms.mean` judged in this cell (PERF.md section 7.11)."""

from pathlib import Path

from chipbench import harness

read = harness.load_file(Path(__file__).with_name("client.ttft_mean_ms.py")).read
