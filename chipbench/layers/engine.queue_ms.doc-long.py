"""`engine.queue_ms` (the reader beside this file) in the `doc-long` cells.
Informational there: time in the queue is part of the first token's time,
which this cell does not judge (`ttft_ms.mean` lists its cells), and no
part of a gap between chunks; the entry names `gap_ms.p95` because it has
to name a metric the cell reports (PERF.md section 7.11)."""

from pathlib import Path

from chipbench import harness

read = harness.load_file(Path(__file__).with_name("engine.queue_ms.py")).read
