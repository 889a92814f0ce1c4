"""`engine.queue_ms` (the reader beside this file) in the `agent-prefix` cells,
where `ttft_ms.mean` is not judged end to end and the span names
`gap_ms.p95` instead: a time to the first token and a gap between chunks
are both made of the same turns that prefill calls and decode calls take
on the chip."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "_base_reader", Path(__file__).with_name("engine.queue_ms.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
