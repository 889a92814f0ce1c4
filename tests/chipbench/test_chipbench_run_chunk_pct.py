"""The reader of `kernel.paged_attn_run_chunk_pct`: the share of the page
walk's chunks fetched as one run, from the engine loop's two counters; a
program without them (every tree before PR 33) reports nothing."""

from pathlib import Path

import pytest

from chipbench import harness
from chipbench_entries import but_its_list, due, per_layer, stands_after

ROOT = Path(__file__).resolve().parents[2]
BENCH, SEARCH = harness.load_bench(ROOT / "BENCHMARK.json")
NAME = "kernel.paged_attn_run_chunk_pct"


@pytest.mark.parametrize("recent,want", [
    ({"walk_chunks": 4000, "walk_run_chunks": 3400}, 85.0),
    ({"walk_chunks": 4000, "walk_run_chunks": 0}, 0.0),   # all scattered
    ({"walk_chunks": 56, "walk_run_chunks": 56}, 100.0),
    ({"decode_steps": 800, "live_slot_steps": 4100}, None),   # no counter
    ({"walk_chunks": 0, "walk_run_chunks": 0}, None),     # nothing decoded
    (None, None),                                         # no engine_trace
])
def test_the_reader_gives_the_ratio_or_nothing(recent, want):
    read = harness.load_reader(SEARCH, NAME)
    stats = {} if recent is None else {"engine_trace": {"recent": recent}}
    got = read({"agent_stats": stats})
    assert got == want if want is None else got == pytest.approx(want)


def test_both_cells_print_it_and_it_moves_the_pace():
    """The entry as PR 33 wrote it but for its list, after the entry that
    stood before it, and due in each cell of its list: the two cells of its
    day first."""
    entry = per_layer(BENCH, NAME)
    assert but_its_list(entry) == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tpot_ms.p90"}
    assert stands_after(BENCH, [NAME], ["device.idle_unfed_pct",
                                        "engine.prefill_behind_steps"])
    cells = entry.get("workloads", [w["name"] for w in BENCH["workloads"]])
    assert cells[:2] == ["qwen25-7b-int8.chat", "qwen25-3b-bf16.agent-prefix"]
    assert all(due(BENCH, NAME, cell) for cell in cells)
