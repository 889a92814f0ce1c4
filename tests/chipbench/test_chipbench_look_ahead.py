"""The reader of `engine.look_ahead_hit_pct`: the share of the seams behind
a running decode call that the pump hid from the chip, from the engine
loop's `look_ahead_late/<hit|late|skipped>` counters (PR 42); a program
without them, or a window that met no seam, reports nothing."""

from pathlib import Path

import pytest

from chipbench import harness
from chipbench_entries import but_its_list, due, per_layer, stands_after

ROOT = Path(__file__).resolve().parents[2]
BENCH, SEARCH = harness.load_bench(ROOT / "BENCHMARK.json")
NAME = "engine.look_ahead_hit_pct"


@pytest.mark.parametrize("recent,want", [
    ({"look_ahead_late": {"hit": 708, "late": 88, "skipped": 204}}, 70.8),
    ({"look_ahead_late": {"hit": 40}}, 100.0),          # every seam hidden
    ({"look_ahead_late": {"late": 3, "skipped": 9}}, 0.0),   # none was
    ({"look_ahead_late": {"hit": 0, "late": 0, "skipped": 0}}, None),
    ({"look_ahead_late": {}}, None),                    # no seam was met
    ({"decode_steps": 800, "admissions": 40}, None),    # before PR 42
    ({"seconds": 0.0}, None),
    (None, None),                                       # no engine_trace
], ids=["hit-late-skipped", "all-hit", "none-hit", "zeros", "empty",
        "no-counter", "nothing-recent", "no-engine-trace"])
def test_the_reader_gives_the_share_or_nothing(recent, want):
    read = harness.load_reader(SEARCH, NAME)
    stats = {} if recent is None else {"engine_trace": {"recent": recent}}
    got = read({"agent_stats": stats})
    assert got == want if want is None else got == pytest.approx(want)
    assert read({"trace": None, "agent_stats": {}, "hotpath": {}}) is None


def test_the_counter_is_read_as_the_agent_nests_it():
    """`/stats`.engine_trace nests the flat `look_ahead_late/<outcome>`
    counters by family (engine/telemetry.py `_nest`); the reader takes
    `recent`, the window's last 30 s, and not `total`, which holds warm-up."""
    from xllm_service_tpu.engine import telemetry

    now = [100.0]
    tel = telemetry.EngineTelemetry(clock=lambda: now[0])
    tel.count_by("look_ahead_late", "skipped", 50)      # warm-up's
    now[0] += telemetry.SNAPSHOT_S
    tel.tick()                                          # a copy is kept
    for outcome, n in (("hit", 6), ("late", 1), ("skipped", 1)):
        tel.count_by("look_ahead_late", outcome, n)
    now[0] += 1.0
    stats = {"engine_trace": telemetry.summarize([tel])}
    assert stats["engine_trace"]["total"]["look_ahead_late"]["skipped"] == 51
    assert stats["engine_trace"]["recent"]["look_ahead_late"] == {
        "hit": 6, "late": 1, "skipped": 1}
    assert harness.load_reader(SEARCH, NAME)(
        {"agent_stats": stats}) == pytest.approx(75.0)


def test_the_entry_follows_what_stood_and_is_due_where_it_lists():
    """The entry as PR 44 wrote it but for its list, after the entries that
    stood before it, due in each cell of its list: the five of its day."""
    entry = per_layer(BENCH, NAME)
    assert but_its_list(entry) == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "tpot_ms.p90"}
    assert stands_after(BENCH, [NAME], [
        "engine.prefill_padding_pct", "kernel.prefill_attn_ms",
        "kernel.retention_update_ms", "prog.prefill_chunk_ms"])
    cells = entry.get("workloads", [w["name"] for w in BENCH["workloads"]])
    assert cells and all(due(BENCH, NAME, cell) for cell in cells)
    assert set(cells) <= {w["name"] for w in BENCH["workloads"][:5]}
