"""The readers of the engine loop's own record (`/stats`.engine_trace) and
of its phases on the trace clock (chipbench/hostspans.py): attribution on a
synthetic plane, each reader on a fixture `ctx` and on an empty one."""

import json
from pathlib import Path

import pytest

from chipbench import harness, hostspans
from chipbench_entries import due, per_layer, stands_after

ROOT = Path(__file__).resolve().parents[2]
BENCH, SEARCH = harness.load_bench(ROOT / "BENCHMARK.json")
CELL2 = "qwen25-3b-bf16.agent-prefix"


def _span(name, start, end):
    return {"name": name, "start": start, "dur": end - start}


# One pump thread, two loop iterations: admit 0-10 ms, decode_dispatch
# 10-100 with an emit 20-100 inside it that waits on the chip 30-90; then
# admit 100-130 with a prefill_dispatch 105-115 inside; nothing after 130.
PUMP = {"python#3": [
    _span("admit", 0.000, 0.010),
    _span("decode_dispatch", 0.010, 0.100),
    _span("emit", 0.020, 0.100),
    _span("fetch_wait", 0.030, 0.090),
    _span("admit", 0.100, 0.130),
    _span("prefill_dispatch", 0.105, 0.115),
]}


def _device(*modules, ops=None):
    mods = [{"name": f"jit_{n}(1)", "start": a, "dur": b - a}
            for n, a, b in modules]
    return {"/device:TPU:0": {
        "XLA Modules": mods,
        "XLA Ops": ops or [dict(m, name="fusion.1") for m in mods]}}


def test_exclusive_gives_each_moment_to_the_innermost_span():
    got = hostspans.exclusive(PUMP["python#3"])
    assert [(round(a, 3), round(b, 3), n) for a, b, n in got] == [
        (0.0, 0.01, "admit"), (0.01, 0.02, "decode_dispatch"),
        (0.02, 0.03, "emit"), (0.03, 0.09, "fetch_wait"),
        (0.09, 0.1, "emit"), (0.1, 0.105, "admit"),
        (0.105, 0.115, "prefill_dispatch"), (0.115, 0.13, "admit")]


@pytest.mark.parametrize("gap,label", [
    ((0.040, 0.060), "fetch_wait"),     # the pump was waiting for results
    ((0.101, 0.104), "admit"),          # the pump was admitting a request
    ((0.140, 0.150), "unnamed"),        # no span covers any of it
    ((0.085, 0.100), "emit"),           # 5 ms of waiting, 10 ms of emitting
])
def test_a_gap_is_charged_to_the_phase_covering_most_of_it(gap, label):
    ir = _device(("decode_multi", gap[0] - 0.02, gap[0]),
                 ("decode_multi", gap[1], gap[1] + 0.02))
    assert hostspans.idle_by_span(ir, PUMP) == [
        [label, pytest.approx(gap[1] - gap[0])]]


def test_idle_by_span_sums_gaps_by_phase_largest_first():
    ir = _device(("prefill_install", 0.0, 0.035), ("decode_multi", 0.045, 0.1),
                 ("decode_multi", 0.102, 0.135), ("decode_multi", 0.145, 0.2))
    assert hostspans.idle_by_span(ir, PUMP) == [
        ["fetch_wait", pytest.approx(0.010)],
        ["unnamed", pytest.approx(0.010)], ["admit", pytest.approx(0.002)]]
    assert hostspans.idle_by_span(ir, {}) == [
        ["unnamed", pytest.approx(0.022)]]


def test_idle_unfed_leaves_out_the_idle_time_spent_waiting_for_the_chip():
    # idle 35-45 ms (inside fetch_wait), 80-95 (10 ms of it inside), 120-125
    ir = _device(("decode_multi", 0.0, 0.035), ("decode_multi", 0.045, 0.08),
                 ("decode_multi", 0.095, 0.12), ("decode_multi", 0.125, 0.2))
    unfed, window = hostspans.idle_unfed(ir, PUMP)
    assert window == pytest.approx(0.2)
    assert unfed == pytest.approx(0.005 + 0.005)
    assert hostspans.idle_unfed(ir, {})[0] == pytest.approx(0.030)


def test_idle_unfed_reads_the_host_plane_run_py_hands_it(monkeypatch):
    """`ctx["host_spans"]` is the trace's host plane as run.py loaded it,
    beside the device planes in `ctx["trace"]`: the reader opens no file
    (it used to look for `.chipbench_work/<cell>/trace` itself), so a
    trace another run left there cannot be read for this one's."""
    def opened(*a, **k):
        raise AssertionError("a reader opened a trace")

    monkeypatch.setattr(hostspans, "load_spans", opened)
    monkeypatch.setattr("chipbench.xplane.find_xplane", opened)
    read = harness.load_reader(SEARCH, "device.idle_unfed_pct")
    ir = _device(("decode_multi", 0.0, 0.1), ("decode_multi", 0.12, 0.2))
    ctx = {"trace": ir, "cell": CELL2,
           "host_spans": {"python#1": [_span("fetch_wait", 0.1, 0.115)]}}
    assert read(ctx) == pytest.approx(100 * 0.005 / 0.2)
    assert read(dict(ctx, host_spans=None)) is None
    assert read(dict(ctx, host_spans={})) is None
    assert read(dict(ctx, trace=None)) is None
    assert not hasattr(hostspans, "find_trace")


# ------------------------------------------------------------- the readers
RECENT = {
    "seconds": 29.6, "admissions": 140, "prompt_tokens": 238000,
    "prefix_hit_tokens": 215040, "decode_steps": 800,
    "live_slot_steps": 17600, "context_token_steps": 1760000,
    "pages_reserved_steps": 160000,
    "host_s": {"admit": 0.5, "prefill_dispatch": 1.0, "decode_dispatch": 0.7,
               "fetch_wait": 24.0, "emit": 2.8, "idle": 0.6}}


def _ctx(spans=None):
    hf = json.loads((ROOT / "chipbench/configs/qwen25-3b-bf16/config.json")
                    .read_text())
    trace = json.loads((ROOT / "tests/chipbench/data/trace_head_tpu_v5e.json")
                       .read_text())
    return {"trace": trace, "agent_stats": {"engine_trace": {"recent": RECENT}},
            "hotpath": {}, "hf": hf, "cell": CELL2, "host_spans": spans,
            "family": harness.Family(SEARCH),
            "engine": {"decode_horizon": 8, "page_size": 16,
                       "weights": "bfloat16"},
            "device": {"kind": "TPU v5 lite"}}


# 2200 context tokens a step x 36864 bytes of K and V a token, over the
# kernel's 1.008347 ms / 8 steps of the recorded trace (its head holds 6 of
# a call's 288 kernel launches, so a whole batch's context would not fit
# the time), of 819 GB/s
KV_BW = 100 * (2200 * 2 * 36 * 2 * 128 * 2 / 819e9) / (1.008347e-3 / 8)

EXPECTED = {
    "engine.prefix_hit_pct": 100 * 215040 / 238000,
    "engine.batch_live_mean": 22.0,
    "engine.kv_used_of_reserved_pct": 100 * 1760000 / (160000 * 16),
    "engine.host_busy_pct": 100 * (1 - 24.6 / 29.6),
    "kernel.paged_attn_kv_bw_pct": KV_BW,
    # the recorded trace is busy 317.294 ms of 320.514; the synthetic pump
    # waits through the first 2 ms of the gap between its two programs
    "device.idle_unfed_pct": 100 * (0.320513533 - 0.317294335 - 0.002)
    / 0.320513533,
}


@pytest.fixture()
def pump_in_the_gap():
    """The pump waits for the chip through the first 2 ms of the recorded
    trace's one gap between programs (50.227-53.431 ms)."""
    return {"python#1": [_span("fetch_wait", 0.050226763, 0.052226763)]}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_fixture_ctx(name, pump_in_the_gap):
    value = harness.load_reader(SEARCH, name)(_ctx(pump_in_the_gap))
    assert value == pytest.approx(EXPECTED[name], rel=1e-6)
    if name.endswith("_pct"):
        assert 0 <= value <= 100


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_program_without_the_record(name):
    """The parent commit: `/stats` has no `engine_trace`, the trace no
    `engine.*` span. Nothing to read, nothing raised."""
    read = harness.load_reader(SEARCH, name)
    ctx = _ctx({})
    ctx["agent_stats"] = {"ttft_spans": {"n": 3}, "cached_blocks": 224}
    assert read(ctx) is None
    assert read({"trace": None, "agent_stats": {}, "hotpath": {}}) is None
    ctx["agent_stats"] = {"engine_trace": {"recent": {"seconds": 0.0}}}
    assert read(ctx) is None


def test_kv_bandwidth_share_of_a_pool_type_it_does_not_know(pump_in_the_gap):
    ctx = _ctx(pump_in_the_gap)
    read = harness.load_reader(SEARCH, "kernel.paged_attn_kv_bw_pct")
    # through the default family: the parent's own arithmetic, to the last
    # digit (2200 context tokens a step, the kernel's 1.008347 ms / 8)
    assert read(ctx) == 100.0 * (2200.0 * 36864 / 819e9) / (
        harness.load_reader(SEARCH, "kernel.paged_attn_ms")(ctx) / 1000.0)
    assert read(dict(ctx, family=None)) is None
    ctx["hf"] = dict(ctx["hf"], torch_dtype="float8_e4m3fn")
    assert read(ctx) is None
    del ctx["hf"]["torch_dtype"]
    assert read(ctx) is None


def test_kv_bandwidth_share_counts_what_the_family_counts(pump_in_the_gap):
    """A family whose tokens hold keys and values in 9 of its 36 layers
    says so in its `bytes`; the reader divides by nothing of its own."""
    class Quarter:
        class bytes:
            @staticmethod
            def kv_bytes_per_token(hf):
                return 36864 // 4

    ctx = _ctx(pump_in_the_gap)
    read = harness.load_reader(SEARCH, "kernel.paged_attn_kv_bw_pct")
    assert read(dict(ctx, family=Quarter)) == pytest.approx(read(ctx) / 4)


THIRTEEN = [                       # the per-layer entries PR 23 brought
    "master.schedule_ms", "agent.first_delta_ms", "engine.queue_ms",
    "engine.prefill_ms", "client.ttft_mean_ms",
    "agent.first_delta_ms.agent-prefix", "engine.queue_ms.agent-prefix",
    "engine.prefill_ms.agent-prefix", "prog.decode_step_ms",
    "prog.prefill_call_ms", "kernel.paged_attn_ms", "device.idle_pct",
    "device.decode_weight_bw_pct"]
SIX = ["engine.prefix_hit_pct", "engine.batch_live_mean",
       "engine.kv_used_of_reserved_pct", "engine.host_busy_pct",
       "kernel.paged_attn_kv_bw_pct", "device.idle_unfed_pct"]


def test_the_six_follow_the_thirteen_and_are_reported_where_they_read():
    """PR 24's six in their order after PR 23's thirteen, reported in the
    two cells of their day as they were (the prefix cache's share in the
    cell that shares prefixes alone), and each due in every cell of the
    list it has since (a reader of keys lists the cells that hold any)."""
    assert set(SIX) == set(EXPECTED)
    assert stands_after(BENCH, SIX, THIRTEEN)
    for cell in ("qwen25-7b-int8.chat", CELL2):
        got = {n for n in SIX if due(BENCH, n, cell)}
        assert got == set(SIX) - ({"engine.prefix_hit_pct"}
                                  if cell != CELL2 else set())
    for name in SIX:
        for cell in per_layer(BENCH, name).get("workloads", ()):
            assert due(BENCH, name, cell)
