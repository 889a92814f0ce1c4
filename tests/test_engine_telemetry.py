"""The engine loop's telemetry record (engine/telemetry.py): what the
counters count, against hand counts on the tiny engine; the windowed view
on an injected clock; what the agent serves from it (`/stats`, `/metrics`,
heartbeat tables, the `engine.prefill` span); the phases on the profiler's
clock."""

import time
from collections import deque
from types import SimpleNamespace

import pytest
import requests

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine import telemetry as T
from xllm_service_tpu.engine.agent import EngineAgent
from xllm_service_tpu.engine.engine import EngineRequest
from xllm_service_tpu.ops.page_walk import walk_run_counts

from test_e2e_real_engine import _base, cluster  # noqa: F401 (fixture)
from test_engine import (LONG_CALL, ONE_STEP_CALL, STEADY_LONG_CALLS,
                         Collector, FakeClock, finish, late_engine,
                         make_engine, pin_measurements, run_requests,
                         step_until_decoding)

PROMPT = list(range(1, 71))     # 70 tokens: two whole hash blocks of 32


def _req(rid, prompt=PROMPT, max_tokens=9):
    return EngineRequest(
        service_request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=max_tokens, temperature=0.0,
                                ignore_eos=True),
        on_output=Collector())


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------- counters on the engine
@pytest.fixture(scope="module")
def served_twice():
    """The same prompt served twice, one after the other."""
    e = make_engine(decode_horizon=4)
    reqs = [_req("a"), _req("b")]
    for r in reqs:
        run_requests(e, [r])
    return e, reqs


def test_second_admission_hits_the_block_aligned_prefix(served_twice):
    e, (first, second) = served_twice
    a, b = e.telemetry.admissions
    # the request carries the ring's own record, for the caller's span
    assert (first.admission, second.admission) == (a, b)
    # 70 tokens = 2 blocks of 32 + 6: the match is block-aligned
    assert (a.matched, b.matched, b.prompt_len) == (0, 64, 70)
    assert (a.bucket, b.bucket) == (256, 32)
    assert b.queue_ms >= 0 and b.prefill_ms > 0 and b.t >= a.t


def test_admission_totals_add_up(served_twice):
    c = served_twice[0].telemetry.counters
    assert (c["admissions"], c["prompt_tokens"]) == (2, 140)
    assert c["prefix_hit_tokens"] == 64
    assert (c["prefill_calls/256"], c["prefill_calls/32"]) == (1, 1)
    assert c["prefill_padded_tokens"] == (256 - 70) + (32 - 6)
    assert c["finished"] == 2
    assert c["prefix_onload_tokens"] == c["cancelled"] == 0


def test_a_full_match_keeps_one_suffix_token():
    """A prompt of exactly two blocks: the second admission may reuse only
    one, because prefill needs a suffix token to give the next logits."""
    e = make_engine()
    prompt = list(range(1, 65))
    for rid in "ab":
        run_requests(e, [_req(rid, prompt, max_tokens=2)])
    assert e.telemetry.counters["prefix_hit_tokens"] == 32
    assert e.telemetry.admissions[-1].matched == 32


def test_decode_counters_against_a_hand_count():
    """One request, 70-token prompt, 9 tokens out, horizon 4. The first
    token comes from the prefill; calls 1 and 2 are dispatched before call
    1's tokens are emitted (emission runs behind the next dispatch, whether
    or not the pump looks a call ahead), so both see context 70; call 3
    sees 74 and is in flight when call 2's tokens finish the request. The
    per-call samples (the heartbeat's TPOT table) keep the rule they always
    had: the call's sequences still live when it is
    fetched, with their context then. Call 1 is fetched after the prefill's
    token only (70), call 2 after call 1's four (74), call 3 after the
    request has finished: no sample."""
    e = make_engine(decode_horizon=4)
    run_requests(e, [_req("a")])
    e.step()                     # drains the call left in flight
    c = e.telemetry.counters
    assert c["decode_calls/4"] == 3
    assert c["decode_steps"] == 12
    assert c["live_slot_steps"] == 12
    assert c["context_token_steps"] == (70 + 70 + 74) * 4
    # 70 + 9 tokens reserve ceil(79 / 16) = 5 pages from admission on
    assert c["pages_reserved_steps"] == 5 * 12
    assert [(d.horizon, d.live, d.context_tokens)
            for d in e.telemetry.decodes] == [(4, 1, 70), (4, 1, 74)]
    assert all(d.ms_per_tok > 0 for d in e.telemetry.decodes)


def test_decode_counters_follow_the_dispatched_calls():
    """Two requests of different lengths in one batch: every dispatched
    call is counted with the horizon, the live sequences and their context
    at dispatch, whatever the schedule turns out to be."""
    e = make_engine(decode_horizon=4)
    seen = []
    inner = e._decode_multi

    def spy(params, d, horizon):
        live = [s for s in e._running.values() if not s.finished]
        seen.append((horizon, len(live), sum(s.context_len for s in live),
                     sum(len(s.pages.all_pages) for s in live)))
        return inner(params, d, horizon)

    e._decode_multi = spy
    run_requests(e, [_req("a", max_tokens=11),
                     _req("b", list(range(100, 140)), max_tokens=5)])
    e.step()
    c = e.telemetry.counters
    assert max(n for _, n, _, _ in seen) == 2
    assert c["decode_steps"] == sum(h for h, _, _, _ in seen)
    assert c["live_slot_steps"] == sum(h * n for h, n, _, _ in seen)
    assert c["context_token_steps"] == sum(h * t for h, _, t, _ in seen)
    assert c["pages_reserved_steps"] == sum(h * p for h, _, _, p in seen)
    assert sum(v for k, v in c.items() if k.startswith("decode_calls/")) \
        == len(seen)
    # a call fetched after its last sequence finished leaves no sample
    assert 0 < len(e.telemetry.decodes) < len(seen)


def test_walk_counters_are_the_kernels_rule_on_the_live_rows():
    """What the decode kernel's page walk fetches, counted at dispatch by
    its own rule (`walk_run_counts`; the 16-wide table is one chunk). A
    sequence that grows to 254 tokens on a fresh pool, which hands its 16
    pages out ascending, fills the chunk only in its last calls: a run
    then, page by page before. Its pages are freed whole and the free
    list is a stack, so the next long row holds them descending: a run
    again, beside a short row that fills no chunk. The counters follow
    the rule on each live row of each call, and reach `engine_trace` as
    `total` and `recent`."""
    e = make_engine(decode_horizon=4)
    want = [0, 0]
    run_rows = set()
    inner = e._decode_multi

    def spy(params, d, horizon):
        for s in e._running.values():
            if not s.finished:
                row = s.pages.all_pages
                n, runs = walk_run_counts(
                    row, -(-(s.context_len + 1) // 16), 16)
                want[0] += n * horizon
                want[1] += runs * horizon
                if runs:
                    run_rows.add(tuple(row))
        return inner(params, d, horizon)

    e._decode_multi = spy
    # 20 + 234 tokens on 16 pages of its own, none donated: freed whole
    run_requests(e, [_req("a", list(range(300, 320)), max_tokens=234)])
    run_requests(e, [_req("b", list(range(500, 745)), max_tokens=9),
                     _req("c", list(range(800, 840)), max_tokens=5)])
    e.step()
    c = e.telemetry.counters
    assert (c["walk_chunks"], c["walk_run_chunks"]) == tuple(want)
    assert 0 < c["walk_run_chunks"] < c["walk_chunks"]
    assert run_rows == {tuple(range(1, 17)), tuple(range(16, 0, -1))}
    trace = T.summarize([e.telemetry])
    total, recent = trace["total"], trace["recent"]
    assert (total["walk_chunks"], total["walk_run_chunks"]) == tuple(want)
    assert 0 < recent["walk_run_chunks"] < recent["walk_chunks"] <= want[0]


@pytest.mark.parametrize("measured,behind", [
    (ONE_STEP_CALL, 4), (LONG_CALL, 0), (STEADY_LONG_CALLS, 1)],
    ids=["ahead-at-once", "fetch-first", "late"])
def test_prefill_behind_steps_is_what_was_in_flight_at_the_dispatch(
        measured, behind):
    """Nothing runs when the first request is admitted: 0. The second is
    dispatched behind whatever decode call is unfetched then, counted in
    the steps the chip still has to run: a whole call of 4 steps where the
    pump looks ahead at once, nothing where it fetches first, and one step
    where it looks ahead late (a margin of a 93 ms call is left)."""
    e = make_engine(decode_horizon=4)
    pin_measurements(e, *measured)
    FakeClock().drive(e)
    e._result_ready = lambda call: False        # the chip is still at it
    a, b = _req("a", max_tokens=20), _req("b", list(range(100, 140)))
    e.submit(a)
    step_until_decoding(e)
    assert e.telemetry.counters["prefill_behind_steps"] == 0
    e.submit(b)
    e.step()
    assert e.telemetry.counters["admissions"] == 2
    assert e.telemetry.counters["prefill_behind_steps"] == behind
    view = T.summarize([e.telemetry])
    assert view["total"]["prefill_behind_steps"] == behind


def test_the_seams_outcomes_are_counted_in_total_and_recent():
    """hit: the call's result was not ready when the next program went
    onto the queue; late: it was; skipped: the pump fetched first."""
    e, clock = late_engine()
    e.telemetry._t_snapshot -= T.SNAPSHOT_S     # the pump's copy is due:
    e.telemetry.tick()                          # what `recent` starts from
    a = _req("a", max_tokens=60)
    e.submit(a)
    step_until_decoding(e)
    for _ in range(3):
        e.step()
    assert e.telemetry.counters["look_ahead_late/hit"] == 3
    began = clock.t
    e._result_ready = lambda call: clock.t >= began + 0.050   # early
    e.step()
    e._result_ready = lambda call: False
    e._prefillings.append(None)      # a chunked prefill in flight: hold
    plan = e._seam_plan(e._pending_decode)
    e._prefillings.clear()
    assert plan.action == "fetch" and plan.estimate_s > 0
    finish(e, [a])                   # the last call: every budget ends
    view = T.summarize([e.telemetry])
    for part in ("total", "recent"):
        seam = view[part]["look_ahead_late"]
        assert seam["late"] == 1 and seam["skipped"] == 1
        assert seam["hit"] >= 4
    assert view["total"]["look_ahead_late"] == {
        k.split("/")[1]: v for k, v in e.telemetry.counters.items()
        if k.startswith("look_ahead_late/")}


def test_turnaround_is_the_admit_and_decode_dispatch_phases():
    clock = Clock()
    tel = T.EngineTelemetry(clock)
    assert tel.turnaround_s() == 0.0
    with tel.phase("admit"):
        clock.t += 0.25
        with tel.phase("prefill_dispatch"):
            clock.t += 4.0                  # not the pump's turn-around
    with tel.phase("fetch_wait"):
        clock.t += 2.0
    with tel.phase("decode_dispatch"):
        clock.t += 0.5
        # read inside a phase: its time so far is brought up to date
        assert tel.turnaround_s() == pytest.approx(0.75)
        clock.t += 0.125
    with tel.phase("emit"):
        clock.t += 1.0
    assert tel.turnaround_s() == pytest.approx(0.875)
    assert sum(v for k, v in tel.counters.items()
               if k.startswith("host_s/")) == pytest.approx(clock.t)


@pytest.mark.parametrize("recent,want", [
    ({"admissions": 96, "prefill_behind_steps": 768}, 8.0),   # looked ahead
    ({"admissions": 96, "prefill_behind_steps": 0}, 0.0),     # at the seam
    ({"admissions": 96, "prefill_behind_steps": 8}, 8 / 96),
    ({"admissions": 96}, None),     # the parent's program: no such counter
    ({"admissions": 0, "prefill_behind_steps": 0}, None),     # none admitted
    (None, None),
])
def test_the_benchmarks_reader_of_prefill_behind_steps(recent, want):
    from chipbench import harness

    _, search = harness.load_bench(harness.ROOT / "BENCHMARK.json")
    read = harness.load_reader(search, "engine.prefill_behind_steps")
    stats = {} if recent is None else {"engine_trace": {"recent": recent}}
    got = read({"agent_stats": stats})
    assert got == want if want is None else got == pytest.approx(want)


def test_blocked_admissions_and_cancellations_are_counted():
    e = make_engine(max_batch_size=1)
    a, b = _req("a", max_tokens=40), _req("b", list(range(200, 230)))
    e.submit(a)
    e.submit(b)
    e.step()
    e.step()
    assert e.telemetry.counters["admissions_blocked/no_slot"] >= 1
    e.cancel("a")
    e.cancel("b")
    for _ in range(3):
        e.step()
    assert e.telemetry.counters["cancelled"] == 2
    assert e.telemetry.counters["admissions"] == 1


def test_no_pages_blocks_admission():
    e = make_engine(num_pages=8)      # 7 usable pages of 16 tokens
    e.submit(_req("a", max_tokens=20))          # 90 tokens: 6 pages
    e.submit(_req("b", list(range(300, 340))))  # 49 tokens: 4 pages
    e.step()
    assert e.telemetry.counters["admissions_blocked/no_pages"] >= 1


def test_phase_seconds_sum_to_the_loops_wall_time():
    e = make_engine(decode_horizon=4)
    tel = e.telemetry
    t0 = tel.clock()
    before = sum(v for k, v in tel.counters.items() if k.startswith("host_s/"))
    e.start()
    r = _req("a")
    e.submit(r)
    assert r.on_output.done.wait(60)
    time.sleep(0.12)               # the pump falls idle
    e.stop()
    tel.switch("idle")             # bring the running phase up to date
    wall = tel.clock() - t0
    phases = {p: tel.counters[f"host_s/{p}"] for p in T.PHASES}
    assert sum(phases.values()) - before == pytest.approx(wall, rel=0.05)
    assert all(v > 0 for v in phases.values()), phases


def test_the_six_phases_land_in_a_profiler_trace(tmp_path):
    """A `jax.profiler` session on the CPU: the pump's thread line holds
    the six `engine.*` annotations and the `engine.decode_live.<n>.<h>`
    markers, read back by chipbench/hostspans.py."""
    import jax

    from chipbench import hostspans, xplane

    e = make_engine(decode_horizon=4)
    run_requests(e, [_req("warm")])      # compile outside the session

    def calls():
        return sum(e.telemetry.counters.get(f"decode_calls/{h}", 0)
                   for h in (1, 2, 4))

    before = calls()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        e.start()
        r = _req("a", list(range(50, 120)))
        e.submit(r)
        assert r.on_output.done.wait(60)
        time.sleep(0.12)
        e.stop()
    finally:
        jax.profiler.stop_trace()
    spans = hostspans.load_spans(xplane.find_xplane(tmp_path))
    assert len(spans) == 1               # one pump thread
    (line,) = spans.values()
    names = {s["name"] for s in line}
    assert set(T.PHASES) <= names
    # beside them one marker per landed decode call, entered and left at
    # once: the sequences it was dispatched for and its steps
    marks = [s for s in line if s["name"] not in T.PHASES]
    assert marks and {s["name"] for s in marks} <= {
        f"decode_live.1.{h}" for h in (1, 2, 4)}
    assert "decode_live.1.4" in names and max(s["dur"] for s in marks) < 1e-4
    # (one landing a dispatch; the warm request's last call may land here)
    assert 0 <= len(marks) - (calls() - before) <= 1
    pieces = hostspans.exclusive(line)
    assert all(a < b for a, b, _ in pieces)
    assert all(p[1] <= q[0] + 1e-9 for p, q in zip(pieces, pieces[1:]))


# ---------------------------------------- the windowed view, injected clock
def _ticked(seconds: int, per_second: int = 1):
    """A telemetry whose pump admits `per_second` requests a second."""
    clock = Clock()
    tel = T.EngineTelemetry(clock)
    for s in range(seconds):
        for k in range(per_second):
            clock.t = s + (k + 0.5) / per_second
            tel.admitted(100, 64, 32, 5.0 + s, 20.0)
            tel.decode_dispatched(8, 8, 3, 900, 70, 21, 15)
            tel.decode_fetched(8, 3, 900, 25.0)
        clock.t = s + 1.0
        tel.tick()
    return tel, clock


def test_recent_covers_the_window_and_no_more():
    tel, clock = _ticked(80, per_second=4)
    view = T.summarize([tel])
    assert view["total"]["admissions"] == 320
    r = view["recent"]
    assert 29.0 <= r["seconds"] <= 30.0
    assert r["admissions"] == 4 * r["seconds"]         # rate x seconds
    assert r["prompt_tokens"] == 100 * r["admissions"]
    assert r["decode_calls"] == {"8": r["admissions"]}
    assert r["decode_steps"] == 8 * r["admissions"]
    # rings hold the last 512 samples; only those inside the window count
    assert r["queue_ms"]["n"] == r["prefill_ms"]["n"] == 120
    assert r["queue_ms"]["p50"] == 5.0 + 65 and r["queue_ms"]["p90"] == 5.0 + 77
    assert r["live_slot_steps"] == 3 * r["decode_steps"]


def test_recent_drops_samples_older_than_its_window():
    tel, clock = _ticked(3)
    clock.t = 100.0
    for _ in range(35):               # an idle pump still ticks
        clock.t += 1.0
        tel.tick()
    tel.admitted(10, 0, 32, 1.0, 2.0)
    r = T.summarize([tel])["recent"]
    assert r["admissions"] == 1 and r["prompt_tokens"] == 10
    assert r["queue_ms"] == {"n": 1, "p50": 1.0, "p90": 1.0}
    assert r["decode_steps"] == 0 and r["decode_calls"] == {"8": 0}
    assert T.summarize([tel])["total"]["admissions"] == 4


def test_recent_of_a_young_engine_is_its_whole_life():
    clock = Clock()
    tel = T.EngineTelemetry(clock)
    tel.admitted(10, 0, 32, None, 2.0)
    view = T.summarize([tel])
    assert view["recent"]["admissions"] == 0       # no snapshot yet:
    assert view["recent"]["seconds"] == 0          # nothing to difference
    clock.t = 1.5
    tel.tick()
    tel.admitted(10, 0, 32, None, 2.0)
    clock.t = 4.0
    view = T.summarize([tel])
    assert view["recent"]["seconds"] == pytest.approx(2.5)
    assert view["recent"]["admissions"] == 1
    assert view["recent"]["queue_ms"] == {"n": 0}  # never submitted: None


def test_a_pump_that_kept_no_snapshot_in_the_window_says_how_far_it_reached():
    tel, clock = _ticked(5)
    clock.t = 50.0                    # one step of 45 s: no tick inside it
    tel.admitted(10, 0, 32, 1.0, 2.0)
    r = T.summarize([tel])["recent"]
    assert r["seconds"] == pytest.approx(45.0)
    assert r["admissions"] == 1


def test_phase_clock_is_exclusive_and_ticks_bring_it_up_to_date():
    clock = Clock()
    tel = T.EngineTelemetry(clock)
    clock.t = 1.0
    with tel.phase("emit"):
        clock.t = 1.5
        with tel.phase("fetch_wait"):
            clock.t = 3.5
        clock.t = 3.75
        tel.tick()                    # inside a phase: charged so far
        assert tel.counters["host_s/emit"] == pytest.approx(0.75)
        clock.t = 4.0
    clock.t = 4.5
    tel.switch("idle")
    got = {p: tel.counters[f"host_s/{p}"] for p in T.PHASES}
    assert got == {"admit": 0, "prefill_dispatch": 0, "decode_dispatch": 0,
                   "fetch_wait": 2.0, "emit": 1.0, "idle": 1.5}


def test_summarize_sums_engines_and_pools_their_samples():
    a, _ = _ticked(40)
    b, _ = _ticked(40, per_second=2)
    view = T.summarize([a, b])
    assert view["total"]["admissions"] == 120
    assert view["total"]["host_s"].keys() == set(T.PHASES)
    assert view["recent"]["seconds"] == 30.0
    assert view["recent"]["admissions"] == 30 * 3
    assert view["recent"]["queue_ms"]["n"] == 30 * 3
    assert set(view) == {"total", "recent"}


# ------------------------------------------------- what the agent serves
def _stub_agent(*telemetries, spans=()):
    return SimpleNamespace(
        engines=[SimpleNamespace(telemetry=t) for t in telemetries],
        ttft_spans=deque(spans),
        DEFAULT_TTFT_TABLE=EngineAgent.DEFAULT_TTFT_TABLE,
        DEFAULT_TPOT_TABLE=EngineAgent.DEFAULT_TPOT_TABLE)


def test_heartbeat_latency_tables_on_a_fixed_input():
    """What `profiling_tables` made of the old (prompt_len, ms) and
    (batch, context tokens, ms/token) rings, it makes of the new ones."""
    tel = T.EngineTelemetry(Clock())
    for plen, ms in [(20, 9.0), (30, 11.0), (100, 30.0), (120, 50.0),
                     (128, 40.0), (600, 200.0)]:
        tel.admitted(plen, 0, 32, 1.0, ms)
    for live, ctx, ms in [(1, 100, 5.0), (1, 300, 7.0), (2, 500, 8.0),
                          (4, 2000, 12.0), (4, 1000, 10.0), (4, 3000, 11.0)]:
        tel.decode_fetched(8, live, ctx, ms)
    ttft, tpot = EngineAgent.profiling_tables(_stub_agent(tel))
    assert ttft == [[32, 10.0], [128, 40.0], [1024, 200.0]]
    assert tpot == [[1, 200.0, 6.0], [2, 500, 8.0], [4, 2000, 11.0]]
    # fewer than three buckets: the cold-start defaults stand
    young = T.EngineTelemetry(Clock())
    young.admitted(20, 0, 32, 1.0, 9.0)
    assert EngineAgent.profiling_tables(_stub_agent(young)) == (
        EngineAgent.DEFAULT_TTFT_TABLE, EngineAgent.DEFAULT_TPOT_TABLE)


def test_ttft_spans_keep_their_keys_and_meaning():
    tel = T.EngineTelemetry(Clock())
    for q, p in [(3.0, 30.0), (1.0, 10.0), (2.0, 20.0)]:
        tel.admitted(50, 0, 64, q, p)
    tel.admitted(50, 0, 64, None, 99.0)     # a continuation: never submitted
    got = EngineAgent._span_summary(_stub_agent(tel, spans=[40.0, 60.0, 50.0]))
    assert got == {"n": 3, "agent_accept_to_first_delta_ms": 50.0,
                   "engine_queue_ms": 2.0, "engine_prefill_ms": 20.0}


def test_stats_metrics_and_the_prefill_span_carry_the_record(cluster):  # noqa: F811
    master, agent = cluster
    # Compile the programs these requests use before the master times one
    # (a first-call compile reads as a TPOT breach, and brownout then stops
    # sampling traces): the same shapes, straight into the engine.
    for rid in ("warm-a", "warm-b"):
        warm = _req(rid, list(range(300, 492)), max_tokens=5)
        agent.engine.submit(warm)
        assert warm.on_output.done.wait(120)
    body = {"model": "tiny-llama", "prompt": "count the pages " * 12,
            "max_tokens": 5, "temperature": 0, "ignore_eos": True}
    sids = []
    for _ in range(2):
        r = requests.post(_base(master) + "/v1/completions", json=body,
                          timeout=120)
        assert r.status_code == 200, r.text
        sids.append(r.headers["X-Request-Id"])
    stats = requests.get(f"http://{agent.name}/stats", timeout=5).json()
    assert set(stats["ttft_spans"]) == {
        "n", "agent_accept_to_first_delta_ms", "engine_queue_ms",
        "engine_prefill_ms"}
    trace = stats["engine_trace"]
    assert set(trace) == {"total", "recent"}
    total, recent = trace["total"], trace["recent"]
    assert total["admissions"] >= 4 and total["prefix_hit_tokens"] >= 2 * 160
    assert total["decode_steps"] >= total["live_slot_steps"] / 4 > 0
    assert set(total["host_s"]) == set(T.PHASES)
    assert 0 < recent["seconds"] <= T.WINDOW_S
    assert recent["admissions"] <= total["admissions"]
    assert {"queue_ms", "prefill_ms"} <= set(recent)
    assert stats["sarathi_rides"] == total["sarathi_rides"]
    assert "prefill_behind_steps" in total and "prefill_behind_steps" in recent
    assert 0 <= total["walk_run_chunks"] <= total["walk_chunks"] > 0
    assert recent["walk_chunks"] <= total["walk_chunks"]
    # the look-ahead rule as it stands, one entry an engine
    (rule,) = stats["look_ahead"]
    assert set(rule) == {"turnaround_ms", "call_ms", "ahead", "estimate_ms",
                         "margin_ms", "error_ms"}
    assert rule["call_ms"] > 0 and isinstance(rule["ahead"], bool)
    assert rule["estimate_ms"] >= 0
    # every engine's first decode call is fetched first: nothing measured
    assert total["look_ahead_late"]["skipped"] >= 1

    text = requests.get(f"http://{agent.name}/metrics", timeout=5).text
    for line in ("engine_admissions_total ", "engine_prefix_hit_tokens_total ",
                 "engine_preemptions_total 0", "engine_sarathi_rides_total ",
                 'engine_host_seconds_total{phase="fetch_wait"} ',
                 "engine_prefill_behind_steps_total ",
                 'engine_look_ahead_late_total{outcome="skipped"} ',
                 "engine_walk_chunks_total ", "engine_walk_run_chunks_total ",
                 'engine_decode_calls_total{horizon="',
                 'engine_prefill_calls_total{bucket="'):
        assert "\n" + line in text, line
    assert text.count("# TYPE engine_preemptions_total") == 1

    # the second request's own span says the cache served it
    got = requests.get(f"http://{agent.name}/admin/trace",
                       params={"request_id": sids[1]}, timeout=5).json()
    spans = [s for s in got.get("spans", [])
             if s.get("name", s.get("point")) == "engine.prefill"]
    assert spans, [(s["point"], s["attrs"]) for s in got["spans"]]
    attrs = spans[0]["attrs"]
    assert (attrs["prompt_tokens"], attrs["prefix_hit_tokens"]) == (192, 160)
    assert attrs["bucket"] == 32 and attrs["queue_ms"] >= 0
