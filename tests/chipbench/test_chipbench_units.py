"""The benchmark's own arithmetic, on the CPU, in seconds: traffic as a pure
function of the seed, percentiles and pooled gaps, the knee rule, the
weight-bytes model against hand-worked numbers, the trace reduction."""

import json
import shutil
import statistics
from pathlib import Path

import pytest

from chipbench import bytes_model, harness, loadgen, peaks, stats, xplane

ROOT = Path(__file__).resolve().parents[2]
CHAT = loadgen.read_mix(ROOT / "chipbench/traffic/chat.json")
AGENT = loadgen.read_mix(ROOT / "chipbench/traffic/agent-prefix.json")


def _key(reqs):
    return [(r.rid, r.due, tuple(r.prompt), r.max_tokens, r.group, r.phase)
            for r in reqs]


@pytest.mark.parametrize("mix,rate", [(CHAT, 2.0), (AGENT, 8.0)])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_schedule_is_a_pure_function_of_the_seed(mix, rate, seed):
    a = loadgen.schedule(mix, rate, 20, seed, 152064)
    b = loadgen.schedule(mix, rate, 20, seed, 152064)
    assert _key(a) == _key(b)
    c = loadgen.schedule(mix, rate, 20, seed + 1, 152064)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("mix,rate", [(CHAT, 2.0), (AGENT, 8.0)])
def test_every_seed_gets_the_same_schedule_and_other_tokens(mix, rate):
    def shape(seed):
        w = [r for r in loadgen.schedule(mix, rate, 30, seed, 152064)
             if r.phase != "fill"]
        return [(r.rid, r.due, r.private_tokens, r.max_tokens, r.group,
                 r.phase) for r in w], [r.prompt for r in w]
    s1, p1 = shape(1)
    s2, p2 = shape(2 ** 31 + 5)
    assert s1 == s2 and p1 != p2
    window = [x for x in s1 if x[5] == "window"]
    assert len(window) == round(rate * 30)
    assert all(0 <= x[1] < 30 for x in window)
    # shuffled, not sorted: long and short answers are mixed through the window
    assert [x[3] for x in window] != sorted(x[3] for x in window)


@pytest.mark.parametrize("seed", [3, 99])
def test_shared_prefix_requests_start_with_their_groups_prefix(seed):
    reqs = loadgen.schedule(AGENT, 8.0, 10, seed, 151936)
    fills = {r.group: r.prompt[:1536] for r in reqs if r.phase == "fill"}
    assert len(fills) == 16
    for r in reqs:
        assert r.prompt[:1536] == fills[r.group]
        assert 256 <= min(r.prompt) and max(r.prompt) < 151936
        if r.phase != "fill":
            assert 32 <= r.private_tokens <= 256
            assert 32 <= r.max_tokens <= 128
    ramp = [r for r in reqs if r.phase == "ramp"]
    assert ramp and all(-5 <= r.due < 0 for r in ramp)


def test_lengths_follow_the_mix_files_distribution():
    n = 400
    xs = loadgen.stratified(CHAT["prompt_tokens"], n)
    assert min(xs) == 32 and max(xs) == 1024
    assert abs(statistics.median(xs) - 256) <= 2
    out = loadgen.stratified(CHAT["output_tokens"], n)
    assert min(out) >= 16 and max(out) <= 384
    assert abs(statistics.median(out) - 128) <= 2
    assert abs(sum(loadgen.exp_gaps(100, 50.0)) - 50.0) < 1e-9
    assert loadgen.longest_total(CHAT) == 1408
    assert loadgen.longest_total(AGENT) == 1536 + 256 + 128
    assert loadgen.prefill_ranges(AGENT) == [(32, 256), (1568, 1792)]
    assert loadgen.prefill_ranges(CHAT) == [(32, 1024)]


@pytest.mark.parametrize("groups,s,n", [(16, 1.0, 384), (16, 1.0, 7),
                                        (4, 0.0, 10)])
def test_zipf_counts_sum_and_order(groups, s, n):
    c = loadgen.zipf_counts(groups, s, n)
    assert sum(c) == n and len(c) == groups
    assert all(a >= b - 1 for a, b in zip(c, c[1:]))


@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    (list(range(101)), 90, 90.0), ([10.0], 99, 10.0),
    ([0, 10], 90, 9.0), ([5, 1, 3], 0, 1.0), ([5, 1, 3], 100, 5.0)])
def test_percentile(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_pooled_gaps_tpot_and_spread():
    assert stats.pooled_gaps_ms([[0.0, 0.1, 0.3], [1.0], [2.0, 2.05]]) == \
        pytest.approx([100.0, 200.0, 50.0])
    assert stats.tpot_ms(1.0, 2.0, 11) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx(
        (q[2] - q[0]) / statistics.median(xs))


def _row(rate, late=450.0, failed=0):
    return {"rate": rate, "failed": failed, "ttft_ms.p50_late": late}


@pytest.mark.parametrize("rows,want", [
    ([_row(1), _row(2, 480), _row(3, 2700)], 2),
    ([_row(1), _row(2, failed=1), _row(3)], 1),        # a gap in the ladder
    ([_row(1, failed=2)], None),
    ([_row(3, 500), _row(1), _row(2)], 3),             # order of rows is free
    ([_row(1), _row(2, 900), _row(3, 901)], 2),        # twice the lowest's
])
def test_knee_rule(rows, want):
    assert stats.knee(rows) == want


def _hf(name):
    return json.loads((ROOT / "chipbench/configs" / name
                       / "config.json").read_text())


def test_weight_bytes_qwen25_7b_int8_by_hand():
    # per layer: q 3584x3584, k and v 3584x512, o 3584x3584,
    # gate/up 3584x18944, down 18944x3584: one byte each + 4 bytes a column
    kern = (3584 * 3584 + 4 * 3584) + 2 * (3584 * 512 + 4 * 512) \
        + (3584 * 3584 + 4 * 3584) + 2 * (3584 * 18944 + 4 * 18944) \
        + (18944 * 3584 + 4 * 3584)
    layer = kern + (3584 + 2 * 512) * 2 + 2 * 3584 * 2
    want = 28 * layer + 3584 * 2 + (3584 * 152064 + 4 * 152064)
    got = bytes_model.decode_weight_stream_bytes(_hf("qwen25-7b-int8"), "int8")
    assert got == want == 7077123072


def test_weight_bytes_qwen25_3b_bf16_by_hand():
    kern = 2 * (2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008)
    layer = kern + (2048 + 2 * 256) * 2 + 2 * 2048 * 2
    want = 36 * layer + 2048 * 2 + 2048 * 151936 * 2
    got = bytes_model.decode_weight_stream_bytes(_hf("qwen25-3b-bf16"),
                                                 "bfloat16")
    assert got == want == 6171877376


def test_weight_bytes_refuses_an_unknown_type():
    with pytest.raises(ValueError):
        bytes_model.decode_weight_stream_bytes(_hf("qwen25-3b-bf16"), "fp8")


def test_peaks_table_knows_v5e_and_nothing_by_default():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


# A hand-made trace in load()'s structure: one chip, two programs.
SYNTH = {"/device:TPU:0": {
    "XLA Modules": [
        {"name": "jit_decode_multi(1)", "start": 0.000, "dur": 0.080},
        {"name": "jit_prefill_install(2)", "start": 0.100, "dur": 0.050},
        {"name": "jit_decode_multi(1)", "start": 0.160, "dur": 0.040}],
    "XLA Ops": [
        {"name": "fusion.1", "start": 0.000, "dur": 0.030},
        {"name": "_paged_attention_impl.7", "start": 0.030, "dur": 0.010},
        {"name": "fusion.2", "start": 0.035, "dur": 0.045},   # overlaps
        {"name": "fusion.9", "start": 0.100, "dur": 0.050},
        {"name": "_paged_attention_impl.7", "start": 0.160, "dur": 0.020},
        {"name": "fusion.1", "start": 0.180, "dur": 0.020}]}}


def test_trace_reduction_on_a_hand_made_trace():
    busy, window = xplane.busy_and_window(SYNTH)
    assert busy == pytest.approx(0.080 + 0.050 + 0.040)
    assert window == pytest.approx(0.200)
    d = xplane.module_durations(SYNTH)
    assert d["decode_multi"] == pytest.approx([0.080, 0.040])
    assert d["prefill_install"] == pytest.approx([0.050])
    sums = xplane.ops_inside(SYNTH, "decode_multi",
                             lambda e: e["name"].startswith("_paged"))
    assert sums == pytest.approx([0.010, 0.020])
    ops = dict(xplane.top_ops(SYNTH))
    assert ops["decode_multi/fusion"] == pytest.approx(0.030 + 0.045 + 0.020)
    assert ops["decode_multi/_paged_attention_impl"] == pytest.approx(0.030)
    assert ops["prefill_install/fusion"] == pytest.approx(0.050)
    from chipbench import hostspans

    # a trace without host spans: one row, every gap between programs
    assert hostspans.idle_by_span(SYNTH, {}) == [
        ["unnamed", pytest.approx(0.020 + 0.010)]]
    assert xplane.program_name("jit_prefill_install_nc(123)") == \
        "prefill_install_nc"
    assert xplane.op_stem("%fusion.123") == "fusion"
    assert xplane.merge([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]


def test_trace_reduction_on_a_recorded_tpu_trace():
    """The head of a trace recorded on a TPU v5e (PR 23, cell
    qwen25-3b-bf16.agent-prefix): one whole `prefill_install` call and the
    first 12 ms of the `decode_multi` call after it, names and times as
    `xplane.load` gives them."""
    ir = json.loads((ROOT / "tests/chipbench/data/trace_head_tpu_v5e.json")
                    .read_text())
    assert list(ir) == ["/device:TPU:0"]
    busy, window = xplane.busy_and_window(ir)
    assert busy == pytest.approx(0.317294335, rel=1e-6)
    assert window == pytest.approx(0.320513533, rel=1e-6)
    assert 100 * (1 - busy / window) == pytest.approx(1.0044, rel=1e-3)
    d = xplane.module_durations(ir)
    assert d == {"prefill_install": [pytest.approx(0.050226763)],
                 "decode_multi": [pytest.approx(0.274422101)]}
    ops = dict(xplane.top_ops(ir))
    assert ops["prefill_install/fusion"] == pytest.approx(0.019618696, rel=1e-6)
    assert ops["prefill_install/copy"] == pytest.approx(0.018816489, rel=1e-6)
    assert not any(k.endswith("/while") for k in ops)
    from chipbench import hostspans

    assert hostspans.idle_by_span(ir, {}) == [
        ["unnamed", pytest.approx(0.003204217, rel=1e-6)]]
    kernel = xplane.ops_inside(
        ir, "decode_multi", lambda e: e["name"].startswith("_paged_attention"))
    assert kernel == [pytest.approx(0.001008347, rel=1e-6)]   # 6 calls
    # the per-layer readers on the same trace
    _, search = harness.load_bench(ROOT / "BENCHMARK.json")
    ctx = {"trace": ir, "engine": {"decode_horizon": 8, "weights": "bfloat16"},
           "hf": _hf("qwen25-3b-bf16"), "device": {"kind": "TPU v5 lite"},
           "family": harness.Family(search)}
    step = harness.load_reader(search, "prog.decode_step_ms")(ctx)
    assert step == pytest.approx(274.422101 / 8)
    assert harness.load_reader(search, "prog.prefill_call_ms")(ctx) == \
        pytest.approx(50.226763)
    assert harness.load_reader(search, "kernel.paged_attn_ms")(ctx) == \
        pytest.approx(1.008347 / 8)
    assert harness.load_reader(search, "device.idle_pct")(ctx) == \
        pytest.approx(1.0044, rel=1e-3)
    # through the default family: the number the parent's arithmetic gave
    # (bytes_model.py called by the reader itself), to the last digit
    assert harness.load_reader(search, "device.decode_weight_bw_pct")(ctx) == \
        100.0 * (6171877376 / 819e9) / (step / 1000.0)
    # the 25 rows the line before the result holds, the ten in it
    assert len(xplane.top_ops(ir, 25)) == min(25, len(xplane.top_ops(ir, 99)))
    assert xplane.top_ops(ir, 25)[:10] == xplane.top_ops(ir)


# ------------------------------------------- what `correct` compares (run.py)
class _Cell:
    def __init__(self, mix=None, check_requests=8, check_logprobs=5):
        self.mix, self.check_requests = mix or AGENT, check_requests
        self.check_logprobs = check_logprobs
        self.engine = {"hash_block_size": 128}


def _window(mix, rate, seconds, seed):
    return [r for r in loadgen.schedule(mix, rate, seconds, seed, 151936)
            if r.phase == "window"]


@pytest.mark.parametrize("mix,rate,n", [(CHAT, 3.2, 6), (AGENT, 4.8, 8)])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 9])
def test_check_sample_has_the_longest_and_one_of_each_stretch(mix, rate, n,
                                                              seed):
    from chipbench import run

    w = _window(mix, rate, 50, seed)
    got = run.check_sample(_Cell(mix, n), w, seed)
    assert len(got) == n and len({r.rid for r in got}) == n
    assert got[0] is max(w, key=lambda r: len(r.prompt) + r.max_tokens)
    again = run.check_sample(_Cell(mix, n), _window(mix, rate, 50, seed), seed)
    assert [r.rid for r in got] == [r.rid for r in again]
    other = run.check_sample(_Cell(mix, n), w, seed + 1)
    assert [r.rid for r in got] != [r.rid for r in other]
    # one from each equal stretch of the window: every seed's compared
    # requests are spread over the window alike
    rest = [r for r in w if r is not got[0]]
    for i, r in enumerate(got[1:]):
        assert r in rest[i * len(rest) // (n - 1):
                         (i + 1) * len(rest) // (n - 1)]


@pytest.mark.parametrize("n_window,n", [(0, 3), (1, 3), (2, 8)])
def test_check_sample_of_a_nearly_empty_window(n_window, n):
    from chipbench import run

    w = _window(AGENT, 4.8, 50, 1)[:n_window]
    got = run.check_sample(_Cell(AGENT, n), w, 1)
    assert len(got) == n_window and len({r.rid for r in got}) == n_window


def _rec(lps, tokens):
    from chipbench import harness

    r = harness.Record(loadgen.Request("w0", 0.0, [300], tokens), 0.0)
    r.chunks, r.lps = [(0.0, tokens)], lps
    return r


@pytest.mark.parametrize("lps,tokens,want", [
    ([{"AAE": -0.1, "AAF": -2.5}, {"AAG": -0.2, "AAA": -3.0}], 2,
     ([[4, 5], [6, 0]], [[-0.1, -2.5], [-0.2, -3.0]])),
    ([{"AAE": -0.1, "AAF": -2.5}], 2, None),            # a token without
    ([{"AAE": -0.1}, {"AAG": -0.2, "AAA": -3.0}], 2, None),   # fewer than k
    ([], 2, None),
])
def test_served_logprobs_are_read_whole_or_not_at_all(lps, tokens, want):
    from chipbench import run

    assert run.served_logprobs(_rec(lps, tokens), 2) == want


def test_measure_by_hand():
    import numpy as np

    from chipbench import reference, run

    lg = np.log(np.array([[0.5, 0.25, 0.125, 0.125],
                          [0.1, 0.2, 0.3, 0.4]])) + 7.0   # any shift
    ids, vals = reference.top_logprobs(lg, 2)
    assert ids.tolist() == [[0, 1], [3, 2]]
    assert np.allclose(np.exp(vals), [[0.5, 0.25], [0.4, 0.3]])
    exact = run.measure([lg], [[0, 3]], [(ids, vals)])
    assert exact["gap_max"] == 0 and exact["flipped"] == 0
    assert exact["lp_values"] == 4 and exact["lp_rms"] < 1e-12
    # served token 2 where the reference prefers 3, log-probabilities off by
    # +0.1, -0.1, +0.3, 0
    off = run.measure([lg], [[0, 2]], [(ids, vals + [[0.1, -0.1], [0.3, 0.0]])])
    assert off["flipped"] == 1
    assert off["gap_max"] == pytest.approx(np.log(0.4 / 0.3))
    assert off["gap_mean"] == pytest.approx(np.log(0.4 / 0.3) / 2)
    assert off["lp_max"] == pytest.approx(0.3)
    assert off["lp_rms"] == pytest.approx((0.11 / 4) ** 0.5)
    assert "lp_rms" not in run.measure([lg], [[0, 3]], None)
    assert "lp_rms" not in run.measure([lg], [[0, 3]], [None])


@pytest.mark.parametrize("filled,end,want", [
    (200, 260, [200, 192]), (260, 150, [150, 192]), (0, 0, [0, 192])])
def test_prefix_blocks_reads_the_fewest_held_against_the_mixes_prefixes(
        filled, end, want):
    from chipbench import run

    marks = {"stats_filled": {"cached_blocks": filled},
             "stats": {"cached_blocks": end}}
    assert run.prefix_blocks(_Cell(AGENT), marks) == want
    assert run.prefix_blocks(_Cell(CHAT), marks) is None


@pytest.mark.parametrize("limits,cmp_,blocks,want", [
    ({"gap_max": 0.3}, {"gap_max": 0.1}, None, True),
    ({"gap_max": 0.3, "lp_rms": 0.02}, {"gap_max": 0.1, "lp_rms": 0.01},
     [200, 192], True),
    ({"gap_max": 0.3, "lp_rms": 0.02}, {"gap_max": 0.1, "lp_rms": 0.03},
     [200, 192], False),
    ({"gap_max": 0.3, "lp_rms": 0.02}, {"gap_max": 0.1}, None, False),
    ({"gap_max": 0.3}, {"gap_max": 0.1}, [100, 192], False),
    ({"gap_max": 0.3}, None, None, False),
])
def test_decide_holds_every_number_the_cell_limits(limits, cmp_, blocks, want):
    from chipbench import run

    ok, checks = run.decide(limits, 0, 0, 0, {"paged_attention": "pallas"},
                            harness.DECODE_PATHS, blocks, cmp_)
    assert ok is want
    assert all(len(v) == 2 for v in checks.values())   # number beside limit
    assert set(limits) <= set(checks)
    assert checks["decode_multi.paged_attention"] == ["pallas", "pallas*"]


@pytest.mark.parametrize("paths,required,want", [
    ({"paged_attention": "pallas (shard_map model=4)", "linear_scan": "pallas-chunked"},
     {"paged_attention": "pallas", "linear_scan": "pallas"}, True),
    ({"paged_attention": "pallas", "linear_scan": "xla (cpu backend)"},
     {"paged_attention": "pallas", "linear_scan": "pallas"}, False),
    ({"paged_attention": "pallas"},                     # an op never taken
     {"paged_attention": "pallas", "linear_scan": "pallas"}, False),
    ({"paged_attention": "xla (cpu backend)", "linear_scan": "pallas"},
     {"linear_scan": "pallas"}, True),                  # only what is named
    ({}, {}, False),                     # nothing named, nothing held
    ({"paged_attention": "xla (cpu backend)"}, {"paged_attention": ""}, False),
])
def test_decide_holds_every_op_the_configuration_names(paths, required, want):
    from chipbench import run

    ok, checks = run.decide({"gap_max": 0.3}, 0, 0, 0, paths, required, None,
                            {"gap_max": 0.1})
    assert ok is want
    for op, prefix in required.items():
        assert checks["decode_multi." + op] == [paths.get(op, ""),
                                                prefix + "*"]
    assert not any(k.startswith("decode_multi.") and k[13:] not in required
                   for k in checks)


def test_resolve_cell_reads_the_decode_paths_and_the_family():
    bench, search = harness.load_bench(
        ROOT / "tests/chipbench/data/BENCHMARK.json")
    toy = harness.resolve_cell(bench, search, "tiny-moe.tiny-chat")
    assert toy.decode_paths == {"paged_attention": "pallas"}
    assert toy.family.name == "toy-moe" and toy.family.search == search
    old = harness.resolve_cell(bench, search, "tiny-qwen2.tiny-chat")
    assert old.decode_paths == harness.DECODE_PATHS and old.family.name == ""
    assert old.decode_paths is not harness.DECODE_PATHS


@pytest.mark.parametrize("stated", [
    {}, {"paged_attention": ""}, {"paged_attention": "pallas", "scan": ""},
    {"paged_attention": None}, ["paged_attention"], "pallas", None])
def test_decode_paths_that_would_hold_no_op_are_refused(tmp_path, stated):
    """Data alone cannot take the kernel check away: a configuration whose
    `decode_paths` names no op, or gives a prefix every path has, resolves
    to no cell."""
    data = ROOT / "tests/chipbench/data"
    bench, search = harness.load_bench(data / "BENCHMARK.json")
    shutil.copytree(data / "configs/tiny-moe", tmp_path / "cfg")
    cfg = tmp_path / "cfg/config.json"
    hf = json.loads(cfg.read_text())
    hf["chipbench"]["decode_paths"] = stated
    cfg.write_text(json.dumps(hf))
    bench["configs"][1]["file"] = str(cfg)
    with pytest.raises(harness.Failure,
                       match=r"cfg/config.json.*decode_paths"):
        harness.resolve_cell(bench, search, "tiny-moe.tiny-chat")
    del hf["chipbench"]["decode_paths"]              # absent: the default
    cfg.write_text(json.dumps(hf))
    cell = harness.resolve_cell(bench, search, "tiny-moe.tiny-chat")
    assert cell.decode_paths == harness.DECODE_PATHS


# ------------------------------------------ engine.json beyond the eleven keys
ELEVEN = {"weights": "int8", "num_pages": 64, "page_size": 16,
          "hash_block_size": 128, "max_batch_size": 4, "max_seq_len": 256,
          "prefill_buckets": [128, 256], "decode_horizon": 8,
          "warmup_programs": False, "tp": 1, "replicas": 1}


def _engine_dir(tmp_path, **more):
    (tmp_path / "engine.json").write_text(json.dumps({**ELEVEN, **more}))
    return tmp_path


def test_engine_json_takes_engine_config_fields_by_name(tmp_path):
    from chipbench import engine_setup

    eng = engine_setup.read_engine_json(_engine_dir(tmp_path, engine_config={
        "prefill_chunk_tokens": 64, "admission_horizon": 4}))
    assert engine_setup.engine_config_kwargs(eng, ()) == {
        "prefill_chunk_tokens": 64, "admission_horizon": 4}
    assert engine_setup.engine_config_kwargs(ELEVEN, ()) == {}
    shutil.copy(ROOT / "tests/chipbench/data/configs/tiny-qwen2/config.json",
                tmp_path / "config.json")
    ecfg, _ = engine_setup.build_engine_config(tmp_path, 5, "t")
    assert (ecfg.prefill_chunk_tokens, ecfg.admission_horizon) == (64, 4)
    assert ecfg.num_pages == 64 and ecfg.prefill_buckets == (128, 256)


@pytest.mark.parametrize("extra,message", [
    ({"engine_config": {"state_slots": 3}}, "state_slots"),     # no field
    # what build_engine_config passes itself, whichever key it comes from
    ({"engine_config": {"num_pages": 9}}, "decided elsewhere.*num_pages"),
    ({"engine_config": {"seed": 1}}, "decided elsewhere.*seed"),
    ({"engine_config": {"model_family": "x"}},
     "decided elsewhere.*model_family"),
    ({"engine_config": {"mesh": None}}, "decided elsewhere.*mesh"),
    ({"engine_config": [1]}, "engine_config must be an object"),
    ({"state_slots": 3}, "unknown keys"),
])
def test_engine_json_refuses_what_is_no_engine_config_field(tmp_path, extra,
                                                            message):
    """The documented ValueError, never a TypeError from EngineConfig for a
    name given twice: the refused names are the call's own arguments."""
    from chipbench import engine_setup

    shutil.copy(ROOT / "tests/chipbench/data/configs/tiny-qwen2/config.json",
                tmp_path / "config.json")
    with pytest.raises(ValueError, match=message):
        engine_setup.build_engine_config(_engine_dir(tmp_path, **extra), 5,
                                         "t")


@pytest.mark.parametrize("name", ["qwen25-7b-int8", "qwen25-3b-bf16"])
def test_the_two_engine_files_of_pr_23_hold_the_eleven_keys_alone(name):
    """ISSUE 26: the two files the benchmark had stay as they were. A
    configuration a later PR adds may carry `engine_config`: this test
    names the two, and the drop-in test of test_chipbench_files.py runs it
    in a copy that holds such a configuration."""
    eng = json.loads((ROOT / "chipbench/configs" / name / "engine.json")
                     .read_text())
    assert set(eng) == set(ELEVEN)


def test_sizing_line_holds_the_arguments_against_the_chip_and_the_floors():
    from chipbench import sizing

    bench, search = harness.load_bench(
        ROOT / "tests/chipbench/data/BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, "tiny-moe.tiny-chat")
    line = sizing.size_line("tiny-moe", cell.family, cell.hf, cell.engine,
                            {"argument_gib": 4.0}, 16e9)
    # embedding and head 2 x 1024 x 256, per layer 2 x (256 x 256 + 256 x
    # 128) attention + 256 x 4 float32 router + 3 x 4 x 256 x 128 experts +
    # two norms, one final norm: bfloat16
    want = 2 * (2 * 1024 * 256 + 2 * (2 * 256 * 256 + 2 * 256 * 128
                                      + 12 * 256 * 128 + 2 * 256) + 256) \
        + 2 * 256 * 4 * 4
    assert line["family"] == "toy-moe"
    assert line["family_weights_bytes"] == want
    assert line["decode_arguments_bytes"] == 4 * 2 ** 30
    assert line["arguments_pct_of_hbm"] == pytest.approx(26.84, abs=0.01)
    assert (line["floor_pct"], line["floor_pct_where_busy_75"]) == (25.0, 12.5)
