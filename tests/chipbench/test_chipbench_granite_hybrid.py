"""The granite-hybrid family's benchmark files (chipbench/families/
granite-hybrid/) and the readers of its kernel's per-layer metrics: the
reference against the program's own forward at the family's toy size under
tests/chipbench/data (its BENCHMARK.granite-hybrid.json is found by
test_chipbench_family.py, which holds the family to the contract and its
weights to being a pure function of the seed), the byte counts at the
benchmark's configuration, and the readers on a small trace."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, hostspans
from chipbench.engine_setup import build_engine_config
from chipbench_entries import first_token_is_read

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests/chipbench/data"
BENCH, SEARCH = harness.load_bench(DATA / "BENCHMARK.granite-hybrid.json")
TOY = DATA / "configs/tiny-granite-hybrid"
REAL = ROOT / "chipbench/configs/granite-4.0-h-micro"
CELL = "granite-4.0-h-micro.chat-short"
SEED = 2 ** 31 + 77


def _family(config_dir):
    hf = json.loads((config_dir / "config.json").read_text())
    return harness.family_of(SEARCH, hf), hf


# ------------------------------------------- the reference and the program
def _program_logits(family, hf, toks, n):
    """Last-token logits of the first `n` tokens from the program's own
    prefill forward in float32 (the chunked scan, a bucket of 128)."""
    from xllm_service_tpu.models import granite_hybrid as gh

    ecfg, _ = build_engine_config(TOY, SEED, "t")
    assert ecfg.model_family == "granite_hybrid"
    mcfg = dataclasses.replace(ecfg.model, dtype=jnp.float32)
    params = family.weights.make_params(SEED, hf, "bfloat16")
    kv = jnp.zeros((mcfg.kv_layers, 2, 64, mcfg.num_kv_heads, 16,
                    mcfg.kv_head_dim), jnp.float32)
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        logits, _, state = gh.prefill_forward(
            params, mcfg, jnp.asarray([toks + [0] * (128 - len(toks))]),
            jnp.arange(128)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([n]))
    assert state["ssm"].shape == (8, 1, 128, 128)
    return np.asarray(logits[0], np.float32)


def test_familys_reference_agrees_with_the_programs_forward():
    """Float32 on both sides at `highest` precision: what is left is the
    order of float32 sums, and the two sides share no algorithm (the
    reference runs the recurrence token by token, the program the chunked
    scan): 1e-6 on logits whose spread is 0.01 (5e-8 read; the embedding's
    rows have norm 1 / `embedding_multiplier`, `logits_scaling` is 8)."""
    family, hf = _family(TOY)
    assert family.name == "granite-hybrid"
    toks = np.random.default_rng(1).integers(256, 1024, 96).tolist()
    want = family.reference.logits_at(SEED, hf, "bfloat16", [toks],
                                      [list(range(96))])[0]
    assert 0.005 < want.std() < 0.02
    for n in (96, 37, 5):
        got = _program_logits(family, hf, toks, n)
        assert np.max(np.abs(got - want[n - 1])) < 1e-6
    # the recurrence matters: another seed's weights read far off
    other = family.reference.logits_at(SEED + 1, hf, "bfloat16", [toks],
                                       [[95]])[0]
    assert np.max(np.abs(other - want[95])) > 0.01
    # and the control (one precision lower) moves it, padded or not
    low = family.reference.logits_at(SEED, hf, "bfloat16", [toks], [[4, 95]],
                                     "int8", pad_len=256, pad_pos=8)[0]
    assert low.shape == (2, hf["vocab_size"])
    assert 1e-4 < np.max(np.abs(low - want[[4, 95]])) < 0.01


def test_familys_bytes_at_the_benchmarks_configuration():
    family, hf = _family(REAL)
    b = family.bytes
    assert hf["chipbench"]["reduced"] == [] and len(hf["layer_types"]) == 40
    assert b.ssm_state_bytes_per_slot(hf) == 75_497_472
    assert b.kv_bytes_per_token(hf) == 16_384
    # 2 x the state of every live slot, and its operands
    assert b.ssm_update_bytes(hf, 0) == 0
    per_slot = b.ssm_update_bytes(hf, 1)
    assert 2 * 75_497_472 < per_slot < 2 * 75_497_472 * 1.02
    assert b.ssm_update_bytes(hf, 24) == 24 * per_slot
    # every weight but nothing twice: the tree's bytes (embedding = head)
    tree = family.weights.param_shapes(hf, "bfloat16")
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert b.decode_weight_stream_bytes(hf, "bfloat16") == held
    assert 6.37e9 < held < 6.39e9
    with pytest.raises(ValueError, match="bfloat16"):
        b.decode_weight_stream_bytes(hf, "int8")


def test_the_cell_resolves_and_is_due_every_standing_metric_it_moves():
    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert cell.family.name == "granite-hybrid"
    assert cell.decode_paths == {"paged_attention": "pallas",
                                 "ssm_update": "pallas"}
    assert cell.engine["max_batch_size"] == 32 and cell.chips == 1
    e2e = {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)}
    assert e2e - {"ttft_ms.mean"} == {"tpot_ms.p90", "gap_ms.p95",
                                      "out_tok_per_s", "setup_s"}
    # the first token: judged, or read through the cell's own three entries
    assert first_token_is_read(bench, CELL, "chat-short")
    due = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    assert {"kernel.ssm_update_ms", "kernel.ssm_update_state_bw_pct",
            "kernel.paged_attn_ms",
            "kernel.paged_attn_kv_bw_pct", "kernel.paged_attn_run_chunk_pct",
            "prog.decode_step_ms", "device.decode_weight_bw_pct"} <= due
    # and no other cell is given the new ones
    for other in ("qwen25-7b-int8.chat", "qwen25-3b-bf16.agent-prefix"):
        names = {m["name"] for m in harness.metrics_for(
            bench, "per_layer", other)}
        assert not {n for n in names if "ssm" in n or "chat-short" in n}


# What the chip read in this cell (TPU v5e, PR 34, `run.py --control`): each
# number's extremes over the sound runs (14 seeds) and over the int8
# control (4 seeds), each of 680-1120 served tokens.
SOUND_LARGEST = {"gap_max": 0.0024986, "gap_mean": 8.370e-5, "lp_rms": 6.0885e-4}
CONTROL_SMALLEST = {"gap_max": 0.0048829, "gap_mean": 4.0298e-4,
                    "lp_rms": 1.47582e-3}


@pytest.mark.parametrize("number", ["gap_max", "gap_mean", "lp_rms"])
def test_each_limit_of_the_cell_lies_between_the_chips_two_readings(number):
    """The sound runs' largest passes `run.decide` with room, and the
    control's smallest of that one number alone makes it not correct."""
    from chipbench import run

    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert set(cell.limits) == set(SOUND_LARGEST) and cell.check_logprobs == 5

    def verdict(cmp_):
        return run.decide(cell.limits, 0, 0, 0, cell.decode_paths,
                          cell.decode_paths, None, cmp_)[0]

    assert verdict(SOUND_LARGEST) is True
    assert verdict(dict(SOUND_LARGEST,
                        **{number: CONTROL_SMALLEST[number]})) is False
    assert (1.5 * SOUND_LARGEST[number] < cell.limits[number]
            < CONTROL_SMALLEST[number] / 1.2)

# -------------------------------------------------------------- the readers
def _span(name, a, b):
    return {"name": name, "start": a, "dur": b - a}


def _toy_trace(markers=((7, 8), (9, 8), (9, 4))):
    """Three `decode_multi` executions with two kernel events a step and a
    prefill between them; `markers`: (live, steps) of the marker that lands
    just after each execution (None: it is missing)."""
    mods = [("jit_decode_multi(1)", 0.000, 0.080),
            ("jit_prefill_install(2)", 0.081, 0.095),
            ("jit_decode_multi(1)", 0.096, 0.176),
            ("jit_decode_multi(1)", 0.178, 0.218)]
    ops, k = [], 0
    for name, a, b in mods:
        if "decode" not in name:
            ops.append({"name": "fusion.9", "start": a, "dur": b - a})
            continue
        steps = round((b - a) / 0.010)
        for s in range(steps):
            for j in range(2):        # two Mamba layers
                k += 1
                ops.append({"name": f"_ssm_update_impl.{k}",
                            "start": a + s * 0.010 + j * 0.004,
                            "dur": 0.002})
                ops.append({"name": f"fusion.{k}",
                            "start": a + s * 0.010 + j * 0.004 + 0.002,
                            "dur": 0.002})
    ir = {"/device:TPU:0": {
        "XLA Modules": [{"name": n, "start": a, "dur": b - a}
                        for n, a, b in mods],
        "XLA Ops": ops}}
    lands = [0.0803, 0.1764, 0.2185]
    pump = [_span("fetch_wait", 0.001, 0.0802), _span("emit", 0.0802, 0.0808),
            _span("fetch_wait", 0.097, 0.1763), _span("emit", 0.1763, 0.177),
            _span("fetch_wait", 0.179, 0.2184)]
    for t, m in zip(lands, markers):
        if m is not None:
            pump.append(_span(f"decode_live.{m[0]}.{m[1]}", t, t + 2e-7))
    return ir, {"python#3": sorted(pump, key=lambda s: (s["start"],
                                                        -s["dur"]))}


def _ctx(ir, spans, family=None, hf=None):
    fam, real = _family(REAL)
    return {"trace": ir, "host_spans": spans, "agent_stats": {},
            "hotpath": {}, "hf": hf or real, "family": family or fam,
            "engine": {"decode_horizon": 8, "weights": "bfloat16"},
            "device": {"kind": "TPU v5 lite"}, "cell": CELL}


def _share(calls, hf, bytes_):
    """[(live, steps, kernel seconds)] -> the share by hand."""
    need = sum(bytes_.ssm_update_bytes(hf, live) * steps
               for live, steps, _ in calls)
    return 100 * need / 819e9 / sum(s for _, _, s in calls)


def test_ssm_update_ms_is_the_kernels_time_a_step():
    read = harness.load_reader(harness.load_bench(ROOT / "BENCHMARK.json")[1],
                               "kernel.ssm_update_ms")
    ir, spans = _toy_trace()
    # executions of 8, 8 and 4 steps x 2 events of 2 ms: 32, 32, 16 ms;
    # the median over the configured horizon
    assert read(_ctx(ir, spans)) == pytest.approx(32.0 / 8)
    no_kernel = {p: {ln: [e for e in evs if "ssm" not in e["name"]]
                     for ln, evs in pl.items()} for p, pl in ir.items()}
    assert read(_ctx(no_kernel, spans)) is None        # a parent commit
    assert read(_ctx(None, spans)) is None


def test_state_bw_pct_prices_each_traced_call_from_its_own_marker():
    search = harness.load_bench(ROOT / "BENCHMARK.json")[1]
    read = harness.load_reader(search, "kernel.ssm_update_state_bw_pct")
    fam, hf = _family(REAL)
    ir, spans = _toy_trace()
    calls = [(7, 8, 0.032), (9, 8, 0.032), (9, 4, 0.016)]
    assert read(_ctx(ir, spans)) == pytest.approx(_share(calls, hf, fam.bytes))
    mod = harness.load_file(harness._find(
        search, "layers", "kernel.ssm_update_state_bw_pct.py"))
    assert mod.paired(ir, spans) == [
        (live, steps, pytest.approx(s)) for live, steps, s in calls]


@pytest.mark.parametrize("missing", [0, 1, 2])
def test_a_missing_marker_drops_its_call_and_cannot_raise_the_share(missing):
    """Bytes and seconds go together: the share of what is left is the
    share of the calls that kept their marker, never more than the largest
    single call's (which no kernel can push past 100)."""
    read = harness.load_reader(harness.load_bench(ROOT / "BENCHMARK.json")[1],
                               "kernel.ssm_update_state_bw_pct")
    fam, hf = _family(REAL)
    calls = [(7, 8, 0.032), (9, 8, 0.032), (9, 4, 0.016)]
    markers = [(c[0], c[1]) for c in calls]
    markers[missing] = None
    ir, spans = _toy_trace(markers)
    kept = [c for i, c in enumerate(calls) if i != missing]
    got = read(_ctx(ir, spans))
    assert got == pytest.approx(_share(kept, hf, fam.bytes))
    assert got <= max(_share([c], hf, fam.bytes) for c in calls) * (1 + 1e-9)


def test_state_bw_pct_reads_nothing_where_there_is_nothing_to_read():
    read = harness.load_reader(harness.load_bench(ROOT / "BENCHMARK.json")[1],
                               "kernel.ssm_update_state_bw_pct")
    ir, spans = _toy_trace()
    assert read(_ctx(ir, spans)) is not None
    # a program without the markers (a parent commit), without the kernel,
    # a family that counts no such bytes (the default): nothing, never a 0
    phases = {ln: [s for s in evs if not s["name"].startswith("decode_live")]
              for ln, evs in spans.items()}
    assert read(_ctx(ir, phases)) is None
    assert read(_ctx(ir, {})) is None and read(_ctx(ir, None)) is None
    no_kernel = {p: {ln: [e for e in evs if "ssm" not in e["name"]]
                     for ln, evs in pl.items()} for p, pl in ir.items()}
    assert read(_ctx(no_kernel, spans)) is None
    qwen = json.loads((ROOT / "chipbench/configs/qwen25-3b-bf16/config.json")
                      .read_text())
    assert read(_ctx(ir, spans, harness.Family(SEARCH), qwen)) is None


def test_the_markers_take_no_idle_gap_from_the_phases():
    """`breakdown.idle_gaps` on a recorded TPU trace: the rows it has with
    the pump's phases alone are the rows it has with a marker, shorter
    than a microsecond, dropped into every gap between two programs."""
    ir = json.loads((DATA / "trace_head_tpu_v5e.json").read_text())
    mods = ir["/device:TPU:0"]["XLA Modules"]
    gaps = [(m["start"] + m["dur"], n["start"]) for m, n in zip(mods, mods[1:])]
    assert gaps and all(b > a for a, b in gaps)
    pump = [_span("fetch_wait", mods[0]["start"], gaps[0][0] + 1e-5),
            _span("emit", gaps[0][0] + 1e-5, gaps[0][1] + 1e-4)]
    plain = hostspans.idle_by_span(ir, {"python#1": pump})
    assert plain and plain[0][0] in ("emit", "fetch_wait")
    marked = sorted(pump + [_span("decode_live.5.8", a + 2e-5, a + 2e-5 + 3e-7)
                            for a, _ in gaps],
                    key=lambda s: (s["start"], -s["dur"]))
    assert hostspans.idle_by_span(ir, {"python#1": marked}) == [
        [name, pytest.approx(s)] for name, s in plain]
    # and on the toy trace, whose markers sit inside `emit`
    ir, spans = _toy_trace()
    phases = {ln: [s for s in evs if not s["name"].startswith("decode_live")]
              for ln, evs in spans.items()}
    assert hostspans.idle_by_span(ir, spans) == [
        [name, pytest.approx(s)] for name, s in
        hostspans.idle_by_span(ir, phases)]


def test_the_readers_on_a_recorded_trace_of_the_cell():
    """0.3 s of a traced run of the cell on the chip (three decode calls of
    8 steps with 7, 8 and 8 sequences, two prefills between them; of the op
    line the two kernels' events): each execution finds the marker that
    landed after it, the kernel's time a step is the 36 layers', and the
    share, bytes and seconds from those same calls, stays under 100."""
    rec = json.loads((DATA / "trace_granite_tpu_v5e.json").read_text())
    search = harness.load_bench(ROOT / "BENCHMARK.json")[1]
    ctx = _ctx(rec["trace"], rec["host_spans"])
    mod = harness.load_file(harness._find(
        search, "layers", "kernel.ssm_update_state_bw_pct.py"))
    calls = mod.paired(rec["trace"], rec["host_spans"])
    assert [(live, steps) for live, steps, _ in calls] == [
        (7, 8), (8, 8), (8, 8)]
    fam, hf = _family(REAL)
    ms = harness.load_reader(search, "kernel.ssm_update_ms")(ctx)
    assert ms == pytest.approx(
        1000 * sorted(s for _, _, s in calls)[1] / 8)
    assert 0.8 < ms < 2.0                 # 7-8 live slots at ~0.2 ms each
    share = harness.load_reader(search, "kernel.ssm_update_state_bw_pct")(ctx)
    assert share == pytest.approx(_share(calls, hf, fam.bytes))
    assert 60 < share < 100
    # the paged-attention reader still finds its four layers' events
    assert harness.load_reader(search, "kernel.paged_attn_ms")(ctx) > 0
    # and the markers take no idle gap from the phases
    phases = {ln: [s for s in evs if not s["name"].startswith("decode_live")]
              for ln, evs in rec["host_spans"].items()}
    assert hostspans.idle_by_span(rec["trace"], rec["host_spans"]) == [
        [name, pytest.approx(s)] for name, s in
        hostspans.idle_by_span(rec["trace"], phases)]
