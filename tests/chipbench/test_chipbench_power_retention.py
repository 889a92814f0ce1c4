"""The power-retention family's benchmark files (chipbench/families/
power-retention/) and the readers of its two kernels' per-layer metrics: the
reference against the program's own forward at the family's toy size under
tests/chipbench/data (its BENCHMARK.power-retention.json is found by
test_chipbench_family.py, which holds the family to the contract and its
weights to being a pure function of the seed), the byte and operation counts
at the benchmark's configuration, the toy cell's limits through `run.compare`
and `run.decide`, and the readers on a small trace."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.engine_setup import build_engine_config
from chipbench_entries import (but_its_list, due, first_token_is_read,
                               per_layer, stands_after)

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests/chipbench/data"
BENCH, SEARCH = harness.load_bench(DATA / "BENCHMARK.power-retention.json")
TOY = DATA / "configs/tiny-power-retention"
TOY_CELL = "tiny-power-retention.tiny-chat"
REAL = ROOT / "chipbench/configs/brumby-14b-base"
CELL = "brumby-14b-base.doc-long"
SEED = 2 ** 31 + 77


def _family(config_dir):
    hf = json.loads((config_dir / "config.json").read_text())
    return harness.family_of(SEARCH, hf), hf


# ------------------------------------------- the reference and the program
def _program_logits(family, hf, toks, chunks):
    """Last-token logits after `chunks` (their lengths) of `toks` through
    the program's own prefill forward in float32, each chunk padded to a
    bucket of 64 and starting from the state the one before left."""
    from xllm_service_tpu.models import power_retention as pr

    ecfg, _ = build_engine_config(TOY, SEED, "t")
    assert ecfg.model_family == "power_retention"
    assert ecfg.prefill_chunk_tokens == 32 and ecfg.model.kv_layers == 0
    mcfg = dataclasses.replace(ecfg.model, dtype=jnp.float32)
    params = family.weights.make_params(SEED, hf, "bfloat16")
    kv = jnp.zeros((0, 2, 64, mcfg.num_kv_heads, 16, mcfg.head_dim))
    pt = jnp.zeros((1, 8), jnp.int32)
    state, at = None, 0
    with jax.default_matmul_precision("highest"):
        for c in chunks:
            logits, _, state = pr.prefill_forward(
                params, mcfg,
                jnp.asarray([toks[at:at + c] + [0] * (64 - c)]),
                at + jnp.arange(64)[None, :], kv, pt, jnp.asarray([at]),
                jnp.asarray([c]), state=state)
            at += c
    assert state["ret_s"].shape == (2, 1, 2, 9, 16, 16)
    return np.asarray(logits[0], np.float32)


def test_familys_reference_agrees_with_the_programs_forward():
    """Float32 on both sides at `highest` precision: what is left is the
    order of float32 sums, and the two sides share no algorithm (the
    reference has no phi and no state, the program the chunked form over a
    carried state): 2e-5 on logits whose spread is 1 (6e-6 read)."""
    family, hf = _family(TOY)
    assert family.name == "power-retention"
    toks = np.random.default_rng(1).integers(256, 1024, 96).tolist()
    want = family.reference.logits_at(SEED, hf, "bfloat16", [toks],
                                      [list(range(96))])[0]
    assert 0.5 < want.std() < 2.0
    for chunks in ([64, 32], [37], [5], [20, 40, 3]):
        got = _program_logits(family, hf, toks, chunks)
        assert np.max(np.abs(got - want[sum(chunks) - 1])) < 2e-5
    # the weights matter: another seed's read far off
    other = family.reference.logits_at(SEED + 1, hf, "bfloat16", [toks],
                                       [[95]])[0]
    assert np.max(np.abs(other - want[95])) > 0.5
    # and the control (one precision lower) moves it, padded or not
    low = family.reference.logits_at(SEED, hf, "bfloat16", [toks], [[4, 95]],
                                     "int8", pad_len=256, pad_pos=8)[0]
    assert low.shape == (2, hf["vocab_size"])
    assert 1e-3 < np.max(np.abs(low - want[[4, 95]])) < 0.5


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    """Every key of the catalog's `config` for Brumby-14B-Base under its own
    value, but `num_hidden_layers`, which `reduced` names; `assumed` lists
    each thing the config does not state."""
    hf = json.loads((REAL / "config.json").read_text())
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    group = hf["chipbench"]
    assert group["reduced"] == ["num_hidden_layers"]
    assert {k for k in catalog if hf[k] != catalog[k]} == {
        "num_hidden_layers"}
    assert hf["num_hidden_layers"] == 8
    assert group["published"] == {"num_hidden_layers": 40}
    assert set(group["assumed"]) >= {
        "retention_degree", "retention_gate", "retention_normaliser",
        "qk_norm_and_rotary", "state_dtype", "state_layout", "weights"}
    assert group["source"].endswith("Brumby-14B-Base/blob/main/config.json")
    eng = json.loads((REAL / "engine.json").read_text())
    assert eng["engine_config"] == {"prefill_chunk_tokens": 1024}
    assert eng["warmup_programs"] is True
    assert eng["prefill_buckets"][-1] >= eng["max_seq_len"] == 16896
    # pages never bind: every slot's longest sequence at once
    assert eng["num_pages"] >= eng["max_batch_size"] * (
        eng["max_seq_len"] // eng["page_size"])


def test_familys_counts_at_the_benchmarks_configuration():
    family, hf = _family(REAL)
    b = family.bytes
    # 8 layers x 8 KV heads x (65 slabs of 128 x 128 + 72 x 128) x 4
    assert b.retention_state_bytes(hf) == 8 * 8 * (65 * 128 + 72) * 128 * 4
    assert 274.9e6 < b.retention_state_bytes(hf) < 275.1e6
    assert b.kv_bytes_per_token(hf) is None
    assert b.retention_update_bytes(hf, 0) == 0
    assert b.retention_update_bytes(hf, 9) == 9 * 2 * b.retention_state_bytes(hf)
    # per token and layer: 8 heads x 2 x 65 x 128 x 128 x (5 + 1)
    assert b.retention_prefill_flops(hf, 1) == 8 * 8 * 2 * 65 * 128 * 128 * 6
    assert b.retention_prefill_flops(hf, 1024) == 1024 * b.retention_prefill_flops(hf, 1)
    # every weight but the embedding (a lookup): the tree's bytes less it
    tree = family.weights.param_shapes(hf, "bfloat16")
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    embed = hf["vocab_size"] * hf["hidden_size"] * 2
    assert b.decode_weight_stream_bytes(hf, "bfloat16") == held - embed
    assert 8.39e9 < held < 8.40e9
    with pytest.raises(ValueError, match="bfloat16"):
        b.decode_weight_stream_bytes(hf, "int8")
    # the program holds the state in the layout the counts assume
    from xllm_service_tpu.engine.engine import new_decode_state
    ecfg, _ = build_engine_config(REAL, 1, "t")
    d = jax.eval_shape(lambda: new_decode_state(ecfg))
    assert d["kv"].size == 0
    assert (d["ret_s"].size + d["ret_z"].size) * 4 == (
        ecfg.max_batch_size * b.retention_state_bytes(hf))


def test_the_cell_resolves_and_is_due_every_standing_metric_it_moves():
    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert cell.family.name == "power-retention"
    assert cell.decode_paths == {"retention_update": "pallas"}
    assert cell.engine["max_batch_size"] == 12 and cell.chips == 1
    assert cell.mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.7, "min": 1024,
        "max": 16384}
    assert cell.mix["output_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.6, "min": 32,
        "max": 512}
    assert cell.mix["ramp_s"] == 8 and cell.mix["shared_prefix"] is None
    assert (cell.check_requests, cell.check_logprobs) == (6, 5)
    e2e = {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)}
    assert e2e - {"ttft_ms.mean"} == {"tpot_ms.p90", "gap_ms.p95",
                                      "out_tok_per_s", "setup_s"}
    # the first token: judged, or read through the cell's own three entries
    assert first_token_is_read(bench, CELL, "doc-long")
    got = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    new = {n for n in ADDED if per_layer(bench, n) is not None}
    assert new >= set(ADDED) - {f"{n}.doc-long" for n in (
        "client.ttft_mean_ms", "engine.queue_ms", "engine.prefill_ms")}
    assert new | {"prog.decode_step_ms", "prog.prefill_call_ms",
                  "device.decode_weight_bw_pct", "block.mlp_ms",
                  "block.known_ops_pct", "device.idle_pct"} <= got
    # the six readers of keys and of a pool find nothing to read here, so
    # each lists the cells that hold keys and is not due in this one
    # (`test_a_reader_of_keys_lists_the_standing_cells_and_is_not_due_here`)
    assert not got & set(SILENT)
    # and no other cell is given the new ones, nor loses one it had
    for other in STANDING_CELLS:
        names = {m["name"] for m in harness.metrics_for(
            bench, "per_layer", other)}
        assert not names & new
        assert set(SILENT) <= names


STANDING_CELLS = ["qwen25-7b-int8.chat", "qwen25-3b-bf16.agent-prefix",
                  "granite-4.0-h-micro.chat-short",
                  "kanana-2-30b-a3b.chat-long"]

# The standing per-layer metrics that read keys or a pool of them, as the
# parent's BENCHMARK.json wrote each: none named a cell, so each was due in
# every cell that reports what it moves. This model holds no key, their
# readers return None here and a traced line would lack them, so each now
# lists the cells that stood (the contract's form of "no change").
SILENT = {
    "kernel.paged_attn_ms": ("ms", "lower", "device_trace", "kernels",
                             "tpot_ms.p90"),
    "kernel.paged_attn_kv_bw_pct": ("%", "higher", "device_trace", "kernels",
                                    "tpot_ms.p90"),
    "kernel.paged_attn_run_chunk_pct": ("%", "higher", "program_counter",
                                        "kernels", "tpot_ms.p90"),
    "block.attn_ms": ("ms", "lower", "device_trace", "programs",
                      "tpot_ms.p90"),
    "block.prefill_attn_ms": ("ms", "lower", "device_trace", "programs",
                              "gap_ms.p95"),
    "kernel.prefill_attn_ms": ("ms", "lower", "device_trace", "kernels",
                               "gap_ms.p95"),
}


@pytest.mark.parametrize("name", sorted(SILENT))
def test_a_reader_of_keys_lists_the_standing_cells_and_is_not_due_here(name):
    """The entry as it was written but for its list; a list that begins
    with the four cells that stood when this one came and never holds this
    one; due in each of those four and not here; and the reader finds
    nothing in a run of this family. Of the cells that came after, nothing:
    one whose model holds keys reads them through entries of its own."""
    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    entry = per_layer(bench, name)
    unit, better, source, layer, moves = SILENT[name]
    assert but_its_list(entry) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves}
    assert entry["workloads"][:len(STANDING_CELLS)] == STANDING_CELLS
    assert CELL not in entry["workloads"]
    assert all(due(bench, name, cell) for cell in STANDING_CELLS)
    assert not due(bench, name, CELL)
    ir, spans = _toy_trace()
    assert _reader(name)(_ctx(ir, spans)) is None


STANDING = [
    "master.schedule_ms", "agent.first_delta_ms", "engine.queue_ms",
    "engine.prefill_ms", "client.ttft_mean_ms",
    "agent.first_delta_ms.agent-prefix", "engine.queue_ms.agent-prefix",
    "engine.prefill_ms.agent-prefix", "prog.decode_step_ms",
    "prog.prefill_call_ms", "kernel.paged_attn_ms", "device.idle_pct",
    "device.decode_weight_bw_pct", "engine.prefix_hit_pct",
    "engine.batch_live_mean", "engine.kv_used_of_reserved_pct",
    "engine.host_busy_pct", "kernel.paged_attn_kv_bw_pct",
    "device.idle_unfed_pct", "engine.prefill_behind_steps",
    "kernel.paged_attn_run_chunk_pct", "kernel.ssm_update_ms",
    "kernel.ssm_update_state_bw_pct", "client.ttft_mean_ms.chat-short",
    "engine.queue_ms.chat-short", "engine.prefill_ms.chat-short",
    "kernel.moe_experts_ms", "kernel.moe_experts_weight_bw_pct",
    "engine.moe_experts_touched_mean", "client.ttft_mean_ms.chat-long",
    "engine.queue_ms.chat-long", "engine.prefill_ms.chat-long",
    "block.attn_ms", "block.mlp_ms", "block.head_ms", "block.sample_ms",
    "block.ssm_ms", "block.moe_ms", "block.unscoped_ms",
    "block.prefill_attn_ms", "block.known_ops_pct",
    "engine.prefill_padding_pct", "kernel.prefill_attn_ms"]

# The ten per-layer entries PR 41 appended, each listing this cell alone.
ADDED = [
    "kernel.retention_update_ms", "kernel.retention_update_state_bw_pct",
    "kernel.retention_prefill_ms", "kernel.retention_prefill_mxu_pct",
    "block.ret_ms", "engine.prefill_chunks_per_admission",
    "client.ttft_mean_ms.doc-long", "engine.queue_ms.doc-long",
    "engine.prefill_ms.doc-long", "prog.prefill_chunk_ms"]


def test_the_benchmark_gained_entries_and_lost_or_edited_none():
    """The 43 per-layer entries that stood before this cell come first, in
    their order, then PR 41's ten, each of which lists this cell alone; of
    those that stood the six of `SILENT` gained a list that begins with the
    cells that stood and never holds this one. An entry of either list
    that a `benchmark` PR has taken away since is that PR's to name
    (`BENCHMARK_EDITS`, test_chipbench_files.py); the rest keep their
    places. The configuration is there, and the cells begin with the four
    that stood and this one. Of what came after, nothing."""
    bench, _ = harness.load_bench(ROOT / "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    held = [n for n in STANDING + ADDED if n in names]
    assert len(held) >= len(STANDING + ADDED) - 9     # most still stand
    assert names[:len(held)] == held and stands_after(bench, ADDED, STANDING)
    for name in held:
        entry = per_layer(bench, name)
        if name in ADDED:
            assert entry["workloads"] == [CELL]
        if name in SILENT:
            assert CELL not in entry["workloads"]
            assert entry["workloads"][:len(STANDING_CELLS)] == STANDING_CELLS
    assert "brumby-14b-base" in [c["name"] for c in bench["configs"]]
    assert [w["name"] for w in bench["workloads"]][
        :len(STANDING_CELLS) + 1] == STANDING_CELLS + [CELL]


# What the chip read in this cell (TPU v5e, PR 41, `run.py --control`): each
# number's extremes over the sound runs (23 seeds) and over the int8 control
# (11 seeds), each of 786-1571 served tokens; and the room each limit keeps
# below the control (a maximum's tail is the longer, and its room is kept on
# the sound runs' side, where a reading over the limit refuses a run).
SOUND_LARGEST = {"gap_max": 0.130499, "gap_mean": 0.0020653, "lp_rms": 0.0287572}
CONTROL_SMALLEST = {"gap_max": 0.241815, "gap_mean": 0.0118219,
                    "lp_rms": 0.0734322}
ROOM_BELOW_CONTROL = {"gap_max": 1.2, "gap_mean": 1.25, "lp_rms": 1.25}


@pytest.mark.parametrize("number", ["gap_max", "gap_mean", "lp_rms"])
def test_each_limit_of_the_cell_lies_between_the_chips_two_readings(number):
    """The sound runs' largest passes `run.decide` with room, and the
    control's smallest of that one number alone makes it not correct."""
    from chipbench import run

    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert set(cell.limits) == set(SOUND_LARGEST) and cell.rate == 0.6

    def verdict(cmp_):
        return run.decide(cell.limits, 0, 0, 0, cell.decode_paths,
                          cell.decode_paths, None, cmp_)[0]

    assert verdict(SOUND_LARGEST) is True
    assert verdict(dict(SOUND_LARGEST,
                        **{number: CONTROL_SMALLEST[number]})) is False
    assert (1.4 * SOUND_LARGEST[number] < cell.limits[number]
            < CONTROL_SMALLEST[number] / ROOM_BELOW_CONTROL[number])


# ----------------------------------------- the toy cell's limits, as a run
def _serve_toy(cell, seed, n_req=8, n_out=64):
    """What a run's sample is, without the processes: the toy engine in the
    configuration's own type (bfloat16) serves `n_req` requests at once
    (greedy, top-5 log-probabilities; prompts of 12 to 200 tokens in chunks
    of 32), as `harness.Record`s."""
    from chipbench import loadgen
    from test_engine import Collector, run_requests
    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine

    ecfg, _ = build_engine_config(TOY, seed, "t")
    ecfg.warmup_programs = False
    eng = InferenceEngine(ecfg, params=cell.family.weights.make_params(
        seed, cell.hf, cell.engine["weights"]))
    rng = np.random.default_rng(seed % 997)
    reqs = [EngineRequest(
        f"r{i}", token_ids=rng.integers(
            256, 1024, int(rng.integers(12, 200))).tolist(),
        sampling=SamplingParams(max_tokens=n_out, temperature=0.0,
                                ignore_eos=True, logprobs=True,
                                top_logprobs=cell.check_logprobs),
        on_output=Collector()) for i in range(n_req)]
    run_requests(eng, reqs)
    sample = []
    for r in reqs:
        rec = harness.Record(loadgen.Request(
            r.service_request_id, 0.0, list(r.token_ids), n_out,
            logprobs=cell.check_logprobs), 0.0)
        toks = r.on_output.tokens
        rec.text = "".join(map(harness.token_text, toks))
        rec.lps = [{harness.token_text(t.token_id): t.logprob
                    for t in lp.top_logprobs}
                   for o in r.on_output.outputs for q in o.outputs
                   for lp in q.logprobs]
        rec.chunks, rec.done = [(0.0, len(toks))], 1.0
        assert rec.ok
        sample.append(rec)
    return sample, eng


@pytest.mark.parametrize("seed", [1, 2, 3600000202])
def test_the_int8_control_fails_the_toy_cells_limits_through_compare(
        seed, monkeypatch):
    """`run.compare` and `run.decide` as a run calls them, on what the toy
    engine served in bfloat16 through chunked prefill and the slot state:
    the served tokens and their top-5 log-probabilities pass the toy cell's
    limits, the int8 control in the program's place fails each of them."""
    from chipbench import run

    monkeypatch.delenv("XLLM_PALLAS_INTERPRET", raising=False)
    cell = harness.resolve_cell(BENCH, SEARCH, TOY_CELL)
    assert cell.check_logprobs == 5
    assert set(cell.limits) == {"gap_max", "gap_mean", "lp_rms"}
    sample, eng = _serve_toy(cell, seed)
    took = eng.stats()["attention_paths"]["decode_multi"]
    assert took["retention_update"].startswith("xla")
    assert eng.telemetry.counters["prefill_chunks"] >= 15
    cmp_ = run.compare(cell, sample, seed, control=True)
    control = cmp_["control"]
    assert cmp_["tokens"] == 512 and control["precision"] == "int8"

    def verdict(numbers):
        return run.decide(cell.limits, 0, 0, 0, cell.decode_paths,
                          cell.decode_paths, None, numbers)[0]

    assert verdict(cmp_) is True
    assert verdict(control) is False
    for number in ("gap_max", "gap_mean", "lp_rms"):
        assert verdict(dict(cmp_, **{number: control[number]})) is False
        assert cmp_[number] < cell.limits[number] < control[number]
    # on the path the CPU took, the benchmark's cell would not be correct
    real = harness.resolve_cell(*harness.load_bench(ROOT / "BENCHMARK.json"),
                                CELL)
    assert run.decide(cell.limits, 0, 0, 0, took, real.decode_paths, None,
                      cmp_)[0] is False


# -------------------------------------------------------------- the readers
def _span(name, a, b):
    return {"name": name, "start": a, "dur": b - a}


def _toy_trace(markers=((7, 8), (9, 8), (9, 4))):
    """Three `decode_multi` executions with two update-kernel events a step,
    and between them an install of 512 tokens and a chunk of 1024, two
    prefill-kernel events each; `markers`: (live, steps) of the marker that
    lands just after each decode execution (None: it is missing)."""
    mods = [("jit_decode_multi(1)", 0.000, 0.080),
            ("jit_prefill_install(2)", 0.081, 0.095),
            ("jit_decode_multi(1)", 0.096, 0.176),
            ("jit_prefill_chunk(3)", 0.1765, 0.1775),
            ("jit_decode_multi(1)", 0.178, 0.218)]
    ops, k = [], 0
    for name, a, b in mods:
        if "decode" not in name:
            c, dur = (512, 0.003) if "install" in name else (1024, 0.0002)
            for j in range(2):        # two layers
                k += 1
                ops.append({"name": f"_retention_prefill_impl_c{c}.{k}",
                            "start": a + j * 2 * dur, "dur": dur})
                ops.append({"name": f"fusion.{k}",
                            "start": a + (2 * j + 1) * dur, "dur": dur})
            continue
        steps = round((b - a) / 0.010)
        for s in range(steps):
            for j in range(2):
                k += 1
                ops.append({"name": f"_retention_update_impl.{k}",
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
            _span("fetch_wait", 0.097, 0.1763), _span("emit", 0.1763, 0.1764),
            _span("prefill_dispatch", 0.17645, 0.1777),
            _span("prefill_chunk", 0.1765, 0.1776),
            _span("fetch_wait", 0.179, 0.2184)]
    for t, m in zip(lands, markers):
        if m is not None:
            pump.append(_span(f"decode_live.{m[0]}.{m[1]}", t, t + 2e-7))
    return ir, {"python#3": sorted(pump, key=lambda s: (s["start"],
                                                        -s["dur"]))}


def _ctx(ir, spans, family=None, hf=None, recent=None):
    fam, real = _family(REAL)
    stats = {"engine_trace": {"recent": recent}} if recent else {}
    return {"trace": ir, "host_spans": spans, "agent_stats": stats,
            "hotpath": {}, "hf": hf or real, "family": family or fam,
            "engine": {"decode_horizon": 8, "weights": "bfloat16"},
            "device": {"kind": "TPU v5 lite"}, "cell": CELL}


def _reader(name):
    return harness.load_reader(harness.load_bench(ROOT / "BENCHMARK.json")[1],
                               name)


def _without(ir, word):
    return {p: {ln: [e for e in evs if word not in e["name"]]
                for ln, evs in pl.items()} for p, pl in ir.items()}


def test_retention_update_ms_is_the_kernels_time_a_step():
    read = _reader("kernel.retention_update_ms")
    ir, spans = _toy_trace()
    # executions of 8, 8 and 4 steps x 2 events of 2 ms: 32, 32, 16 ms;
    # the median over the configured horizon
    assert read(_ctx(ir, spans)) == pytest.approx(32.0 / 8)
    assert read(_ctx(_without(ir, "retention_update"), spans)) is None
    assert read(_ctx(None, spans)) is None


@pytest.mark.parametrize("missing", [None, 0, 1, 2])
def test_state_bw_pct_prices_each_traced_call_from_its_own_marker(missing):
    """Bytes and seconds go together: a call without its marker is dropped,
    both alike, so the share never passes the largest single call's."""
    read = _reader("kernel.retention_update_state_bw_pct")
    fam, hf = _family(REAL)
    calls = [(7, 8, 0.032), (9, 8, 0.032), (9, 4, 0.016)]
    markers = [(c[0], c[1]) for c in calls]
    if missing is not None:
        markers[missing] = None
    ir, spans = _toy_trace(markers)
    kept = [c for i, c in enumerate(calls) if i != missing]

    def share(cs):
        need = sum(fam.bytes.retention_update_bytes(hf, live) * steps
                   for live, steps, _ in cs)
        return 100 * need / 819e9 / sum(s for _, _, s in cs)

    got = read(_ctx(ir, spans))
    assert got == pytest.approx(share(kept))
    assert got <= max(share([c]) for c in calls) * (1 + 1e-9)


def test_the_prefill_readers_price_each_event_by_the_tokens_it_held():
    fam, hf = _family(REAL)
    ir, spans = _toy_trace()
    # an install's two events of 3 ms and a chunk's two of 0.2 ms: pooled
    assert _reader("kernel.retention_prefill_ms")(_ctx(ir, spans)) == (
        pytest.approx((6.0 + 0.4) / 2))
    # the chunk's execution of 1 ms, and nothing where no chunk ran
    chunk_ms = _reader("prog.prefill_chunk_ms")
    assert chunk_ms(_ctx(ir, spans)) == pytest.approx(1.0)
    assert chunk_ms(_ctx(_without(ir, "prefill_chunk"), spans)) is None
    assert chunk_ms(_ctx(None, spans)) is None
    # a chunk's event holds the tokens its name says, all a prompt's; an
    # install's the share of its bucket that the counters say was a prompt's
    # (6000 - 4096 = 1904 valid rows of 1904 + 656), of one of the 8 layers
    mxu = _reader("kernel.retention_prefill_mxu_pct")
    recent = {"prompt_tokens": 6000, "prefill_chunk_tokens": 4096,
              "prefill_padded_tokens": 656, "prefix_hit_tokens": 0}
    need = 2 * (fam.bytes.retention_prefill_flops(hf, 512 * 1904 / 2560)
                + fam.bytes.retention_prefill_flops(hf, 1024)) / 8
    assert mxu(_ctx(ir, spans, recent=recent)) == (
        pytest.approx(100 * need / 197e12 / 0.0064))
    padded = 2 * (fam.bytes.retention_prefill_flops(hf, 512)
                  + fam.bytes.retention_prefill_flops(hf, 1024)) / 8
    assert need < padded        # a bucket's padding is not counted as work
    assert mxu(_ctx(ir, spans, recent={"prompt_tokens": 6000})) is None
    assert mxu(_ctx(ir, spans)) is None
    chunks = _reader("engine.prefill_chunks_per_admission")
    assert chunks(_ctx(ir, spans, recent={
        "prefill_chunks": 30, "admissions": 10})) == pytest.approx(4.0)
    assert chunks(_ctx(ir, spans, recent={"admissions": 10})) is None
    assert chunks(_ctx(ir, spans)) is None


def test_the_readers_read_nothing_where_there_is_nothing_to_read():
    ir, spans = _toy_trace()
    names = ("kernel.retention_update_ms",
             "kernel.retention_update_state_bw_pct",
             "kernel.retention_prefill_ms", "kernel.retention_prefill_mxu_pct")
    recent = {"prompt_tokens": 6000, "prefill_chunk_tokens": 4096,
              "prefill_padded_tokens": 656}
    for name in names:
        assert _reader(name)(_ctx(ir, spans, recent=recent)) is not None
        # a parent commit, or another family: no such kernel, never a 0
        assert _reader(name)(_ctx(_without(ir, "retention"), spans,
                                  recent=recent)) is None
        assert _reader(name)(_ctx(None, spans, recent=recent)) is None
    bw = _reader("kernel.retention_update_state_bw_pct")
    phases = {ln: [s for s in evs if not s["name"].startswith("decode_live")]
              for ln, evs in spans.items()}
    assert bw(_ctx(ir, phases)) is None and bw(_ctx(ir, None)) is None
    # a family that counts no such bytes or operations (the default)
    qwen = json.loads((ROOT / "chipbench/configs/qwen25-3b-bf16/config.json")
                      .read_text())
    for name in names[1::2]:
        assert _reader(name)(_ctx(ir, spans, harness.Family(SEARCH),
                                  qwen, recent)) is None
    # the standing readers of keys find nothing in this family's trace
    for name in ("kernel.paged_attn_ms", "kernel.prefill_attn_ms",
                 "kernel.ssm_update_ms"):
        assert _reader(name)(_ctx(ir, spans)) is None
