"""The deepseek-v3-moe family's benchmark files (chipbench/families/
deepseek-v3-moe/) and the readers of its expert products' per-layer
metrics: the reference against the program's own forward at the family's
toy size under tests/chipbench/data (its BENCHMARK.kanana-moe.json is found
by test_chipbench_family.py, which holds the family to the contract and its
weights to being a pure function of the seed), the byte counts and the
catalog's widths at the benchmark's configuration, the cell's limits
between the chip's readings, and the readers on a small trace."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.engine_setup import build_engine_config
from chipbench_entries import first_token_is_read

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests/chipbench/data"
BENCH, SEARCH = harness.load_bench(DATA / "BENCHMARK.kanana-moe.json")
TOY = DATA / "configs/tiny-kanana-moe"
REAL = ROOT / "chipbench/configs/kanana-2-30b-a3b"
CELL = "kanana-2-30b-a3b.chat-long"
SEED = 2 ** 31 + 36


def _family(config_dir):
    hf = json.loads((config_dir / "config.json").read_text())
    return harness.family_of(SEARCH, hf), hf


# ------------------------------------------- the reference and the program
def _program_logits(family, hf, toks, n):
    """Logits at every position of `toks` from the program's own forward in
    float32: a prefill of the first `n` tokens through the paged cache
    (`verify_forward`, a bucket of 64), then one decode step a token."""
    from xllm_service_tpu.models import deepseek_moe as dm

    ecfg, _ = build_engine_config(TOY, SEED, "t")
    assert ecfg.model_family == "deepseek_moe"
    mcfg = dataclasses.replace(ecfg.model, dtype=jnp.float32)
    assert (mcfg.kv_head_dim, mcfg.head_dim, mcfg.num_kv_heads) == (128, 80, 1)
    params = family.weights.make_params(SEED, hf, "bfloat16")
    kv = jnp.zeros((mcfg.kv_layers, 2, 64, 1, 16, mcfg.kv_head_dim),
                   jnp.float32)
    pt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
    prefill = jax.jit(dm.verify_forward, static_argnums=1)
    step = jax.jit(dm.decode_forward_routed, static_argnums=1)
    with jax.default_matmul_precision("highest"):
        lg, kv = prefill(
            params, mcfg, jnp.asarray([toks[:n] + [0] * (64 - n)]),
            jnp.arange(64)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([n]))
        out = [np.asarray(lg[0, :n], np.float32)]
        for i in range(n, len(toks)):
            lg, kv, counts = step(
                params, mcfg, jnp.asarray([toks[i]]), jnp.asarray([i]), kv,
                pt, jnp.asarray([i + 1]), live=jnp.asarray([True]))
            assert counts.tolist() == [1, 2 * hf["num_experts_per_tok"]]
            out.append(np.asarray(lg, np.float32))
    return np.concatenate(out)


def test_familys_reference_agrees_with_the_programs_forward():
    """Float32 on both sides at `highest` precision, and no algorithm
    shared: the reference makes every head's key and value from the latent,
    rotates the pairs in place, runs every expert on every token under a
    gate that is zero where it was not chosen; the program attends over the
    latent with the key's up-projection folded into the query,
    de-interleaves and rotates halves, sorts the live (token, expert) pairs
    and multiplies group by group. What is left is the order of float32
    sums: 2e-5 of the logits' spread."""
    family, hf = _family(TOY)
    assert family.name == "deepseek-v3-moe"
    toks = np.random.default_rng(1).integers(256, 1024, 48).tolist()
    want = family.reference.logits_at(SEED, hf, "bfloat16", [toks],
                                      [list(range(48))])[0]
    assert 0.5 < want.std() < 2.0
    for n in (48, 29):
        got = _program_logits(family, hf, toks, n)
        assert np.max(np.abs(got - want)) < 2e-5 * want.std()
    # another seed's weights read far off
    other = family.reference.logits_at(SEED + 1, hf, "bfloat16", [toks],
                                       [[47]])[0]
    assert np.max(np.abs(other - want[47])) > 0.5
    # and the control (one precision lower) moves it, padded or not
    low = family.reference.logits_at(SEED, hf, "bfloat16", [toks], [[4, 47]],
                                     "int8", pad_len=128, pad_pos=8)[0]
    assert low.shape == (2, hf["vocab_size"])
    assert 1e-3 < np.max(np.abs(low - want[[4, 47]])) < 0.5


@pytest.mark.parametrize("departure", [
    {"routed_scaling_factor": 1.0}, {"norm_topk_prob": False},
    {"rope_theta": 10000}, {"zero_bias": True}])
def test_a_reference_that_departs_from_the_equations_reads_far_off(departure):
    """Each published detail the configuration states moves the logits by
    far more than the agreement above: the routed scale, the normalisation
    over the chosen, the rotation's base, and the choice-only bias."""
    family, hf = _family(TOY)
    toks = np.random.default_rng(2).integers(256, 1024, 40).tolist()
    ref = family.reference
    want = ref.logits_at(SEED, hf, "bfloat16", [toks], [[39]])[0]
    if departure.get("zero_bias"):
        w = family.weights
        real = w.moe_leaves

        def no_bias(key, hf_, served):
            leaves = real(key, hf_, served)
            leaves["router"]["bias"] = jnp.zeros_like(
                leaves["router"]["bias"])
            return leaves

        w.moe_leaves = no_bias
        ref._layer.clear_cache()
        try:
            got = ref.logits_at(SEED, hf, "bfloat16", [toks], [[39]])[0]
        finally:
            w.moe_leaves = real
            ref._layer.clear_cache()
    else:
        got = ref.logits_at(SEED, {**hf, **departure}, "bfloat16", [toks],
                            [[39]])[0]
    assert np.max(np.abs(got - want)) > 1e-3 * want.std()


def test_the_reference_refuses_what_it_leaves_out():
    family, hf = _family(TOY)
    for bad in ({"n_group": 4}, {"q_lora_rank": 64},
                {"scoring_func": "softmax"},
                {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError):
            family.reference.logits_at(SEED, {**hf, **bad}, "bfloat16",
                                       [[300, 301]], [[1]])


# ------------------------------------------------ the configuration's files
def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    """Every key of the catalog row's config is in the file with the same
    value, but `num_hidden_layers`, which `reduced` names; the published
    depth and the six-chip deployment are stated."""
    family, hf = _family(REAL)
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    group = hf["chipbench"]
    assert group["reduced"] == ["num_hidden_layers"]
    assert hf["num_hidden_layers"] == 8 and hf["first_k_dense_replace"] == 1
    assert "48" in group["deployment"] and "six" in group["deployment"]
    assert "48" in group["assumed"]["num_hidden_layers"]
    # the Pallas product by name: the CPU fallback is "grouped (ragged_dot"
    assert group["decode_paths"] == {"paged_attention": "pallas",
                                     "moe_experts": "grouped (pallas"}
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().split("\n"))
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert group["source"] == row["source_url"]
        differing = {k for k, v in row["config"].items() if hf.get(k) != v}
        assert differing == {"num_hidden_layers"}
        assert row["config"]["num_hidden_layers"] == 48
    b = family.bytes
    assert b.moe_layers(hf) == 7
    assert b.moe_expert_bytes(hf, "bfloat16") == 3 * 2048 * 768 * 2
    assert b.kv_bytes_per_token(hf) == 8 * 2 * 640 * 2
    # a step's floor: everything outside the routed experts (attention of
    # 8 layers, the dense SwiGLU, 7 shared experts and float32 routers, the
    # norms, the head) and six experts in each of the 7 expert layers
    attn = 2048 * 6144 + 2048 * 512 + 2048 * 64 + 2 * 32 * 128 * 512 \
        + 4096 * 2048
    rest = 2 * (8 * (attn + 2 * 2048 + 512) + 3 * 2048 * 6144
                + 7 * 3 * 2048 * 1536 + 2048 + 2048 * 128256) \
        + 7 * 4 * (2048 + 1) * 128
    assert 1.16e9 < rest < 1.165e9
    assert b.decode_weight_stream_bytes(hf, "bfloat16") == (
        rest + 7 * 6 * b.moe_expert_bytes(hf, "bfloat16"))
    assert b.moe_expert_flops(hf, 32) == 2 * 32 * 6 * 3 * 2048 * 768
    with pytest.raises(ValueError, match="bfloat16"):
        b.moe_expert_bytes(hf, "int8")
    # the tree's bytes: 7 whole expert layers, the dense one, the
    # vocabulary twice (embedding and untied head)
    tree = family.weights.param_shapes(hf, "bfloat16")
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert 10.12e9 < held < 10.16e9
    experts = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        tree["moe"]["experts"]))
    assert experts == 7 * 128 * b.moe_expert_bytes(hf, "bfloat16")
    assert tree["moe"]["router"]["bias"].shape == (7, 128)


def test_the_cell_resolves_and_is_due_every_standing_metric_it_moves():
    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert cell.family.name == "deepseek-v3-moe"
    assert cell.decode_paths == {"paged_attention": "pallas",
                                 "moe_experts": "grouped (pallas"}
    assert cell.engine["max_batch_size"] == 32 and cell.chips == 1
    assert "engine_config" not in cell.engine
    assert (cell.check_requests, cell.check_logprobs) == (6, 5)
    e2e = {m["name"] for m in harness.metrics_for(bench, "end_to_end", CELL)}
    assert e2e - {"ttft_ms.mean"} == {"tpot_ms.p90", "gap_ms.p95",
                                      "out_tok_per_s", "setup_s"}
    # the first token: judged, or read through the cell's own three entries
    assert first_token_is_read(bench, CELL, "chat-long")
    due = {m["name"] for m in harness.metrics_for(bench, "per_layer", CELL)}
    assert {"kernel.moe_experts_ms", "kernel.moe_experts_weight_bw_pct",
            "engine.moe_experts_touched_mean", "kernel.paged_attn_ms",
            "kernel.paged_attn_kv_bw_pct", "kernel.paged_attn_run_chunk_pct",
            "prog.decode_step_ms", "prog.prefill_call_ms", "device.idle_pct",
            "engine.batch_live_mean", "device.decode_weight_bw_pct"} <= due
    # and no other cell is given the new ones
    for other in ("qwen25-7b-int8.chat", "qwen25-3b-bf16.agent-prefix",
                  "granite-4.0-h-micro.chat-short"):
        names = {m["name"] for m in harness.metrics_for(
            bench, "per_layer", other)}
        assert not {n for n in names if "moe" in n or "chat-long" in n}
        assert "device.decode_weight_bw_pct" in names
    # the program builds the engine it names
    ecfg, _ = build_engine_config(REAL, 1, "k")
    m = ecfg.model
    assert (m.name, m.num_layers, m.num_experts, m.num_experts_per_token,
            m.kv_head_dim, m.router_scoring, m.router_bias, m.routed_scale,
            m.rope_interleave, m.first_dense_layers) == (
        "deepseek_moe", 8, 128, 6, 640, "sigmoid", True, 2.448, True, 1)


# What the chip read in this cell (TPU v5e, PR 36, `run.py --control`): each
# number's extremes over the sound runs (10 seeds) and over the int8 control
# (4 seeds), each of 1692-2877 served tokens.
SOUND_LARGEST = {"gap_max": 5.1833, "gap_mean": 0.30315, "lp_rms": 0.60729}
CONTROL_SMALLEST = {"gap_max": 4.4485, "gap_mean": 0.59125, "lp_rms": 0.86772}
SEPARATES = ("gap_mean", "lp_rms")


@pytest.mark.parametrize("number", ["gap_max", "gap_mean", "lp_rms"])
def test_each_limit_of_the_cell_stands_where_the_chips_readings_put_it(number):
    """The sound runs' largest passes `run.decide` with room. `lp_rms` and
    `gap_mean` each make the control's smallest of that one number alone
    not correct; `gap_max` does not separate the two (the seeded model
    amplifies bfloat16 rounding through its routing: PERF.md section 2) and
    is held over the sound runs' largest, under what an unrelated token
    reads."""
    from chipbench import run

    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, CELL)
    assert set(cell.limits) == set(SOUND_LARGEST) and cell.check_logprobs == 5

    def verdict(cmp_):
        return run.decide(cell.limits, 0, 0, 0, cell.decode_paths,
                          cell.decode_paths, None, cmp_)[0]

    assert verdict(SOUND_LARGEST) is True
    limit = cell.limits[number]
    if number in SEPARATES:
        assert verdict(dict(SOUND_LARGEST,
                            **{number: CONTROL_SMALLEST[number]})) is False
        assert (1.1 * SOUND_LARGEST[number] < limit
                < CONTROL_SMALLEST[number] / 1.1)
    else:
        assert CONTROL_SMALLEST[number] < SOUND_LARGEST[number]
        assert 1.25 * SOUND_LARGEST[number] < limit < 8.0
    # a path the configuration names and the program did not take is not
    # correct whatever the numbers: the dense contraction, the CPU fallback
    took = {"paged_attention": "pallas",
            "moe_experts": "grouped (pallas megablox gmm)"}
    assert run.decide(cell.limits, 0, 0, 0, took, cell.decode_paths, None,
                      SOUND_LARGEST)[0] is True
    for other in ("dense (int8 experts)", "grouped (ragged_dot, cpu backend)"):
        assert run.decide(cell.limits, 0, 0, 0,
                          dict(took, moe_experts=other), cell.decode_paths,
                          None, SOUND_LARGEST)[0] is False


# ------------------------------------- the comparison itself, at the toy size
TOY_CELL = "tiny-kanana-moe.tiny-chat"


def _serve_toy(cell, seed, n_req=8, n_out=64):
    """What a run's sample is, without the processes: the toy engine in the
    configuration's own type (bfloat16) serves `n_req` requests at once
    (greedy, top-5 log-probabilities), as `harness.Record`s."""
    from chipbench import loadgen
    from test_engine import Collector, run_requests
    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine

    ecfg, _ = build_engine_config(TOY, seed, "t")
    eng = InferenceEngine(ecfg, params=cell.family.weights.make_params(
        seed, cell.hf, cell.engine["weights"]))
    rng = np.random.default_rng(seed % 997)
    reqs = [EngineRequest(
        f"r{i}", token_ids=rng.integers(
            256, 1024, int(rng.integers(12, 100))).tolist(),
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
    return sample, eng.stats()["attention_paths"]["decode_multi"]


@pytest.mark.parametrize("seed", [1, 2, 3600000202])
def test_the_int8_control_fails_the_toy_cells_limits_through_compare(
        seed, monkeypatch):
    """`run.compare` and `run.decide` as a run calls them, on what the toy
    engine served in bfloat16 with 8 slots live at once: the served tokens
    and their top-5 log-probabilities pass the toy cell's limits, the int8
    control in the program's place does not, by `lp_rms` alone and by
    `gap_mean` alone; `gap_max` does not separate here either."""
    from chipbench import run

    # the CPU's own products, whatever a rehearsal before this test set
    monkeypatch.delenv("XLLM_PALLAS_INTERPRET", raising=False)
    cell = harness.resolve_cell(BENCH, SEARCH, TOY_CELL)
    assert cell.check_logprobs == 5
    assert set(cell.limits) == {"gap_max", "gap_mean", "lp_rms"}
    sample, took = _serve_toy(cell, seed)
    assert took["moe_experts"] == "grouped (ragged_dot, cpu backend)"
    cmp_ = run.compare(cell, sample, seed, control=True)
    control = cmp_["control"]
    assert cmp_["tokens"] == 512 and control["precision"] == "int8"

    def verdict(numbers):
        return run.decide(cell.limits, 0, 0, 0, cell.decode_paths,
                          cell.decode_paths, None, numbers)[0]

    assert verdict(cmp_) is True
    assert verdict(control) is False
    for number in ("lp_rms", "gap_mean"):
        assert verdict(dict(cmp_, **{number: control[number]})) is False
        assert cmp_[number] < cell.limits[number] < control[number]
    assert verdict(dict(cmp_, gap_max=control["gap_max"])) is True
    # on the path the CPU took, the benchmark's cell would not be correct
    real = harness.resolve_cell(*harness.load_bench(ROOT / "BENCHMARK.json"),
                                CELL)
    assert run.decide(cell.limits, 0, 0, 0, took, real.decode_paths, None,
                      cmp_)[0] is False


# -------------------------------------------------------------- the readers
def _span(name, a, b):
    return {"name": name, "start": a, "dur": b - a}


def _toy_trace(markers=((400, 80, 8), (520, 96, 8), (260, 48, 4))):
    """Three `decode_multi` executions with two expert layers of three
    products a step and a prefill between them; `markers`: (touched, rows,
    steps) of the marker that lands just after each execution (None: it is
    missing)."""
    mods = [("jit_decode_multi(1)", 0.000, 0.080),
            ("jit_prefill_install(2)", 0.081, 0.095),
            ("jit_decode_multi(1)", 0.096, 0.176),
            ("jit_decode_multi(1)", 0.178, 0.218)]
    ops, k = [], 0
    for name, a, b in mods:
        if "decode" not in name:
            ops.append({"name": "_moe_experts_impl.77", "start": a,
                        "dur": b - a})        # a prefill's: not counted
            continue
        for s in range(round((b - a) / 0.010)):
            for j in range(6):
                k += 1
                ops.append({"name": f"_moe_experts_impl.{k}",
                            "start": a + s * 0.010 + j * 0.0015,
                            "dur": 0.001})
                ops.append({"name": f"fusion.{k}",
                            "start": a + s * 0.010 + j * 0.0015 + 0.001,
                            "dur": 0.0005})
    ir = {"/device:TPU:0": {
        "XLA Modules": [{"name": n, "start": a, "dur": b - a}
                        for n, a, b in mods],
        "XLA Ops": ops}}
    lands = [0.0803, 0.1764, 0.2185]
    pump = [_span("fetch_wait", 0.001, 0.0802), _span("emit", 0.0802, 0.0808),
            _span("fetch_wait", 0.097, 0.1763), _span("emit", 0.1763, 0.177),
            _span("fetch_wait", 0.179, 0.2184)]
    for t, m in zip(lands, markers):
        pump.append(_span("decode_live.9.8", t, t + 2e-7))
        if m is not None:
            pump.append(_span("moe.landed.%d.%d.%d" % m, t + 3e-7, t + 5e-7))
    return ir, {"python#3": sorted(pump, key=lambda s: (s["start"],
                                                        -s["dur"]))}


def _ctx(ir, spans, family=None, hf=None, stats=None):
    fam, real = _family(REAL)
    return {"trace": ir, "host_spans": spans, "agent_stats": stats or {},
            "hotpath": {}, "hf": hf or real, "family": family or fam,
            "engine": {"decode_horizon": 8, "weights": "bfloat16"},
            "device": {"kind": "TPU v5 lite"}, "cell": CELL}


def _reader(name):
    return harness.load_reader(harness.load_bench(ROOT / "BENCHMARK.json")[1],
                               name)


def test_moe_experts_ms_is_the_products_time_a_step():
    read = _reader("kernel.moe_experts_ms")
    ir, spans = _toy_trace()
    # executions of 8, 8 and 4 steps x 6 events of 1 ms: 48, 48, 24 ms; the
    # median over the configured horizon; the prefill's event is not a
    # decode call's
    assert read(_ctx(ir, spans)) == pytest.approx(48.0 / 8)
    no_kernel = {p: {ln: [e for e in evs if "moe" not in e["name"]]
                     for ln, evs in pl.items()} for p, pl in ir.items()}
    assert read(_ctx(no_kernel, spans)) is None        # a parent commit
    assert read(_ctx(None, spans)) is None


@pytest.mark.parametrize("missing", [None, 0, 1, 2])
def test_weight_bw_pct_prices_each_traced_call_from_its_own_marker(missing):
    """Bytes and seconds go together: experts touched x one expert's bytes
    over the seconds of the products in the same calls; a call without its
    marker is dropped from both and cannot raise the share."""
    read = _reader("kernel.moe_experts_weight_bw_pct")
    calls = [(400, 0.048), (520, 0.048), (260, 0.024)]
    markers = [(400, 80, 8), (520, 96, 8), (260, 48, 4)]
    if missing is not None:
        markers[missing] = None
    ir, spans = _toy_trace(markers)
    kept = [c for i, c in enumerate(calls) if i != missing]

    def share(cs):
        return (100 * sum(t for t, _ in cs) * 3 * 2048 * 768 * 2 / 819e9
                / sum(s for _, s in cs))

    got = read(_ctx(ir, spans))
    assert got == pytest.approx(share(kept))
    assert got <= max(share([c]) for c in calls) * (1 + 1e-9)


def test_the_moe_readers_read_nothing_where_there_is_nothing_to_read():
    share = _reader("kernel.moe_experts_weight_bw_pct")
    mean = _reader("engine.moe_experts_touched_mean")
    ir, spans = _toy_trace()
    assert share(_ctx(ir, spans)) is not None
    # a program without the markers (a parent commit), without the kernel,
    # a family that counts no such bytes (the default): nothing, never a 0
    phases = {ln: [s for s in evs if not s["name"].startswith("moe.")]
              for ln, evs in spans.items()}
    assert share(_ctx(ir, phases)) is None
    assert share(_ctx(ir, {})) is None and share(_ctx(ir, None)) is None
    no_kernel = {p: {ln: [e for e in evs if "moe" not in e["name"]]
                     for ln, evs in pl.items()} for p, pl in ir.items()}
    assert share(_ctx(no_kernel, spans)) is None
    qwen = json.loads((ROOT / "chipbench/configs/qwen25-3b-bf16/config.json")
                      .read_text())
    default = harness.Family(SEARCH)
    assert share(_ctx(ir, spans, default, qwen)) is None
    recent = {"engine_trace": {"recent": {
        "moe_experts_touched": 7 * 800 * 61.5, "moe_steps": 800,
        "decode_steps": 800}}}
    assert mean(_ctx(None, None, stats=recent)) == pytest.approx(61.5)
    assert mean(_ctx(None, None, default, qwen, recent)) is None
    assert mean(_ctx(None, None)) is None
    assert mean(_ctx(None, None, stats={"engine_trace": {"recent": {
        "moe_steps": 0, "moe_experts_touched": 0}}})) is None
