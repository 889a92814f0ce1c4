"""The granite-hybrid family (Mamba-2 layers with per-slot recurrent state
beside a KV pool only the attention layers use) on the CPU at a toy size of
its own: hidden 64, two periods of a five-layer pattern with one attention
layer, eight state-space heads of 16, state 32, vocabulary 512. Seeded
weights from the benchmark's family files, logits compared with its plain
reference (the sequential recurrence, float32, no import of the program).

Tolerance: float32 on both sides at `highest` matmul precision, so what is
left is the order of float32 sums: 1e-6 on logits whose spread is 0.01
(the seeded embedding has norm 1 / `embedding_multiplier`, and
`logits_scaling` is 8; 8e-8 read). A forward that applied a rotary
embedding, scaled scores by head_dim**-0.5, dropped a multiplier or held
the state in bfloat16 reads 1e-4 or more and fails it.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.common.types import InstanceType
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import (
    EngineRequest, InferenceEngine, new_decode_state)
from xllm_service_tpu.models import granite_hybrid as gh
from xllm_service_tpu.models.hf_config import model_config_from_hf
from xllm_service_tpu.ops import ssm
from xllm_service_tpu.ops.pallas_ssm_update import ssm_update_pallas

from test_engine import Collector, run_requests

ROOT = Path(__file__).resolve().parent.parent
SEED = 2 ** 31 + 77
TOL = 1e-6

TOY_HF = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "num_hidden_layers": 10, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128,
    "shared_intermediate_size": 128, "vocab_size": 512,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"] * 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "position_embedding_type": "nope",
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": True,
    "max_position_embeddings": 512,
    "chipbench": {"family": "granite-hybrid"}}


def _family():
    _, search = harness.load_bench(ROOT / "BENCHMARK.json")
    return harness.family_of(search, TOY_HF)


def _mcfg(tmp_path, hf=TOY_HF, **kw):
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return dataclasses.replace(model_config_from_hf(tmp_path),
                               dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """(model config from the toy's config.json through the program's own
    `model_config_from_hf`, seeded params, the benchmark family)."""
    family = _family()
    return (_mcfg(tmp_path_factory.mktemp("toy")),
            family.weights.make_params(SEED, TOY_HF, "bfloat16"), family)


def test_hf_config_maps_every_width_and_the_pattern(toy):
    mcfg, params, _ = toy
    assert mcfg.name == "granite_hybrid"
    assert (mcfg.kv_layers, mcfg.kv_head_dim, mcfg.head_dim) == (2, 128, 16)
    assert (mcfg.ssm_heads, mcfg.ssm_head_dim, mcfg.ssm_state,
            mcfg.ssm_conv, mcfg.ssm_chunk) == (8, 16, 32, 4, 8)
    assert (mcfg.embed_multiplier, mcfg.residual_multiplier,
            mcfg.attn_multiplier, mcfg.logits_scaling) == (12, 0.22, 0.0625,
                                                           8)
    assert [k for _, k, _ in gh._layers(mcfg)] == list(mcfg.layer_types)
    assert [i for _, _, i in gh._layers(mcfg)] == [0, 1, 0, 2, 3, 4, 5, 1, 6, 7]
    assert gh.toy_config(dtype=jnp.float32) == dataclasses.replace(
        mcfg, max_context_len=512)
    shapes = jax.eval_shape(lambda r: gh.init_params(mcfg, r),
                            jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, shapes)
    # every other family keeps the pool it had
    from xllm_service_tpu.models.base import tiny_config
    t = tiny_config()
    assert (t.kv_layers, t.kv_head_dim) == (t.num_layers, t.head_dim)


@pytest.mark.parametrize("key,value,why", [
    ("num_local_experts", 4, "routed experts"),
    ("mamba_n_groups", 2, "one group"),
    ("position_embedding_type", "rope", "applies no position embedding"),
    ("attention_bias", True, "biases"),
    ("tie_word_embeddings", False, "untied"),
    ("mamba_n_heads", 4, "mamba_expand x hidden_size")])
def test_hf_config_refuses_what_the_family_does_not_compute(
        tmp_path, key, value, why):
    (tmp_path / "config.json").write_text(json.dumps({**TOY_HF, key: value}))
    with pytest.raises(ValueError, match=why):
        model_config_from_hf(tmp_path)


# ----------------------------------------- (a) the program vs the reference
def _served_logits(mcfg, params, toks, n_prompt, bucket=64, slot=1, B=3,
                   round_state=None):
    """Logits at positions n_prompt-1 .. len(toks)-1: the prompt through
    `prefill_forward` (padded to `bucket`), its state installed in slot
    `slot` of the engine's decode state, the rest token by token through
    `decode_forward` with that slot alone live."""
    ecfg = EngineConfig(model=mcfg, model_family="granite_hybrid",
                        num_pages=32, max_batch_size=B, max_seq_len=128,
                        prefill_buckets=(64, 128))
    d = new_decode_state(ecfg)
    assert d["ssm"].dtype == jnp.float32 and d["ssm"].shape == (8, B, 32, 128)
    assert d["conv"].shape == (8, B, 3, 128 + 64)
    assert d["kv"].shape == (2, 2, 32, 2, 16, 128)
    row = jnp.arange(1, 9, dtype=jnp.int32)
    padded = jnp.asarray([toks[:n_prompt] + [0] * (bucket - n_prompt)])
    with jax.default_matmul_precision("highest"):
        lg, kv, st = gh.prefill_forward(
            params, mcfg, padded, jnp.arange(bucket)[None, :], d["kv"],
            row[None, :], jnp.zeros((1,), jnp.int32),
            jnp.asarray([n_prompt]))
        out = [np.asarray(lg[0])]
        state = {k: d[k].at[:, slot].set(st[k][:, 0]) for k in st}
        pt = jnp.zeros((B, 8), jnp.int32).at[slot].set(row)
        live = jnp.zeros((B,), bool).at[slot].set(True)
        step = jax.jit(lambda t, pos, kv, state: gh.decode_forward(
            params, mcfg, jnp.zeros((B,), jnp.int32).at[slot].set(t),
            jnp.full((B,), pos), kv, pt,
            jnp.ones((B,), jnp.int32).at[slot].set(pos + 1),
            state=state, live=live))
        for pos in range(n_prompt, len(toks)):
            if round_state is not None:
                state = dict(state, ssm=state["ssm"].astype(
                    round_state).astype(jnp.float32))
            lg, kv, state = step(toks[pos], pos, kv, state)
            out.append(np.asarray(lg[slot]))
        # the dead slots' state was never touched
        assert not np.asarray(state["ssm"][:, 0]).any()
        assert not np.asarray(state["conv"][:, 2]).any()
    return np.stack(out)


TOKS = np.random.default_rng(1).integers(16, 512, 60).tolist()


@pytest.mark.parametrize("n_prompt", [5, 29, 43, 60])
def test_prefill_then_decode_through_the_state_equals_the_reference(
        toy, n_prompt):
    """Prompt lengths that are no multiple of the chunk (8) and shorter
    than the bucket (64), then decode to 60: every served position's
    logits against the reference's full forward."""
    mcfg, params, family = toy
    got = _served_logits(mcfg, params, TOKS, n_prompt)
    want = family.reference.logits_at(
        SEED, TOY_HF, "bfloat16", [TOKS], [list(range(n_prompt - 1, 60))])[0]
    assert want.std() > 0.005
    assert np.max(np.abs(got - want)) < TOL


def _roped_qkv(monkeypatch):
    """The family's `_qkv` with a rotary embedding on the prompt's queries
    and keys, as every other family here applies one."""
    from xllm_service_tpu.ops.attention import apply_rope
    plain = gh._qkv

    def roped(lp, h, cfg):
        q, k, v = plain(lp, h, cfg)
        if h.ndim == 3:
            pos = jnp.arange(h.shape[1])[None, :]
            q, k = (apply_rope(a, pos, cfg.rope_theta) for a in (q, k))
        return q, k, v

    monkeypatch.setattr(gh, "_qkv", roped)


@pytest.mark.parametrize("change", [
    {"rope": True}, {"attn_multiplier": 0.0}, {"embed_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"logits_scaling": 1.0},
    {"round_state": jnp.bfloat16}], ids=lambda c: next(iter(c)))
def test_a_forward_that_departs_from_the_equations_fails_the_tolerance(
        toy, change, monkeypatch):
    """Rotary embedding applied, scores scaled by head_dim**-0.5, a
    multiplier dropped, the recurrent state held in bfloat16: each reads
    at least a hundred tolerances off."""
    mcfg, params, family = toy
    rs = change.get("round_state")
    if "rope" in change:
        _roped_qkv(monkeypatch)
    cfg = (dataclasses.replace(mcfg, **change)
           if change.keys() <= {f.name for f in dataclasses.fields(mcfg)}
           else mcfg)
    got = _served_logits(cfg, params, TOKS, 29, round_state=rs)
    want = family.reference.logits_at(
        SEED, TOY_HF, "bfloat16", [TOKS], [list(range(28, 60))])[0]
    assert np.max(np.abs(got - want)) > 100 * TOL


# ------------------------------- (b) chunked scan == sequential recurrence
@pytest.mark.parametrize("S,chunk,lens", [
    (37, 8, (37, 20)), (64, 16, (64, 1)), (24, 64, (24, 13)),
    (40, 8, (0, 33))])
def test_chunked_scan_equals_the_sequential_recurrence(S, chunk, lens):
    """Lengths that are no multiple of the chunk, a chunk longer than the
    sequence, padding (dt = 0) that must not touch the state."""
    k = jax.random.split(jax.random.PRNGKey(S), 5)
    B, H, P, N = 2, 4, 8, 16
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)))
    dt = jnp.where(jnp.arange(S)[None, :, None]
                   < jnp.asarray(lens)[:, None, None], dt, 0.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = jax.random.normal(k[3], (B, S, N))
    c = jax.random.normal(k[4], (B, S, N))
    with jax.default_matmul_precision("highest"):
        y0, s0 = ssm.ssm_sequential_scan(x, dt, a, b, c)
        y1, s1 = ssm.ssm_chunked_scan(x, dt, a, b, c, chunk)
        # the state after the last valid token, whatever follows it
        y2, s2 = ssm.ssm_sequential_scan(
            x[:, :max(lens)], dt[:, :max(lens)], a, b[:, :max(lens)],
            c[:, :max(lens)])
    assert np.max(np.abs(y0 - y1)) < 5e-5
    assert np.max(np.abs(s0 - s1)) < 5e-5
    assert np.max(np.abs(s2 - s1)) < 5e-5


def test_conv_window_is_the_last_valid_rows():
    xbc = jnp.arange(2 * 10 * 3, dtype=jnp.float32).reshape(2, 10, 3) + 1
    kern = jnp.asarray([[0.0] * 3, [0.0] * 3, [0.0] * 3, [1.0] * 3])
    out, win = ssm.causal_conv(xbc, kern, jnp.zeros((3,)),
                               jnp.asarray([2, 7]))
    assert np.array_equal(out, xbc)            # the last tap is the input
    assert np.array_equal(win[0], np.concatenate(
        [np.zeros((1, 3)), xbc[0, :2]]))       # zeros before the sequence
    assert np.array_equal(win[1], xbc[1, 4:7])
    # one more token, from that window, is the convolution at position 7
    k2 = jax.random.normal(jax.random.PRNGKey(0), (4, 3))
    full, _ = ssm.causal_conv(xbc, k2, jnp.ones((3,)), jnp.asarray([8, 8]))
    _, w7 = ssm.causal_conv(xbc, k2, jnp.ones((3,)), jnp.asarray([7, 7]))
    step, nxt = ssm.conv_step(w7, xbc[:, 7], k2, jnp.ones((3,)))
    assert np.allclose(step, full[:, 7], atol=1e-5)
    assert np.array_equal(nxt, xbc[:, 5:8])


# ------------------------------------- (c) the kernel == the plain update
@pytest.mark.parametrize("live", [
    [1, 0, 1, 1, 0, 0], [0] * 6, [1] * 6, [0, 0, 0, 0, 0, 1]],
    ids=["some", "none", "all", "last"])
def test_the_kernel_in_interpret_mode_equals_the_plain_update(live):
    """`_ssm_update_impl` over layer 1 of a [3, 6, 128, 256] buffer: y and
    the live slots' state as the plain form gives them, and every dead
    slot's state, and every other layer, bit-identical after the call."""
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    L, B, N, K = 3, 6, 128, 256
    state = jax.random.normal(k[0], (L, B, N, K), jnp.float32)
    before = np.asarray(state)
    live = jnp.asarray(live, bool)
    decay = jax.random.uniform(k[1], (B, K))
    dtx = jax.random.normal(k[2], (B, K))
    b, c = jax.random.normal(k[3], (B, N)), jax.random.normal(k[4], (B, N))
    y0, s0 = ssm.ssm_update_plain(state, 1, live, decay, dtx, b, c)
    y1, s1 = ssm_update_pallas(jnp.array(state), jnp.int32(1), live, decay,
                               dtx, b, c, interpret=True)
    assert np.max(np.abs(y0 - y1)) < 1e-4
    assert np.max(np.abs(s0 - s1)) < 1e-5
    dead = ~np.asarray(live)
    assert np.array_equal(np.asarray(s1)[1][dead], before[1][dead])
    assert np.array_equal(np.asarray(s1)[[0, 2]], before[[0, 2]])
    assert not np.asarray(y1)[dead].any()
    if live.any():
        assert np.abs(np.asarray(s1)[1][~dead] - before[1][~dead]).max() > 0.1


def test_the_path_is_decided_by_backend_and_shape():
    assert ssm.ssm_update_path("tpu", False, 128, 4096) == "pallas"
    assert ssm.ssm_update_path("cpu", True, 128, 4096) == "pallas"
    assert ssm.ssm_update_path("cpu", False, 128, 4096) == "xla (cpu backend)"
    assert ssm.ssm_update_path("tpu", False, 32, 128).startswith(
        "xla (shape outside the kernel's tiling")


# --------------------------------------------- (d) through InferenceEngine
def _engine(mcfg, params, **kw):
    base = dict(model=mcfg, model_family="granite_hybrid", num_pages=64,
                page_size=16, hash_block_size=32, max_batch_size=3,
                max_seq_len=128, prefill_buckets=(32, 64, 128),
                decode_horizon=4)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), params=params)


def _req(name, prompt, n, **kw):
    return EngineRequest(name, token_ids=list(prompt), sampling=SamplingParams(
        max_tokens=n, temperature=0.0, ignore_eos=True),
        on_output=Collector(), **kw)


def _alone(mcfg, params, prompt, n):
    r = _req("alone", prompt, n)
    run_requests(_engine(mcfg, params), [r])
    return r.on_output.tokens


P1, P2, P3 = TOKS[:21], TOKS[10:47], TOKS[30:43]


def test_engine_tokens_are_the_references_best(toy):
    """Through `prefill_install` and `decode_multi`: every served token is
    the reference's best at its position, to the tolerance."""
    mcfg, params, family = toy
    eng = _engine(mcfg, params)
    served = _alone(mcfg, params, P1, 20)
    assert len(served) == 20
    seq = P1 + served
    lg = family.reference.logits_at(
        SEED, TOY_HF, "bfloat16", [seq],
        [list(range(len(P1) - 1, len(seq) - 1))])[0]
    gap = lg.max(-1) - lg[np.arange(20), served]
    assert gap.max() < TOL
    c = eng.telemetry.counters
    assert c["state_bytes_reserved"] == (
        8 * 3 * 32 * 128 * 4 + 8 * 3 * 3 * 192 * 4)     # ssm + conv, float32


def test_a_reused_slot_serves_what_a_fresh_engine_serves(toy):
    """One slot: the second sequence takes the first one's slot and finds
    nothing of its state."""
    mcfg, params, _ = toy
    eng = _engine(mcfg, params, max_batch_size=1)
    a, b = _req("a", P1, 12), _req("b", P2, 9)
    run_requests(eng, [a])
    run_requests(eng, [b])
    assert a.on_output.tokens == _alone(mcfg, params, P1, 12)
    assert b.on_output.tokens == _alone(mcfg, params, P2, 9)


def test_sequences_admitted_at_different_steps_agree_with_each_alone(toy):
    mcfg, params, _ = toy
    eng = _engine(mcfg, params)
    a, b, c = _req("a", P1, 24), _req("b", P2, 10), _req("c", P3, 17)
    eng.submit(a)
    for _ in range(2):
        eng.step()
    eng.submit(b)
    eng.step()
    eng.submit(c)
    run_requests(eng, [])
    while not all(r.on_output.done.is_set() for r in (a, b, c)):
        eng.step()
    for r, p, n in ((a, P1, 24), (b, P2, 10), (c, P3, 17)):
        assert r.on_output.tokens == _alone(mcfg, params, p, n), r
    assert eng.telemetry.counters["live_slot_steps"] > 0
    assert eng.stats()["attention_paths"]["decode_multi"].keys() == {
        "ssm_update", "paged_attention"}


def test_a_preempted_offline_request_resumes_to_the_same_continuation(toy):
    """Preemption re-prefills prompt + generated from position 0, which
    rebuilds the recurrent state: nothing of it is carried over."""
    mcfg, params, _ = toy
    eng = _engine(mcfg, params, num_pages=7, max_batch_size=2)
    off_prompt, on_prompt = TOKS[:30], TOKS[:58]
    off, on = _req("off", off_prompt, 12, offline=True), _req(
        "on", on_prompt, 4)
    eng.submit(off)
    for _ in range(2):
        eng.step()
    assert len(off.on_output.tokens) >= 2
    eng.submit(on)
    while not (off.on_output.done.is_set() and on.on_output.done.is_set()):
        eng.step()
    assert eng.telemetry.counters["preemptions"] >= 1
    assert on.on_output.tokens == _alone(mcfg, params, on_prompt, 4)
    assert off.on_output.tokens == _alone(mcfg, params, off_prompt, 12)


def test_a_repeated_prompt_is_not_asked_of_the_prefix_cache(toy):
    mcfg, params, _ = toy
    eng = _engine(mcfg, params)
    prompt = TOKS[:50]            # one whole hash block of 32 and more
    first, again = _req("first", prompt, 3), _req("again", prompt, 3)
    run_requests(eng, [first])
    run_requests(eng, [again])
    assert first.on_output.tokens == again.on_output.tokens
    c = eng.telemetry.counters
    assert c["prefix_skipped_stateful"] == 2 and c["admissions"] == 2
    assert c["prefix_hit_tokens"] == 0
    assert eng.stats()["cached_blocks"] == 0
    assert eng.page_mgr.match_prefix(prompt)[0] == 0
    # a family without such state still asks, and still counts nothing here
    from xllm_service_tpu.models.base import tiny_config
    plain = InferenceEngine(EngineConfig(
        model=tiny_config(dtype=jnp.float32), num_pages=64,
        hash_block_size=32, max_seq_len=128, prefill_buckets=(64, 128)))
    for name in ("p1", "p2"):
        run_requests(plain, [_req(name, prompt, 2)])
    pc = plain.telemetry.counters
    assert pc["prefix_skipped_stateful"] == 0 and pc["prefix_hit_tokens"] == 32
    assert pc["state_bytes_reserved"] == 0


def test_the_kernel_runs_inside_the_engines_programs(toy, tmp_path,
                                                     monkeypatch):
    """State size 128 (the lane width) under interpret mode: `decode_multi`
    takes the kernel, the layer as a scalar
    and the state aliased, and serves what the plain update serves."""
    hf = {**TOY_HF, "mamba_d_state": 128}
    mcfg = _mcfg(tmp_path, hf)
    params = _family().weights.make_params(SEED, hf, "bfloat16")
    plain = _req("plain", P1, 6)
    run_requests(_engine(mcfg, params), [plain])
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
    eng = _engine(mcfg, params)
    a, b = _req("a", P1, 6), _req("b", P3, 5)
    run_requests(eng, [a, b])
    assert eng.stats()["attention_paths"]["decode_multi"] == {
        "ssm_update": "pallas", "paged_attention": "pallas"}
    assert a.on_output.tokens == plain.on_output.tokens


# ------------------------------------------------------- (e) the refusals
@pytest.mark.parametrize("kw,why", [
    ({"role": InstanceType.PREFILL}, "role PREFILL is refused"),
    ({"role": InstanceType.DECODE}, "role DECODE is refused"),
    ({"prefill_chunk_tokens": 32}, "prefill_chunk_tokens=32 is refused")])
def test_what_the_state_cannot_follow_is_refused_at_start(toy, kw, why):
    mcfg, params, _ = toy
    with pytest.raises(ValueError, match=why) as e:
        _engine(mcfg, params, **kw)
    assert "per-slot recurrent state" in str(e.value)


@pytest.mark.parametrize("family,kw,refused", [
    ("granite_hybrid", {"prefill_chunk_tokens": 32}, "starts from an empty"),
    ("granite_hybrid", {"prefill_chunk_tokens": 0}, None),
    ("power_retention", {"prefill_chunk_tokens": 32}, None),
    ("power_retention", {"prefill_chunk_tokens": 0}, None),
    ("power_retention", {"role": InstanceType.PREFILL}, "role PREFILL"),
    ("power_retention", {"role": InstanceType.DECODE}, "role DECODE"),
    ("power_retention", {"speculate_k": 2}, "speculate_k=2 is refused"),
    ("llama", {"prefill_chunk_tokens": 32}, None)])
def test_who_is_refused_chunked_prefill_and_who_is_served(
        toy, family, kw, refused):
    """A prefill chunk needs the state of the chunk before it: a family
    whose `prefill_forward` takes and returns state says so
    (`ModelFamily.prefill_carries_state`) and is served; one whose prefill
    starts from an empty state is refused, as before; one without such
    state never was. What no stateful family can run stays refused."""
    from xllm_service_tpu.models import power_retention as pr
    from xllm_service_tpu.models.base import get_model_family, tiny_config

    mcfg = {"granite_hybrid": toy[0],
            "power_retention": pr.toy_config(dtype=jnp.float32),
            "llama": tiny_config(dtype=jnp.float32)}[family]
    assert get_model_family(family).prefill_carries_state == (
        family == "power_retention")
    cfg = EngineConfig(**{**dict(
        model=mcfg, model_family=family, num_pages=64, page_size=16,
        hash_block_size=32, max_batch_size=2, max_seq_len=128,
        prefill_buckets=(32, 64, 128)), **kw})
    if refused:
        with pytest.raises(ValueError, match=refused) as e:
            InferenceEngine(cfg)
        assert "per-slot recurrent state" in str(e.value)
        return
    eng = InferenceEngine(cfg)
    r = _req("r", TOKS[:45], 3)
    run_requests(eng, [r])
    assert len(r.on_output.tokens) == 3
    chunked = kw.get("prefill_chunk_tokens", 0) > 0
    assert eng.telemetry.counters["prefill_chunks"] == (1 if chunked else 0)
