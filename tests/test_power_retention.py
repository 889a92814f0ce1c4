"""The power-retention family (HF `brumby`: no layer holds keys, the only
per-sequence state is float32 and lives in the slot, prefill in chunks that
carry it) on the CPU at a toy size of its own: hidden 64, 2 layers, 4 query
and 2 KV heads of 16 (the folded state has (16/2 + 1) x 16 = 144 entries a
KV head), vocabulary 512. Seeded weights from the benchmark's family files,
logits compared with its plain reference (the attention form, float32, no
import of the program).

Tolerance: float32 on both sides at `highest` matmul precision; the two
sides share no algorithm (the reference has no phi and no state), so what
is left is the order of float32 sums over up to 60 tokens: 2e-5 on logits
whose spread is 1 (6e-6 read). A forward that dropped the gate, the
normaliser, the square or a chunk's carry reads 1e-2 or more and fails it.
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
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import (
    EngineRequest, InferenceEngine, new_decode_state)
from xllm_service_tpu.models import power_retention as pr
from xllm_service_tpu.models.hf_config import model_config_from_hf
from xllm_service_tpu.ops import retention as R
from xllm_service_tpu.ops import pallas_retention as PR

from test_engine import Collector, run_requests

ROOT = Path(__file__).resolve().parent.parent
SEED = 2 ** 31 + 77
TOL = 2e-5
EPS = 1e-6

TOY_HF = {
    "model_type": "brumby", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False, "sliding_window": None,
    "use_sliding_window": False, "max_position_embeddings": 512,
    "chipbench": {"family": "power-retention"}}


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


def test_hf_config_maps_every_width_and_names_no_kv_layer(toy):
    mcfg, params, _ = toy
    assert mcfg.name == "power_retention"
    assert (mcfg.kv_layers, mcfg.num_layers) == (0, 2)
    assert mcfg.layer_types == ("retention", "retention")
    assert (mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim) == (4, 2, 16)
    assert (mcfg.rms_eps, mcfg.rope_theta, mcfg.tie_embeddings) == (
        1e-6, 1e6, False)
    assert pr.toy_config(dtype=jnp.float32) == mcfg
    shapes = jax.eval_shape(lambda r: pr.init_params(mcfg, r),
                            jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == jax.tree.map(
        lambda a: (a.shape, a.dtype), jax.eval_shape(
            lambda r: pr.init_params(dataclasses.replace(
                mcfg, dtype=jnp.bfloat16), r), jax.random.PRNGKey(0)))
    assert shapes["layers"]["g_proj"]["bias"].dtype == jnp.float32
    # the program's own init holds the gate where the seeded weights do
    own = pr.init_params(mcfg, jax.random.PRNGKey(1))
    gate = jax.nn.sigmoid(own["layers"]["g_proj"]["bias"])
    assert 0.89 < float(gate.min()) and float(gate.max()) < 0.9991


@pytest.mark.parametrize("key,value,why", [
    ("attention_bias", True, "biases"),
    ("tie_word_embeddings", True, "tied"),
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("use_sliding_window", True, "sliding window"),
    ("num_key_value_heads", 3, "integer group"),
    ("head_dim", 15, "even head size")])
def test_hf_config_refuses_what_the_family_does_not_compute(
        tmp_path, key, value, why):
    (tmp_path / "config.json").write_text(json.dumps({**TOY_HF, key: value}))
    with pytest.raises(ValueError, match=why):
        model_config_from_hf(tmp_path)


def test_weights_are_a_pure_function_of_the_seed(toy):
    _, params, family = toy
    again = family.weights.make_params(SEED, TOY_HF, "bfloat16")
    other = family.weights.make_params(SEED + 1, TOY_HF, "bfloat16")
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, again)
    assert all(jax.tree.leaves(same))
    differ = jax.tree.map(lambda a, b: bool((a != b).any()), params, other)
    assert all(jax.tree.leaves(differ))
    gate = jax.nn.sigmoid(params["layers"]["g_proj"]["bias"])
    assert 0.89 < float(gate.min()) and float(gate.max()) < 0.9991


# ------------------------------------------------- (a) the three plain forms
def _qkvg(S, Hq=4, Hk=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    lg = jax.nn.log_sigmoid(jax.random.uniform(ks[3], (S, Hk), minval=1.0,
                                               maxval=5.0))
    return (jax.random.normal(ks[0], (S, Hq, d)),
            jax.random.normal(ks[1], (S, Hk, d)),
            jax.random.normal(ks[2], (S, Hk, d)), lg)


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_squares_the_dot_product(d):
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, d))
    assert R.phi(a).shape == (d // 2 + 1, d)
    assert float((R.phi(a) * R.phi(b)).sum()) == pytest.approx(
        float(jnp.dot(a, b)) ** 2 / d, rel=1e-5)
    assert (R.slabs(128), R.z_rows(128)) == (65, 72)


def _recurrent(q, k, v, lg, s, z):
    out = []
    for t in range(q.shape[0]):
        o, s, z = R.retention_step(s, z, q[t], k[t], v[t], lg[t], EPS)
        out.append(o)
    return jnp.stack(out), s, z


@pytest.mark.parametrize("S,sub", [(37, 8), (24, 24), (33, 64), (16, 5)])
def test_recurrent_chunked_and_attention_forms_are_equal(S, sub):
    q, k, v, lg = _qkvg(S, seed=S)
    s0, z0 = R.empty_state(2, 16)
    with jax.default_matmul_precision("highest"):
        want = R.retention_attention(q, k, v, lg, EPS)
        rec, s_rec, z_rec = _recurrent(q, k, v, lg, s0, z0)
        got, s1, z1 = R.retention_chunked(q, k, v, lg, s0, z0, EPS, sub=sub)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(rec - want).max()) < 1e-4 * scale
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    assert float(jnp.abs(s1 - s_rec).max()) < 1e-5
    assert float(jnp.abs(z1 - z_rec).max()) < 1e-5
    assert not np.asarray(z1[:, R.slabs(16):]).any()


@pytest.mark.parametrize("cut", [1, 13, 20])
def test_a_chunk_takes_the_state_the_chunk_before_left(cut):
    """Two calls that hand (S, z) on equal one call; without the carry the
    second half is far off."""
    q, k, v, lg = _qkvg(37, seed=3)
    s0, z0 = R.empty_state(2, 16)
    with jax.default_matmul_precision("highest"):
        want = R.retention_attention(q, k, v, lg, EPS)
        a, s, z = R.retention_chunked(q[:cut], k[:cut], v[:cut], lg[:cut],
                                      s0, z0, EPS, sub=8)
        b, s, z = R.retention_chunked(q[cut:], k[cut:], v[cut:], lg[cut:],
                                      s, z, EPS, sub=8)
        lost, _, _ = R.retention_chunked(q[cut:], k[cut:], v[cut:], lg[cut:],
                                         s0, z0, EPS, sub=8)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(jnp.concatenate([a, b]) - want).max()) < 1e-5 * scale
    assert float(jnp.abs(lost - want[cut:]).max()) > 1e-2 * scale


def test_padding_behind_the_sequence_leaves_the_state_alone():
    q, k, v, lg = _qkvg(24, seed=5)
    s0, z0 = R.empty_state(2, 16)
    _, s, z = R.retention_chunked(q[:17], k[:17], v[:17], lg[:17], s0, z0,
                                  EPS, sub=8)
    valid = (jnp.arange(24) < 17)
    _, sp, zp = R.retention_chunked(
        q, jnp.where(valid[:, None, None], k, 0), v,
        jnp.where(valid[:, None], lg, 0.0), s0, z0, EPS, sub=8)
    assert float(jnp.abs(sp - s).max()) < 1e-6
    assert float(jnp.abs(zp - z).max()) < 1e-6


# ------------------------------------- (b) each kernel against its plain form
@pytest.mark.parametrize("live", [
    [True, False, True, True], [False, False, False, True],
    [True, True, True, True], [False, False, False, False]])
def test_the_update_kernel_in_interpret_mode_equals_the_plain_update(live):
    L, B, Hq, Hk, d = 2, 4, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    s = jax.random.normal(ks[0], (L, B, Hk, R.slabs(d), d, d))
    z = jnp.abs(jax.random.normal(ks[1], (L, B, Hk, R.z_rows(d), d))) + 1.0
    z = z.at[:, :, :, R.slabs(d):].set(0)
    q = jax.random.normal(ks[2], (B, Hq, d))
    k = jax.random.normal(ks[3], (B, Hk, d))
    v = jax.random.normal(ks[4], (B, Hk, d))
    lg = jax.nn.log_sigmoid(jax.random.normal(ks[5], (B, Hk)) + 3.0)
    live = jnp.asarray(live)
    with jax.default_matmul_precision("highest"):
        want = R.retention_update_plain(s, z, jnp.int32(1), live, q, k, v,
                                        lg, EPS)
        got = PR.retention_update_pallas(s + 0, z + 0, jnp.int32(1), live,
                                         q, k, v, lg, EPS, interpret=True)
    scale = max(1.0, float(jnp.abs(want[0]).max()))
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-4 * scale
    assert float(jnp.abs(got[1] - want[1]).max()) < 1e-5
    assert float(jnp.abs(got[2] - want[2]).max()) < 1e-5
    # a dead slot's state, and the other layer, are left as they were
    dead = ~np.asarray(live)
    assert (np.asarray(got[1][1])[dead] == np.asarray(s[1])[dead]).all()
    assert (np.asarray(got[1][0]) == np.asarray(s[0])).all()
    assert not np.asarray(got[0])[dead].any()


@pytest.mark.parametrize("S,sub", [(37, 8), (32, 32)])
def test_the_prefill_kernel_in_interpret_mode_equals_the_plain_form(S, sub):
    q, k, v, lg = _qkvg(S, seed=11)
    s0 = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                 (2, R.slabs(16), 16, 16))
    z0 = jnp.zeros((2, R.z_rows(16), 16)).at[:, :R.slabs(16)].set(0.3)

    def cross(*a):
        return PR.retention_cross_pallas(*a, interpret=True)

    with jax.default_matmul_precision("highest"):
        want = R.retention_chunked(q, k, v, lg, s0, z0, EPS, sub=sub)
        got = R.retention_chunked(q, k, v, lg, s0, z0, EPS, sub=sub,
                                  cross=cross)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < 1e-5 * max(
            1.0, float(jnp.abs(w).max()))


def test_the_path_is_decided_by_backend_and_shape():
    assert R.retention_path("tpu", False, 128) == "pallas"
    assert R.retention_path("cpu", True, 16) == "pallas"
    assert R.retention_path("cpu", False, 128) == "xla (cpu backend)"
    assert "head_dim=64" in R.retention_path("tpu", False, 64)


# ----------------------------------------- (c) the program vs the reference
TOKS = np.random.default_rng(1).integers(16, 512, 60).tolist()


def _served_logits(mcfg, params, chunks, slot=1, B=3, others=False):
    """Logits at positions n_prompt-1 .. 59: the prompt through
    `prefill_forward` in `chunks` (each padded to a bucket of 32, each
    taking the state the one before left), the state installed in slot
    `slot` of the engine's decode state, the rest token by token through
    `decode_forward`. With `others`, slot 0 is live on another sequence and
    slot 2 holds a dead sequence's state."""
    ecfg = EngineConfig(model=mcfg, model_family="power_retention",
                        num_pages=32, max_batch_size=B, max_seq_len=128,
                        prefill_buckets=(64, 128))
    d = new_decode_state(ecfg)
    assert d["kv"].shape[0] == 0 and d["kv"].size == 0
    assert d["ret_s"].dtype == d["ret_z"].dtype == jnp.float32
    assert d["ret_s"].shape == (2, B, 2, 9, 16, 16)
    assert d["ret_z"].shape == (2, B, 2, 16, 16)
    pt = jnp.zeros((B, 8), jnp.int32)
    with jax.default_matmul_precision("highest"):
        state, at = None, 0
        for c in chunks:
            padded = jnp.asarray([TOKS[at:at + c] + [0] * (32 - c)])
            lg, kv, state = pr.prefill_forward(
                params, mcfg, padded, at + jnp.arange(32)[None, :], d["kv"],
                pt[:1], jnp.asarray([at]), jnp.asarray([c]), state=state)
            at += c
        out = [np.asarray(lg[0])]
        st = {k: d[k].at[:, slot].set(state[k][:, 0]) for k in state}
        live = jnp.zeros((B,), bool).at[slot].set(True)
        if others:
            st = {k: v.at[:, 0].set(0.5 * state[k][:, 0]).at[:, 2].set(7.0)
                  for k, v in st.items()}
            live = live.at[0].set(True)
        step = jax.jit(lambda t, pos, st: pr.decode_forward(
            params, mcfg, jnp.full((B,), 17, jnp.int32).at[slot].set(t),
            jnp.full((B,), pos), d["kv"], pt, jnp.ones((B,), jnp.int32),
            state=st, live=live))
        for pos in range(at, 60):
            lg, kv, st = step(TOKS[pos], pos, st)
            out.append(np.asarray(lg[slot]))
        dead = 2 if others else 0
        want_dead = 7.0 if others else 0.0
        assert (np.asarray(st["ret_s"][:, dead]) == want_dead).all()
    return np.stack(out)


@pytest.fixture(scope="module")
def want(toy):
    _, _, family = toy
    out = family.reference.logits_at(SEED, TOY_HF, "bfloat16", [TOKS],
                                     [list(range(60))])[0]
    assert 0.5 < out.std() < 2.0
    return out


@pytest.mark.parametrize("chunks,others", [
    ([5], False), ([29], False), ([29], True),          # prefill whole
    ([16, 20, 7], False), ([32, 28], True), ([1, 32, 3], False)])
def test_prefill_chunks_then_decode_through_the_state_equal_the_reference(
        toy, want, chunks, others):
    """Whole, and in chunks of unequal length with the state carried; then
    decode to 60 through the slot, alone and beside a live and a dead
    slot: every served position's logits against the reference's."""
    mcfg, params, _ = toy
    got = _served_logits(mcfg, params, chunks, others=others)
    assert np.max(np.abs(got - want[sum(chunks) - 1:])) < TOL


def _no_gate(monkeypatch):
    real = pr._qkvg
    monkeypatch.setattr(pr, "_qkvg", lambda *a: (
        *real(*a)[:3], jnp.zeros_like(real(*a)[3])))


def _no_normaliser(monkeypatch):
    for name in ("retention_prefill", "retention_update"):
        real = getattr(pr, name)
        monkeypatch.setattr(pr, name, lambda *a, _f=real, **kw: _f(
            *a[:-1], 1e3, **kw))


def _no_carry(monkeypatch):
    real = pr.prefill_forward
    monkeypatch.setattr(pr, "prefill_forward", lambda *a, state=None, **kw:
                        real(*a, state=None, **kw))


@pytest.mark.parametrize("drop,chunks,least", [
    (_no_gate, [29], 1e-2), (_no_normaliser, [29], 1e-3),
    (_no_carry, [16, 13], 1e-2)])
def test_a_forward_that_departs_from_the_equations_fails_the_tolerance(
        toy, want, monkeypatch, drop, chunks, least):
    """Without the gate (every g = 1), with another normaliser (eps 1e3 in
    1e-6's place), or with a second chunk that starts from an empty state,
    the same comparison reads far outside the tolerance."""
    mcfg, params, _ = toy
    drop(monkeypatch)
    got = _served_logits(mcfg, params, chunks)
    assert np.max(np.abs(got - want[sum(chunks) - 1:])) > max(least,
                                                               50 * TOL)


def test_the_degree_is_part_of_what_is_compared(toy, want, monkeypatch):
    """The reference at degree 1 (the weights |q.k| in (q.k)^2's place)
    reads far from the program, which reads the reference at degree 2 to
    the tolerance."""
    mcfg, params, family = toy
    ref = family.reference

    def degree_one(q, k, v, log_g):
        G = jnp.cumsum(log_g, axis=0).T
        S = q.shape[0]
        seen = jnp.tril(jnp.ones((S, S), bool))
        a = jnp.abs(jnp.einsum("tngh,snh->ngts", q, k)) * jnp.exp(jnp.where(
            seen, G[:, :, None] - G[:, None, :], -jnp.inf))[:, None]
        den = jnp.moveaxis(a.sum(-1), -1, 0)
        return jnp.einsum("ngts,snh->tngh", a, v) / (den[..., None] + EPS)

    got = _served_logits(mcfg, params, [29])
    assert np.max(np.abs(got - want[28:])) < TOL
    monkeypatch.setattr(ref, "_retention", degree_one)
    jax.clear_caches()
    try:
        one = ref.logits_at(SEED, TOY_HF, "bfloat16", [TOKS],
                            [list(range(28, 60))])[0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert np.max(np.abs(got - one)) > 1e-2


# --------------------------------------------- (d) through InferenceEngine
def _engine(mcfg, params, **kw):
    base = dict(model=mcfg, model_family="power_retention", num_pages=64,
                page_size=16, hash_block_size=32, max_batch_size=3,
                max_seq_len=128, prefill_buckets=(16, 32, 64, 128),
                decode_horizon=4, prefill_chunk_tokens=16)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base), params=params)


def _req(name, prompt, n, **kw):
    return EngineRequest(name, token_ids=list(prompt), sampling=SamplingParams(
        max_tokens=n, temperature=0.0, ignore_eos=True),
        on_output=Collector(), **kw)


def _alone(mcfg, params, prompt, n, **kw):
    r = _req("alone", prompt, n)
    run_requests(_engine(mcfg, params, **kw), [r])
    return r.on_output.tokens


P1, P2, P3 = TOKS[:21], TOKS[10:57], TOKS[30:43]


def test_engine_tokens_are_the_references_best_chunked_or_whole(toy):
    """Through `prefill_chunk`, `prefill_install` and `decode_multi`: every
    served token is the reference's best at its position, to the
    tolerance, and chunking serves what a whole install serves."""
    mcfg, params, family = toy
    eng = _engine(mcfg, params)
    r = _req("r", P2, 12)
    run_requests(eng, [r])
    served = r.on_output.tokens
    assert len(served) == 12
    seq = P2 + served
    lg = family.reference.logits_at(
        SEED, TOY_HF, "bfloat16", [seq],
        [list(range(len(P2) - 1, len(seq) - 1))])[0]
    assert (lg.max(-1) - lg[np.arange(12), served]).max() < TOL
    assert served == _alone(mcfg, params, P2, 12, prefill_chunk_tokens=0)
    c = eng.telemetry.counters
    # 47 tokens at 16 a chunk: two chunks, then an install of 15
    assert (c["prefill_chunks"], c["prefill_chunk_tokens"],
            c["prefill_chunked_admissions"], c["admissions"]) == (2, 32, 1, 1)
    assert c["prefill_calls/chunk"] == 2 and c["prefill_calls/16"] == 1
    assert c["prefix_skipped_stateful"] == 1 and c["walk_chunks"] == 0
    assert c["state_bytes_reserved"] == 2 * 3 * 2 * (9 * 16 + 16) * 16 * 4
    assert eng.stats()["attention_paths"]["prefill_chunk"] == {
        "retention_prefill": "xla (shape outside the kernel's tiling: "
                             "head_dim=16)"}


def test_under_queue_pressure_the_rule_is_still_one(toy):
    """Arrivals waiting do not send a suffix of several chunks to a whole
    install: no program of this family holds more than a chunk."""
    mcfg, params, _ = toy
    eng = _engine(mcfg, params)
    reqs = [_req("a", P2, 4), _req("b", P2[3:], 4), _req("c", P1, 4)]
    run_requests(eng, reqs)
    c = eng.telemetry.counters
    assert c["prefill_chunked_admissions"] == 3
    assert not [k for k in c if k.startswith("prefill_calls/")
                and k.split("/")[1] not in ("chunk", "16")]
    assert eng._install_buckets() == (16,)
    assert _engine(mcfg, params,
                   prefill_chunk_tokens=0)._install_buckets() == (
        16, 32, 64, 128)
    for r in reqs:
        assert r.on_output.tokens == _alone(
            mcfg, params, r.token_ids, 4, prefill_chunk_tokens=0)


def test_a_slots_state_is_zeroed_by_the_next_occupants_first_chunk(toy):
    """One slot: the second sequence takes the first one's slot, whose
    state its first chunk (or its install) starts from zeros, and serves
    what a fresh engine serves."""
    mcfg, params, _ = toy
    eng = _engine(mcfg, params, max_batch_size=1)
    a, b, c = _req("a", P1, 12), _req("b", P2, 9), _req("c", P3, 5)
    run_requests(eng, [a, b, c])
    assert a.on_output.tokens == _alone(mcfg, params, P1, 12)
    assert b.on_output.tokens == _alone(mcfg, params, P2, 9)
    assert c.on_output.tokens == _alone(mcfg, params, P3, 5)


def test_sequences_admitted_at_different_steps_agree_with_each_alone(toy):
    mcfg, params, _ = toy
    eng = _engine(mcfg, params)
    a, b, c = _req("a", P1, 16), _req("b", P2, 12), _req("c", P3, 8)
    eng.submit(a)
    for _ in range(2):
        eng.step()
    eng.submit(b)         # three chunks, interleaved with a's decode calls
    eng.step()
    eng.submit(c)
    run_requests(eng, [a, b, c][:0])
    while not all(r.on_output.done.is_set() for r in (a, b, c)):
        eng.step()
    assert a.on_output.tokens == _alone(mcfg, params, P1, 16)
    assert b.on_output.tokens == _alone(mcfg, params, P2, 12)
    assert c.on_output.tokens == _alone(mcfg, params, P3, 8)


def test_the_kernels_run_inside_the_engines_programs(toy, monkeypatch):
    """Under interpret mode every program takes its kernel (the layer as a
    scalar, the state aliased, the sub-chunk's size in the kernel's name)
    and serves what the plain forms serve; warm-up compiles the chunk
    program and only the buckets an install can meet."""
    mcfg, params, _ = toy
    plain = _alone(mcfg, params, P2, 6)
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
    eng = _engine(mcfg, params, warmup_programs=True)
    assert not np.asarray(eng._dstate["ret_s"]).any()
    a, b = _req("a", P2, 6), _req("b", P3, 5)
    run_requests(eng, [a, b])
    paths = eng.stats()["attention_paths"]
    assert paths["decode_multi"] == {"retention_update": "pallas"}
    assert paths["prefill_chunk"] == paths["prefill_install"] == {
        "retention_prefill": "pallas"}
    assert a.on_output.tokens == plain
    assert PR._prefill_impl(16).__name__ == "_retention_prefill_impl_c16"
