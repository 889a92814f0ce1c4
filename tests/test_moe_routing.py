"""The expert block of the sparse families (models/deepseek_moe.py
`_moe_mlp`, shared by mixtral): the router's two forms, the grouped
dispatch against the dense contraction, the rows that hold no request, and
the router's counts brought home by the engine."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models import deepseek_moe as dm
from xllm_service_tpu.models.base import tiny_config
from xllm_service_tpu.models.mixtral import mixtral_tiny_config
from xllm_service_tpu.ops import grouped_matmul as gm

from test_engine import Collector, run_requests

V3 = dict(router_scoring="sigmoid", router_bias=True, routed_scale=2.448,
          rope_interleave=True)
FORMS = {"softmax-over-chosen": {}, "softmax-of-all": {"router_norm_topk": False},
         "sigmoid-bias-scale": V3}


def _cfg(**kw):
    return dm.tiny_mla_config(
        dtype=jnp.float32, num_experts=8, num_experts_per_token=3,
        first_dense_layers=1, num_layers=3, **kw)


def _block(cfg, seed=0):
    params = dm.init_params(cfg, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (5, 7, cfg.hidden_size), jnp.float32)
    live = jax.random.bernoulli(jax.random.PRNGKey(seed + 2), 0.6, (5, 7))
    return params["moe"], x, live


def _dense(moe, layer, x, cfg, live):
    with mock.patch.object(dm, "experts_path", lambda c, e: "dense (test)"):
        return dm._moe_mlp(moe, layer, x, cfg, live)


@pytest.mark.parametrize("form", FORMS)
def test_grouped_dispatch_equals_the_dense_contraction(form):
    """Every live row's output, both router forms, to float32 rounding;
    the counts are the router's own on either path."""
    cfg = _cfg(**FORMS[form])
    moe, x, live = _block(cfg)
    yg, cg = dm._moe_mlp(moe, 1, x, cfg, live)
    yd, cd = _dense(moe, 1, x, cfg, live)
    rows = np.asarray(live)
    assert np.max(np.abs(np.asarray(yg - yd)[rows])) < 5e-6 * float(
        jnp.abs(yd).max())
    assert cg.tolist() == cd.tolist() == [int(rows.sum()), cg[1]]
    # without a mask every row is live and the two agree everywhere
    ya, ca = dm._moe_mlp(moe, 1, x, cfg)
    assert np.max(np.abs(np.asarray(ya - _dense(moe, 1, x, cfg, None)[0]))
                  ) < 5e-6 * float(jnp.abs(yd).max())
    assert int(ca[0]) == 35


def test_mixtrals_dense_path_is_bit_for_bit_the_softmax_over_the_chosen():
    """The dense contraction with the default form is what it was: a
    softmax over the chosen logits scattered into a [T, E] map."""
    cfg = mixtral_tiny_config(dtype=jnp.float32)
    params = dm.init_params(cfg, jax.random.PRNGKey(3))
    lp = jax.tree.map(lambda a: a[1], params["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (9, cfg.hidden_size))
    logits = x @ lp["router"]["kernel"]
    topv, topi = jax.lax.top_k(logits, 2)
    gates = jnp.zeros_like(logits).at[jnp.arange(9)[:, None], topi].set(
        jax.nn.softmax(topv, -1))
    ex = lp["experts"]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, ex["gate_proj"]["kernel"])
                    ) * jnp.einsum("td,edf->etf", x, ex["up_proj"]["kernel"])
    want = jnp.einsum("etd,te->td", jnp.einsum(
        "etf,efd->etd", h, ex["down_proj"]["kernel"]), gates)
    got, _ = _dense(params["moe"], 1, x, cfg, None)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    grouped, _ = dm._moe_mlp(params["moe"], 1, x, cfg)
    assert np.max(np.abs(np.asarray(grouped - want))) < 1e-5


def test_the_bias_enters_the_choice_and_never_the_weights():
    cfg = _cfg(**V3)
    moe, x, _ = _block(cfg)
    x2 = x.reshape(-1, cfg.hidden_size)
    router = jax.tree.map(lambda a: a[0], moe["router"])
    topi, gates = dm._route(router, x2, cfg)
    s = np.asarray(jax.nn.sigmoid(x2 @ router["kernel"]))
    b = np.asarray(router["bias"])
    want_i = np.argsort(-(s + b), axis=-1)[:, :3]
    assert np.array_equal(np.sort(np.asarray(topi)), np.sort(want_i))
    w = np.take_along_axis(s, np.asarray(topi), -1)
    assert np.allclose(np.asarray(gates),
                       w / w.sum(-1, keepdims=True) * 2.448, rtol=1e-6)
    # and it changes some choices: without it other experts are chosen
    no_bias, _ = dm._route({"kernel": router["kernel"]}, x2, cfg)
    changed = (np.sort(np.asarray(no_bias)) != np.sort(np.asarray(topi))
               ).any(-1)
    assert 0 < changed.sum() < len(changed)


def test_dead_rows_reach_no_expert():
    """The experts touched are exactly those the live rows chose: the count
    says so, and a dead row's input cannot move any live row's output nor
    the count (NaNs in it stay where they are)."""
    cfg = _cfg(**V3)
    moe, x, _ = _block(cfg)
    live = jnp.zeros((5, 7), bool).at[1, 2:4].set(True)    # 2 of 35 rows
    y, counts = dm._moe_mlp(moe, 0, x, cfg, live)
    router = jax.tree.map(lambda a: a[0], moe["router"])
    topi, _ = dm._route(router, x.reshape(-1, cfg.hidden_size), cfg)
    rows = np.asarray(live).reshape(-1)
    chosen = set(np.asarray(topi)[rows].reshape(-1).tolist())
    assert counts.tolist() == [int(rows.sum()), len(chosen)]
    assert len(chosen) < len(set(np.asarray(topi).reshape(-1).tolist()))
    poisoned = jnp.where(live[..., None], x, jnp.nan)
    yp, cp = dm._moe_mlp(moe, 0, poisoned, cfg, live)
    assert cp.tolist() == counts.tolist()
    assert np.array_equal(np.asarray(yp)[np.asarray(live)],
                          np.asarray(y)[np.asarray(live)])
    # no live row at all: nothing is touched
    none = jnp.zeros_like(live)
    assert dm._moe_mlp(moe, 0, x, cfg, none)[1].tolist() == [0, 0]


def _pairs(sizes, total):
    """Pairs' experts with `sizes[g]` pairs on group g and the rest dead,
    shuffled: the plan has to sort them."""
    pe = np.concatenate([np.full(n, g) for g, n in enumerate(sizes)]
                        + [np.full(total - sum(sizes), len(sizes))])
    return np.random.default_rng(sum(sizes)).permutation(pe).astype(np.int32)


def _plan_cases():
    rng = np.random.default_rng(7)
    mixed = rng.integers(0, 128, 192)
    mixed[rng.random(192) < 0.6] = 128
    return {
        # name: (pairs' experts, experts, row tile)
        "decode-192-pairs": (rng.integers(0, 128, 192), 128, 16),
        "prefill-3072-pairs": (rng.integers(0, 128, 3072), 128, 32),
        "all-on-one-expert": (np.full(192, 5), 128, 16),
        "no-live-row": (np.full(192, 128), 128, 16),
        "dead-rows-mixed-in": (mixed, 128, 16),
        "an-expert-over-three-tiles": (_pairs([3, 0, 0, 40, 0, 0, 0, 5], 64),
                                       8, 16),
        "pairs-not-a-multiple-of-the-tile": (rng.integers(0, 9, 50), 8, 16),
    }


_PLAN_CASES = _plan_cases()


@pytest.mark.parametrize("case", _PLAN_CASES)
def test_the_dispatch_plan_is_the_librarys_group_metadata(case):
    """`dispatch_plan` against `megablox.gmm.make_group_metadata`, the
    independent reference (it builds the list from the sizes with
    cumulative sums, `repeat` and a histogram): the same offsets, the same
    (group, row tile) visits in the same order up to the count; past it
    the plan repeats its last visit. The order is the stable sort's and
    `inverse` undoes it."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    pe, E, tm = _PLAN_CASES[case]
    P = len(pe)
    plan = jax.jit(gm.dispatch_plan, static_argnums=(1, 2))(
        jnp.asarray(pe, jnp.int32), E, tm)
    assert np.array_equal(plan.order, np.argsort(pe, kind="stable"))
    assert np.array_equal(np.asarray(plan.inverse)[np.asarray(plan.order)],
                          np.arange(P))
    assert np.array_equal(plan.sizes, np.bincount(pe, minlength=E + 1)[:E])
    (offsets, group_ids, m_tile_ids), n = make_group_metadata(
        group_sizes=plan.sizes, m=-(-P // tm) * tm, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=E,
        visit_empty_groups=False)
    n = int(n)
    assert int(plan.num_visits) == n
    assert np.array_equal(plan.offsets, offsets)
    assert plan.group_ids.shape == group_ids.shape == (-(-P // tm) + E - 1,)
    assert np.array_equal(plan.group_ids[:n], group_ids[:n])
    assert np.array_equal(plan.m_tile_ids[:n], m_tile_ids[:n])
    last = max(n - 1, 0)
    assert (np.asarray(plan.group_ids[n:]) == plan.group_ids[last]).all()
    assert (np.asarray(plan.m_tile_ids[n:]) == plan.m_tile_ids[last]).all()
    assert int(plan.group_ids.max()) < E
    if case == "an-expert-over-three-tiles":
        # group 3 holds rows 3..42: tiles 0, 1 and 2; it shares tile 0
        # with group 0 and tile 2 with group 7
        assert plan.group_ids[:n].tolist() == [0, 3, 3, 3, 7]
        assert plan.m_tile_ids[:n].tolist() == [0, 0, 1, 2, 2]
    if case == "no-live-row":
        assert n == 0


@pytest.mark.parametrize("sizes,layer", [
    ([5, 0, 9, 3], 2), ([0, 0, 0, 0], 2), ([24, 0, 0, 0], 2),
    ([1, 16, 0, 6], 1)])
def test_the_pallas_grouped_product_in_interpret_mode(sizes, layer):
    """The kernel the chip runs (the repo's, over the whole stack, with
    the layer's index added to the visit's group) against plain products;
    rows of no group are the caller's to mask."""
    L, G, K, N = 3, 4, 256, 128
    stack = jax.random.normal(jax.random.PRNGKey(0), (L, G, K, N))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, K))
    plan = gm.dispatch_plan(
        jnp.asarray(np.sort(_pairs(sizes, 24))), G, gm.row_tile(24, G))
    assert plan.sizes.tolist() == sizes
    want, row = [], 0
    for g, n in enumerate(sizes):
        want.append(x[row:row + n] @ stack[layer, g])
        row += n
    want = jnp.concatenate(want)
    for interpret in (True, False):
        got = gm.grouped_matmul(x, stack, layer, plan, backend="cpu",
                                interpret=interpret)
        assert got.shape == (24, N)
        assert np.allclose(np.asarray(got[:row]), np.asarray(want),
                           atol=2e-4)
    assert gm.grouped_path("tpu", False).startswith("grouped (pallas")
    assert gm.grouped_path("cpu", True).startswith("grouped (pallas")
    assert gm.grouped_path("cpu", False).startswith("grouped (ragged_dot")
    assert gm.row_tile(192, 128) == 16 and gm.row_tile(12288, 128) == 128
    assert gm._tiling(192, 2048, 768, 128, 2) == (16, 2048, 768)
    assert gm._tiling(192, 768, 2048, 128, 2) == (16, 768, 2048)
    assert gm._tiling(64, 4096, 14336, 8, 2)[1:] == (2048, 768)
    # a plan made for another row tile is refused, not mis-read
    with pytest.raises(ValueError, match="visits"):
        gm.grouped_matmul(x, stack, layer, gm.dispatch_plan(
            jnp.zeros((24,), jnp.int32), G, 8), backend="cpu",
            interpret=True)


def test_the_pallas_kernel_takes_column_strips_and_a_ragged_k():
    """Widths past one block: K in tiles of 2048 with a remainder that is
    masked, N in strips of 768, bfloat16 operands; a group that straddles
    row tiles. Against `ragged_dot` to the output's rounding."""
    L, G, K, N = 2, 3, 2048 + 512, 1024
    stack = (0.05 * jax.random.normal(jax.random.PRNGKey(0), (L, G, K, N))
             ).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, K)).astype(
        jnp.bfloat16)
    assert gm._tiling(40, K, N, G, 2) == (16, 2048, 768)
    plan = gm.dispatch_plan(jnp.asarray(np.sort(_pairs([17, 0, 20], 40))),
                            G, 16)
    got, want = (gm.grouped_matmul(x, stack, 1, plan, backend="cpu",
                                   interpret=i)[:37].astype(jnp.float32)
                 for i in (True, False))
    assert float(jnp.abs(want).max()) > 4
    assert float(jnp.abs(got - want).max()) <= 2 ** -5


def test_the_expert_block_on_the_cpu_is_the_parents_bit_for_bit():
    """`_moe_mlp` through `ragged_dot` with the plan's order, inverse and
    sizes: the live rows' output of the tree before the plan (PR 36's,
    values pasted from it), to the last bit."""
    import hashlib

    cfg = _cfg(**V3)
    moe, x, live = _block(cfg)
    y, counts = dm._moe_mlp(moe, 1, x, cfg, live)
    rows = np.asarray(y)[np.asarray(live)]
    assert counts.tolist() == [22, 8]
    assert [float(v).hex() for v in rows[0, :4]] == [
        "-0x1.0e8b740000000p-2", "-0x1.4418920000000p-1",
        "0x1.5d078c0000000p+0", "-0x1.44b7d20000000p-2"]
    assert [float(v).hex() for v in rows[-1, -4:]] == [
        "-0x1.abce260000000p-1", "-0x1.5d17a80000000p-1",
        "-0x1.624b9c0000000p+0", "0x1.8e7eac0000000p-2"]
    assert hashlib.sha256(rows.tobytes()).hexdigest() == (
        "250f68bc9775f200fb9552bfc21526e91d3d4ce7cde18e141c56c597be0d1ee0")


def _equations(jaxpr, into):
    """Every equation of `jaxpr` by primitive, through nested programs but
    not into a kernel's body."""
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] = into.get(eqn.primitive.name, 0) + 1
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _equations(inner, into)
    return into


def test_an_expert_layer_plans_once_in_few_operations():
    """One expert layer of the benchmark's sparse configuration at
    decode's shape (32 rows, 128 experts, 6 a token), traced down the TPU
    branch: three kernel calls on one plan, no loop, one sort beside the
    router's `top_k`, and few equations around them. A library upgrade or
    an edit that brings a product's own group metadata back (PR 36: three
    times ~150 equations and a `searchsorted` loop a layer) fails here."""
    from benchmarks import compile_gate as gate

    cfg = dm.tiny_mla_config(
        dtype=jnp.bfloat16, hidden_size=2048, moe_ffn_size=768,
        num_experts=128, num_experts_per_token=6, first_dense_layers=1,
        num_layers=3, **V3)
    moe = jax.eval_shape(
        lambda: dm.init_params(cfg, jax.random.PRNGKey(0)))["moe"]
    with gate.steer_to_tpu():
        traced = jax.make_jaxpr(
            lambda m, x, live: dm._moe_mlp(m, 1, x, cfg, live))(
                moe, jax.ShapeDtypeStruct((32, 2048), jnp.bfloat16),
                jax.ShapeDtypeStruct((32,), jnp.bool_))
    count = _equations(traced.jaxpr, {})
    assert count.pop("pallas_call") == 3
    assert "while" not in count and "scan" not in count
    assert count["sort"] == 1 and count["top_k"] == 1
    assert "cumsum" not in count and "reduce_window_sum" not in count
    count.pop("jit", None)                  # the wrappers themselves
    # 198 as landed (PR 37), + 10%
    assert sum(count.values()) <= 218, count


def test_the_path_is_decided_from_what_the_code_sees():
    cfg = _cfg()
    moe, _, _ = _block(cfg)
    assert dm.experts_path(cfg, moe["experts"]).startswith("grouped")
    q = {"gate_proj": {"kernel": {"q8": 0, "scale": 0}}}
    assert dm.experts_path(cfg, q) == "dense (int8 experts)"
    mesh = mock.Mock(shape={"expert": 4}, size=4)
    with mock.patch.object(dm, "program_mesh", lambda: mesh):
        assert dm.experts_path(cfg, moe["experts"]).startswith("dense (mesh")


# ------------------------------------------------------ through the engine
def _engine(mcfg, **kw):
    base = dict(model=mcfg, model_family=mcfg.name, num_pages=64,
                page_size=16, hash_block_size=32, max_batch_size=4,
                max_seq_len=128, prefill_buckets=(32, 64, 128),
                decode_horizon=4)
    base.update(kw)
    return InferenceEngine(EngineConfig(**base))


def _req(name, prompt, n):
    return EngineRequest(name, token_ids=list(prompt), sampling=SamplingParams(
        max_tokens=n, temperature=0.0, ignore_eos=True),
        on_output=Collector())


def test_the_routers_counts_come_home_with_the_decode_calls():
    """One request in a batch of four slots: every step it is live touches
    exactly k experts a layer, the three empty slots none; the steps after
    it stops inside its last call route nothing."""
    cfg = _cfg(**V3)
    eng = _engine(cfg)
    r = _req("a", range(5, 25), 10)
    run_requests(eng, [r])
    assert len(r.on_output.tokens) == 10
    c = eng.telemetry.counters
    layers = cfg.num_layers - cfg.first_dense_layers
    # 9 decode steps follow the prefill's token, in calls of 4
    assert c["moe_tokens_routed"] == 9
    # (a call still in flight when the request ended never lands)
    assert 9 <= c["moe_steps"] <= c["decode_steps"]
    assert c["moe_experts_touched"] == 9 * 3 * layers
    paths = eng.stats()["attention_paths"]
    assert paths["decode_multi"]["moe_experts"].startswith("grouped")
    assert paths["prefill_install"]["moe_experts"].startswith("grouped")
    assert "paged_attention" in paths["decode_multi"]
    # two at once: rows routed add up, experts touched at most add up
    eng2 = _engine(cfg)
    a, b = _req("a", range(5, 25), 9), _req("b", range(30, 41), 9)
    run_requests(eng2, [a, b])
    c2 = eng2.telemetry.counters
    assert c2["moe_tokens_routed"] == 16
    assert 8 * 3 * layers <= c2["moe_experts_touched"] <= 16 * 3 * layers
    assert a.on_output.tokens == r.on_output.tokens[:9]
    # a family that routes nothing brings no counts
    plain = _engine(tiny_config(dtype=jnp.float32, max_context_len=128))
    run_requests(plain, [_req("p", range(5, 25), 6)])
    assert plain.telemetry.counters["moe_steps"] == 0
