"""DeepSeek-V2-style MoE family (BASELINE config 4: expert-parallel decode).

Mixture-of-experts transformer with shared + routed experts and top-k
gating in the router form the configuration states (`_route`: softmax over
the chosen, DeepSeek-V2's softmax of all, DeepSeek-V3's sigmoid with a
choice-only bias and a routed scale). Where the expert stacks
`[L, E, D, F]` are whole on one device in the model's type, the live rows'
(token, expert) pairs are sorted by expert and each projection is ONE
grouped product over the experts that got a row (`_experts_grouped`,
ops/grouped_matmul.py): an expert nobody chose is not read. Under a mesh
(**expert parallelism over the `expert` axis**: the stacks sharded on E)
and with int8 stacks every token is scored against all experts with a
dense dispatch einsum and the gated combine contracts the expert
dimension — GSPMD turns that contraction into a psum over the expert axis
(no all-to-all token shuffling needed at serving batch sizes).
`experts_path` decides, from what the code sees while tracing.

Attention is **MLA (multi-head latent attention)** when
`kv_lora_rank > 0` (the DeepSeek-V2 design): the paged cache stores one
compressed latent `[kv_lora_rank ‖ rope_dim]` per token, the per-head K
up-projection is absorbed into the query, and the V up-projection is
applied after attention — so the framework's paged-attention ops run
unchanged over latents and the KV cache shrinks by the heads factor.
GQA+RoPE remains available for non-MLA configs. The first
`first_dense_layers` layers run a plain dense MLP (DeepSeek-V2 layer 0 in
real checkpoints, `modeling_deepseek.py` first_k_dense_replace); their
weights live in a separate `dense_mlp` subtree stacked over those layers
only, and the `moe` subtree stacks over the remaining layers — so real HF
checkpoints map position-for-position (models/loader.py
load_hf_deepseek_safetensors).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import attention as _attention
from ..ops.attention import (
    _pallas_interpret,
    apply_rope,
    decode_attention_step,
    note_path,
    paged_attention,
    prefill_attention,
    program_mesh,
    rms_norm,
    write_kv,
)
from ..ops.grouped_matmul import (
    dispatch_plan, grouped_matmul, grouped_path, row_tile)
from ..parallel.mesh import AXIS_EXPERT, AXIS_MODEL
from ..parallel.sharding import ShardingRules
from .base import ModelConfig, ModelFamily, block, register_model_family
from .quant import is_quantized, quantized_einsum
from .llama import _project_qkv, _unembed

Params = dict

MOE_STACKED_RULES = ShardingRules(rules=[
    # int8-quant `/scale` leaves FIRST (first match wins; see
    # LLAMA_STACKED_RULES): a scale has the kernel's dims minus the
    # contraction (-2), sharded with the kernel's OUTPUT dim.
    (r"(k_up|v_up)/kernel/scale", P(None, AXIS_MODEL, None)),
    (r"(kv_down|k_rope)/kernel/scale", P()),
    (r"experts/(gate_proj|up_proj)/kernel/scale",
     P(None, AXIS_EXPERT, AXIS_MODEL)),                # [L, E, F]
    (r"experts/down_proj/kernel/scale", P(None, AXIS_EXPERT, None)),
    (r"(shared|dense_mlp)/(gate_proj|up_proj)/kernel/scale",
     P(None, AXIS_MODEL)),
    (r"(shared|dense_mlp)/down_proj/kernel/scale", P()),
    (r"(q_proj|k_proj|v_proj)/kernel/scale", P(None, AXIS_MODEL)),
    (r"o_proj/kernel/scale", P()),
    (r"lm_head/kernel/scale", P(AXIS_MODEL)),
    # MLA tensors: heads on the model axis; shared latent projections
    # replicated.
    (r"(k_up|v_up)/kernel", P(None, AXIS_MODEL, None, None)),  # [L, H, ., .]
    (r"(kv_down|k_rope)/kernel", P()),
    (r"kv_norm/scale", P()),
    (r"experts/(gate_proj|up_proj)/kernel",
     P(None, AXIS_EXPERT, None, AXIS_MODEL)),          # [L, E, D, F]
    (r"experts/down_proj/kernel",
     P(None, AXIS_EXPERT, AXIS_MODEL, None)),          # [L, E, F, D]
    (r"shared/(gate_proj|up_proj)/kernel", P(None, None, AXIS_MODEL)),
    (r"shared/down_proj/kernel", P(None, AXIS_MODEL, None)),
    (r"dense_mlp/(gate_proj|up_proj)/kernel", P(None, None, AXIS_MODEL)),
    (r"dense_mlp/down_proj/kernel", P(None, AXIS_MODEL, None)),
    (r"router/(kernel|bias)", P()),
    (r"embed/embedding", P(AXIS_MODEL, None)),
    (r"(q_proj|k_proj|v_proj)/kernel", P(None, None, AXIS_MODEL)),
    (r"o_proj/kernel", P(None, AXIS_MODEL, None)),
    (r"lm_head/kernel", P(None, AXIS_MODEL)),
])


def deepseek_v2_lite_config() -> ModelConfig:
    """DeepSeek-V2-Lite with MLA: the paged cache stores one compressed
    latent (kv_lora_rank=512 + rope 64 = 576 dims) per token — advertised to
    the engine as num_kv_heads=1, head_dim=576."""
    return ModelConfig(name="deepseek_moe", vocab_size=102400,
                       hidden_size=2048, num_layers=27, num_heads=16,
                       num_kv_heads=1, head_dim=576, ffn_size=10944,
                       rope_theta=10000.0, max_context_len=32768,
                       kv_lora_rank=512, qk_nope_head_dim=128,
                       qk_rope_head_dim=64, v_head_dim=128,
                       num_experts=64, num_experts_per_token=6,
                       num_shared_experts=2, moe_ffn_size=1408,
                       first_dense_layers=1)


def bench_moe_config() -> ModelConfig:
    """~3.5B-total / ~0.9B-active MLA+MoE bench shape — V2-Lite's exact
    layer geometry (dataclasses.replace keeps them locked together) cut
    to 12 layers / 32 experts / 32k vocab so it fits one v5e chip
    weight-only int8 with a latent KV pool: the single-chip datum for
    BASELINE config 4 (expert-parallel decode measures relative to it)."""
    import dataclasses
    return dataclasses.replace(deepseek_v2_lite_config(),
                               vocab_size=32768, num_layers=12,
                               max_context_len=4096, num_experts=32)


def tiny_moe_config(**kw) -> ModelConfig:
    defaults = dict(name="deepseek_moe", vocab_size=512, hidden_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
                    ffn_size=256, max_context_len=512, num_experts=4,
                    num_experts_per_token=2, num_shared_experts=1,
                    moe_ffn_size=64, first_dense_layers=0)
    defaults.update(kw)
    return ModelConfig(**defaults)


def tiny_mla_config(**kw) -> ModelConfig:
    """Tiny MLA+MoE config: cache entry = 32 latent + 16 rope = 48 dims."""
    defaults = dict(name="deepseek_moe", vocab_size=512, hidden_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=1, head_dim=48,
                    ffn_size=256, max_context_len=512,
                    kv_lora_rank=32, qk_nope_head_dim=32,
                    qk_rope_head_dim=16, v_head_dim=32,
                    num_experts=4, num_experts_per_token=2,
                    num_shared_experts=1, moe_ffn_size=64,
                    first_dense_layers=0)
    defaults.update(kw)
    return ModelConfig(**defaults)


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    keys = jax.random.split(rng, 16)
    D, L, E = cfg.hidden_size, cfg.num_layers, cfg.num_experts
    Hq, Hkv = cfg.q_size, cfg.kv_size
    Fe = cfg.moe_ffn_size
    # num_shared_experts == 0 (mixtral): no shared branch at all.
    Fs = cfg.moe_ffn_size * cfg.num_shared_experts

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    if cfg.kv_lora_rank > 0:
        # MLA projections (DeepSeek-V2): shared compressed latent + a
        # decoupled rope key; per-head up-projections absorbed at decode.
        H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
        attn = {
            "q_proj": {"kernel": dense(keys[1], (L, D, H * (dn + dr)), D)},
            "kv_down": {"kernel": dense(keys[2], (L, D, dc), D)},
            "k_rope": {"kernel": dense(keys[3], (L, D, dr), D)},
            "kv_norm": {"scale": jnp.ones((L, dc), cfg.dtype)},
            "k_up": {"kernel": dense(keys[12], (L, H, dn, dc), dc)},
            "v_up": {"kernel": dense(keys[13], (L, H, dc, dv), dc)},
            "o_proj": {"kernel": dense(keys[4], (L, H * dv, D), H * dv)},
        }
    else:
        attn = {
            "q_proj": {"kernel": dense(keys[1], (L, D, Hq), D)},
            "k_proj": {"kernel": dense(keys[2], (L, D, Hkv), D)},
            "v_proj": {"kernel": dense(keys[3], (L, D, Hkv), D)},
            "o_proj": {"kernel": dense(keys[4], (L, Hq, D), Hq)},
        }

    Ld = cfg.first_dense_layers
    Lm = L - Ld                      # MoE layers (stacked separately)
    out = {
        "embed": {"embedding": dense(keys[0], (cfg.vocab_size, D), D)},
        "layers": {
            "input_norm": {"scale": jnp.ones((L, D), cfg.dtype)},
            **attn,
            "post_attn_norm": {"scale": jnp.ones((L, D), cfg.dtype)},
        },
        "moe": {
            "router": {"kernel": dense(keys[5], (Lm, D, E), D)
                       .astype(jnp.float32),
                       # DeepSeek-V3's `e_score_correction_bias`: drawn,
                       # not zero, so that a forward that left it out of
                       # the choice would compute another model
                       **({"bias": 0.1 * jax.random.normal(
                           keys[14], (Lm, E), jnp.float32)}
                          if cfg.router_bias else {})},
            "experts": {
                "gate_proj": {"kernel": dense(keys[6], (Lm, E, D, Fe), D)},
                "up_proj": {"kernel": dense(keys[7], (Lm, E, D, Fe), D)},
                "down_proj": {"kernel": dense(keys[8], (Lm, E, Fe, D), Fe)},
            },
            **({"shared": {
                "gate_proj": {"kernel": dense(keys[9], (Lm, D, Fs), D)},
                "up_proj": {"kernel": dense(keys[10], (Lm, D, Fs), D)},
                "down_proj": {"kernel": dense(keys[11], (Lm, Fs, D), Fs)},
            }} if cfg.num_shared_experts > 0 else {}),
        },
        "final_norm": {"scale": jnp.ones((D,), cfg.dtype)},
        "lm_head": {"kernel": dense(jax.random.fold_in(rng, 99),
                                    (D, cfg.vocab_size), D)},
    }
    if Ld > 0:
        F = cfg.ffn_size
        k2 = jax.random.split(jax.random.fold_in(rng, 55), 3)
        out["dense_mlp"] = {
            "gate_proj": {"kernel": dense(k2[0], (Ld, D, F), D)},
            "up_proj": {"kernel": dense(k2[1], (Ld, D, F), D)},
            "down_proj": {"kernel": dense(k2[2], (Ld, F, D), F)},
        }
    return out


def _route(router: Params, x2: jax.Array, cfg: ModelConfig):
    """x2 [T, D] -> (chosen experts [T, k] int32, their weights [T, k]
    float32), by the configuration's form. Scores in float32."""
    logits = x2.astype(jnp.float32) @ router["kernel"]          # [T, E]
    k = cfg.num_experts_per_token
    if cfg.router_scoring == "sigmoid":
        # DeepSeek-V3: a sigmoid of every logit; the bias enters the
        # choice and never the weights.
        scores = jax.nn.sigmoid(logits)
        choice = scores
        if "bias" in router:
            choice = scores + router["bias"].astype(jnp.float32)
        _, topi = jax.lax.top_k(choice, k)
        gates = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.router_norm_topk:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    else:
        topv, topi = jax.lax.top_k(logits, k)
        if cfg.router_norm_topk:
            # a softmax over the chosen logits = the softmax over all,
            # normalised over the chosen (Mixtral)
            gates = jax.nn.softmax(topv, axis=-1)
        else:
            gates = jnp.take_along_axis(
                jax.nn.softmax(logits, axis=-1), topi, axis=-1)
    if cfg.routed_scale != 1.0:
        gates = gates * cfg.routed_scale
    return topi, gates


def experts_path(cfg: ModelConfig, experts: Params) -> str:
    """How the routed experts' products run, from what the code sees while
    it traces; `/stats`.attention_paths["moe_experts"]. Grouped wherever
    the experts are whole on one device in the model's type; a mesh (the
    Pallas product cannot be partitioned by GSPMD, and an `expert` axis
    wants the contraction it has) and int8 stacks keep the dense
    contraction over every expert."""
    mesh = program_mesh()
    if mesh is not None:
        return f"dense (mesh {dict(mesh.shape)})"
    if is_quantized(experts["gate_proj"]["kernel"]):
        return "dense (int8 experts)"
    return grouped_path(_attention._backend(), _pallas_interpret())


def _experts_dense(experts: Params, x2, topi, gates, E: int):
    """Dense dispatch: all experts score all tokens; the combine
    contracts the (sharded) expert axis."""
    # Scatter the top-k gates back to a dense [T, E] map.
    dense_gates = jnp.zeros((x2.shape[0], E), jnp.float32).at[
        jnp.arange(x2.shape[0])[:, None], topi].set(gates)
    g = quantized_einsum("td,edf->etf", x2, experts["gate_proj"]["kernel"])
    u = quantized_einsum("td,edf->etf", x2, experts["up_proj"]["kernel"])
    h = jax.nn.silu(g) * u                                 # [E, T, Fe]
    eo = quantized_einsum("etf,efd->etd", h,
                          experts["down_proj"]["kernel"])
    return jnp.einsum("etd,te->td", eo.astype(jnp.float32), dense_gates)


def _experts_grouped(stacks: Params, layer: int, x2, plan, gates, live):
    """Grouped dispatch: the (token, expert) pairs sorted by expert as
    the layer's plan has them (ops/grouped_matmul.py `dispatch_plan`: a
    dead row's pairs sort behind every expert and belong to no group),
    one grouped product per projection over the experts that got a row,
    all three on the one plan, un-sorted and weighed."""
    T, k = gates.shape
    xs = x2[plan.order // k]                               # [T*k, D]
    mm = functools.partial(grouped_matmul, layer=layer, plan=plan,
                           backend=_attention._backend(),
                           interpret=_pallas_interpret())
    g = mm(xs, stacks["gate_proj"]["kernel"])
    u = mm(xs, stacks["up_proj"]["kernel"])
    eo = mm(jax.nn.silu(g) * u, stacks["down_proj"]["kernel"])
    # back to (token, choice) order; rows of no group are undefined
    eo = eo[plan.inverse].reshape(T, k, -1)
    eo = jnp.where(live[:, None, None], eo, 0)
    return jnp.einsum("tkd,tk->td", eo.astype(jnp.float32), gates)


def _moe_mlp(moe: Params, layer: int, x: jax.Array, cfg: ModelConfig,
             live: jax.Array | None = None):
    """The expert block of MoE layer `layer` of the stacked subtree `moe`.
    x: [..., D] -> ([..., D], counts). `live` [...] bool: the rows that
    hold a token of a running request; the others reach no expert (None:
    all). counts int32 [2]: the live rows, and the experts that got at
    least one of them. One function for prefill, verify and decode."""
    path = experts_path(cfg, moe["experts"])
    note_path("moe_experts", path)
    with block("moe"):
        orig_shape = x.shape
        x2 = x.reshape(-1, orig_shape[-1])                 # [T, D]
        live = (jnp.ones(x2.shape[:1], bool) if live is None
                else live.reshape(-1))
        lp = {name: jax.tree.map(lambda a: a[layer], moe[name])
              for name in moe if name != "experts"}
        E = cfg.num_experts
        with jax.named_scope("moe.route"):
            topi, gates = _route(lp["router"], x2, cfg)
            # every (token, expert) pair's expert, E for a dead row's
            pair_expert = jnp.where(live[:, None], topi, E).reshape(-1)
        with jax.named_scope("moe.experts"):
            if path.startswith("grouped"):
                # the layer's dispatch, once: the three products and the
                # router's count read the same plan
                with jax.named_scope("moe.plan"):
                    plan = dispatch_plan(pair_expert, E,
                                         row_tile(pair_expert.shape[0], E))
                sizes = plan.sizes
                routed = _experts_grouped(moe["experts"], layer, x2, plan,
                                          gates, live)
            else:
                sizes = jnp.zeros((E + 1,), jnp.int32).at[
                    pair_expert].add(1)[:E]
                routed = _experts_dense(
                    jax.tree.map(lambda a: a[layer], moe["experts"]), x2,
                    topi, gates, E)
        # how many rows are live, how many experts got a pair
        counts = jnp.stack([live.sum(),
                            (sizes > 0).sum()]).astype(jnp.int32)
        routed = routed.astype(x.dtype)

    if "shared" in lp:
        # the shared expert is a dense MLP over every row: the MLP block's
        with block("mlp"):
            sg = quantized_einsum("td,df->tf", x2,
                                  lp["shared"]["gate_proj"]["kernel"])
            su = quantized_einsum("td,df->tf", x2,
                                  lp["shared"]["up_proj"]["kernel"])
            routed = routed + quantized_einsum(
                "tf,fd->td", jax.nn.silu(sg) * su,
                lp["shared"]["down_proj"]["kernel"]).astype(routed.dtype)
    return routed.reshape(orig_shape), counts


def _rope_part(x: jax.Array, positions, cfg: ModelConfig) -> jax.Array:
    """Rotary embedding of q's or k's rope part [..., heads, dr]. With
    `rope_interleave` the part comes as adjacent pairs (2i, 2i+1): both q
    and k are de-interleaved and then rotated as halves, as the published
    code does; their products are those of rotating the pairs in place."""
    if cfg.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, cfg.rope_theta)


def _lanes(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[..., head_dim] -> [..., kv_head_dim], zeros behind: the latent as
    the pool holds it (576 at 640 lanes, `kv_held_dim`); scores and the
    sliced output are unchanged."""
    pad = cfg.kv_head_dim - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def _mla_attention(lp, cfg, h, mode, kv_pages, layer, page_table,
                   prefix_lens, seq_lens, positions, context_lens):
    """mode: "prefill" | "decode" | "dense" (dense = no paged cache at
    all — the embeddings path; nothing is written).

    MLA (DeepSeek-V2): the cache stores one [kv_lora_rank ‖ rope] latent
    per token; per-head K up-projection is absorbed into the query and the
    V up-projection applied after attention — so the existing paged
    attention ops run unchanged over latents (n_kv=1). The latent is
    written as K and as V.

    Returns (attn_out flattened [..., H*dv], kv_pages)."""
    H, dn = cfg.num_heads, cfg.qk_nope_head_dim
    dr, dc, dv = cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim

    # Latent + decoupled rope key (one shared "kv head").
    c = quantized_einsum("...d,dc->...c", h, lp["kv_down"]["kernel"])
    c = rms_norm(c, lp["kv_norm"]["scale"], cfg.rms_eps)
    k_r = quantized_einsum("...d,dr->...r", h, lp["k_rope"]["kernel"])
    k_r = _rope_part(k_r[..., None, :], positions, cfg)[..., 0, :]
    entry = _lanes(jnp.concatenate([c, k_r], axis=-1),
                   cfg)[..., None, :]                  # [..., 1, held]

    # Queries: nope part absorbed through the K up-projection.
    q = quantized_einsum("...d,df->...f", h, lp["q_proj"]["kernel"])
    # Pin q as the projection makes it. Without the barrier the TPU
    # compiler lays q out for the per-head product below (heads leading)
    # and carries that back through the projection to its weight: every
    # step it cut all layers' `q_proj` out of the stack into buffers of
    # their own and transposed them (0.4 GB of traffic a step at
    # kanana-2's widths, PERF.md §6 PR 37) where every other weight is
    # read in place.
    q = jax.lax.optimization_barrier(q)
    q = q.reshape(*q.shape[:-1], H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope_part(q_rope, positions, cfg)
    q_c = quantized_einsum("...hd,hdc->...hc", q_nope,
                           lp["k_up"]["kernel"])
    q_lat = _lanes(jnp.concatenate([q_c, q_rope], axis=-1),
                   cfg)                                # [..., H, held]
    # True scale is over the uncompressed per-head key width.
    scale = 1.0 / ((dn + dr) ** 0.5)

    if mode == "dense":
        attn = prefill_attention(q_lat, entry, entry, None, None, None,
                                 jnp.zeros(h.shape[:1], jnp.int32),
                                 seq_lens, scale=scale)
    elif mode == "prefill":
        kv_pages = write_kv(kv_pages, layer, entry, entry, page_table,
                            prefix_lens, seq_lens)
        attn = prefill_attention(q_lat, entry, entry, kv_pages, layer,
                                 page_table, prefix_lens, seq_lens,
                                 scale=scale)
    else:
        with jax.named_scope("mla.decode"):
            kv_pages = write_kv(kv_pages, layer, entry[:, None],
                                entry[:, None], page_table, positions,
                                jnp.ones_like(positions))
            attn = paged_attention(q_lat, kv_pages, layer, page_table,
                                   context_lens, scale=scale)
    # The weighted sum over [c ‖ k_rope] entries: keep the latent part,
    # apply the absorbed V up-projection per head.
    ctx = attn[..., :dc]                              # [..., H, dc]
    out = quantized_einsum("...hc,hcv->...hv", ctx,
                           lp["v_up"]["kernel"])
    return out.reshape(*out.shape[:-2], H * dv), kv_pages


def _dense_mlp(mp: Params, x: jax.Array) -> jax.Array:
    g = quantized_einsum("...d,df->...f", x, mp["gate_proj"]["kernel"])
    u = quantized_einsum("...d,df->...f", x, mp["up_proj"]["kernel"])
    return quantized_einsum("...f,fd->...d", jax.nn.silu(g) * u,
                            mp["down_proj"]["kernel"])


def _run_layers(params, cfg, x, kv_pages, mode, page_table, prefix_lens,
                seq_lens, positions, context_lens, live=None):
    """Unrolled layer loop over the one donated pool, written in place and
    read as `(pool, layer)` (see models/llama.py). `live`: the rows of `x`
    that reach the experts (`_moe_mlp`). Returns (x, kv_pages, counts):
    the live rows, and the experts that got one summed over the expert
    layers."""
    counts = jnp.zeros((2,), jnp.int32)
    use_mla = cfg.kv_lora_rank > 0
    Ld = cfg.first_dense_layers
    dense = kv_pages is None            # embeddings: no cache at all
    for l in range(cfg.num_layers):
        with block("attn"):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = rms_norm(x, lp["input_norm"]["scale"], cfg.rms_eps)
            if use_mla:
                attn, kv_pages = _mla_attention(
                    lp, cfg, h, "dense" if dense else mode, kv_pages, l,
                    page_table, prefix_lens, seq_lens, positions,
                    context_lens)
            else:
                q, k, v = _project_qkv(lp, h, cfg, positions)
                if dense:
                    attn = prefill_attention(
                        q, k, v, None, None, None,
                        jnp.zeros(x.shape[:1], jnp.int32), seq_lens)
                elif mode == "prefill":
                    kv_pages = write_kv(kv_pages, l, k, v, page_table,
                                        prefix_lens, seq_lens)
                    attn = prefill_attention(q, k, v, kv_pages, l,
                                             page_table, prefix_lens,
                                             seq_lens)
                else:
                    attn, kv_pages = decode_attention_step(
                        q, k, v, kv_pages, l, page_table, context_lens)
                attn = attn.reshape(*attn.shape[:-2], cfg.q_size)
            x = x + quantized_einsum("...f,fd->...d", attn,
                                     lp["o_proj"]["kernel"])
        if l < Ld:
            with block("mlp"):
                h2 = rms_norm(x, lp["post_attn_norm"]["scale"], cfg.rms_eps)
                x = x + _dense_mlp(
                    jax.tree.map(lambda a, _l=l: a[_l],
                                 params["dense_mlp"]), h2)
        else:
            # the norm feeds the router, the experts and the shared
            # expert; `_moe_mlp` names its own blocks
            with block("moe"):
                h2 = rms_norm(x, lp["post_attn_norm"]["scale"], cfg.rms_eps)
            y, c = _moe_mlp(params["moe"], l - Ld, h2, cfg, live)
            with block("moe"):
                x = x + y
                counts = jnp.stack([c[0], counts[1] + c[1]])
    return x, kv_pages, counts


def _suffix_live(tokens, seq_lens):
    """[B, S] bool: the rows of a prefill or verify block that hold a
    token (the rest is bucket padding and reaches no expert)."""
    return jnp.arange(tokens.shape[1])[None, :] < seq_lens[:, None]


def prefill_forward(params, cfg, tokens, positions, kv_pages, page_table,
                    prefix_lens, seq_lens):
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    x, kv_pages, _ = _run_layers(
        params, cfg, x, kv_pages, "prefill", page_table, prefix_lens,
        seq_lens, positions, None, live=_suffix_live(tokens, seq_lens))
    with block("head"):
        idx = jnp.maximum(seq_lens - 1, 0)
        last = x[jnp.arange(x.shape[0]), idx]
    return _unembed(params, cfg, last), kv_pages


def decode_forward_routed(params, cfg, tokens, positions, kv_pages,
                          page_table, context_lens, *, live):
    """One decode step in which only the `live` rows ([B] bool; None: all)
    reach the experts. Returns (logits, kv_pages, counts): `_run_layers`'
    counts of this step (`ModelFamily.decode_forward_routed`)."""
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    x, kv_pages, counts = _run_layers(
        params, cfg, x, kv_pages, "decode", page_table, None, None,
        positions, context_lens, live=live)
    return _unembed(params, cfg, x), kv_pages, counts


def decode_forward(params, cfg, tokens, positions, kv_pages, page_table,
                   context_lens):
    return decode_forward_routed(params, cfg, tokens, positions, kv_pages,
                                 page_table, context_lens, live=None)[:2]


def verify_forward(params, cfg, tokens, positions, kv_pages, page_table,
                   prefix_lens, seq_lens):
    """Speculative verify for the MoE family: the prefill body already
    handles short multi-token blocks against the paged cache (MLA or GQA);
    this returns per-position logits [B, S, V]."""
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    x, kv_pages, _ = _run_layers(
        params, cfg, x, kv_pages, "prefill", page_table, prefix_lens,
        seq_lens, positions, None, live=_suffix_live(tokens, seq_lens))
    return _unembed(params, cfg, x), kv_pages


def embed_forward(params, cfg, tokens, seq_lens):
    """Text embeddings (mean-pooled final hidden states): fully dense
    causal forward — no page pool is allocated or written."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                 (B, S))
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    x, _, _ = _run_layers(params, cfg, x, None, "prefill", None,
                          jnp.zeros((B,), jnp.int32), seq_lens, positions,
                          None, live=_suffix_live(tokens, seq_lens))
    with block("head"):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        mask = (jnp.arange(S)[None, :] < seq_lens[:, None])[..., None]
        summed = jnp.sum(jnp.where(mask, x.astype(jnp.float32), 0.0),
                         axis=1)
        return summed / jnp.maximum(seq_lens[:, None], 1)


register_model_family(ModelFamily(
    name="deepseek_moe",
    init_params=init_params,
    prefill_forward=prefill_forward,
    decode_forward=decode_forward,
    sharding_rules=MOE_STACKED_RULES,
    verify_forward=verify_forward,
    embed_forward=embed_forward,
    supports_int8=True,
    decode_forward_routed=decode_forward_routed,
))
