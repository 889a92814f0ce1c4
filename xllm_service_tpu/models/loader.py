"""Checkpoint loading: HuggingFace safetensors → stacked param pytrees.

The engine serves random-init weights by default (benchmarks); this module
loads real checkpoints. HF llama/qwen2-style weight names are mapped onto
the framework's stacked-layer pytree (leading L dim, see models/llama.py)
and optionally sharded straight onto the mesh (per-tensor `device_put`
with the family's GSPMD rules — no full-model host copy per device).

Orbax round-trip (`save_params`/`load_params`) covers framework-native
checkpoints (engine restarts, converted models).
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .base import ModelConfig
from ..utils import get_logger

logger = get_logger(__name__)

Params = dict

# HF per-layer name -> (our path, transpose?) for llama/qwen2 families.
# HF Linear stores [out, in]; our kernels are [in, out] -> transpose.
_HF_LAYER_MAP = {
    "input_layernorm.weight": ("input_norm/scale", False),
    "self_attn.q_proj.weight": ("q_proj/kernel", True),
    "self_attn.k_proj.weight": ("k_proj/kernel", True),
    "self_attn.v_proj.weight": ("v_proj/kernel", True),
    "self_attn.o_proj.weight": ("o_proj/kernel", True),
    "self_attn.q_proj.bias": ("q_proj/bias", False),
    "self_attn.k_proj.bias": ("k_proj/bias", False),
    "self_attn.v_proj.bias": ("v_proj/bias", False),
    "post_attention_layernorm.weight": ("post_attn_norm/scale", False),
    # gemma-2 sandwich norms (absent from other families' checkpoints).
    "pre_feedforward_layernorm.weight": ("pre_ffw_norm/scale", False),
    "post_feedforward_layernorm.weight": ("post_ffw_norm/scale", False),
    "mlp.gate_proj.weight": ("gate_proj/kernel", True),
    "mlp.up_proj.weight": ("up_proj/kernel", True),
    "mlp.down_proj.weight": ("down_proj/kernel", True),
}
_HF_TOP_MAP = {
    "model.embed_tokens.weight": ("embed/embedding", False),
    "model.norm.weight": ("final_norm/scale", False),
    "lm_head.weight": ("lm_head/kernel", True),
}


def _set_path(tree: dict, path: str, value) -> None:
    parts = path.split("/")
    node = tree
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value


def load_hf_llama_safetensors(ckpt_dir: str | Path, cfg: ModelConfig,
                              mesh=None, rules=None) -> Params:
    """Load an HF llama/qwen2 checkpoint directory (*.safetensors shards)
    into the stacked pytree. Missing lm_head falls back to tied embeddings
    semantics only if cfg.tie_embeddings is set."""
    from safetensors import safe_open

    ckpt_dir = Path(ckpt_dir)
    files = sorted(ckpt_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors in {ckpt_dir}")

    L = cfg.num_layers
    # Collect per-layer tensors then stack along L.
    layer_acc: dict[str, list[Optional[np.ndarray]]] = {}
    tree: Params = {}
    seen = set()

    def place(name: str, tensor: np.ndarray) -> None:
        if name in _HF_TOP_MAP:
            path, transpose = _HF_TOP_MAP[name]
            _set_path(tree, path, np.ascontiguousarray(
                tensor.T if transpose else tensor))
            seen.add(name)
            return
        if not name.startswith("model.layers."):
            logger.warning("unmapped checkpoint tensor: %s", name)
            return
        rest = name[len("model.layers."):]
        idx_str, _, leaf = rest.partition(".")
        if leaf not in _HF_LAYER_MAP:
            logger.warning("unmapped layer tensor: %s", name)
            return
        idx = int(idx_str)
        path, transpose = _HF_LAYER_MAP[leaf]
        layer_acc.setdefault(path, [None] * L)[idx] = np.ascontiguousarray(
            tensor.T if transpose else tensor)
        seen.add(name)

    for f in files:
        with safe_open(str(f), framework="numpy") as sf:
            for name in sf.keys():
                place(name, sf.get_tensor(name))

    for path, tensors in layer_acc.items():
        missing = [i for i, t in enumerate(tensors) if t is None]
        if missing:
            raise ValueError(f"checkpoint missing layers {missing} for {path}")
        _set_path(tree, f"layers/{path}", np.stack(tensors))

    if "lm_head" not in tree and not cfg.tie_embeddings:
        # Tied checkpoints ship no lm_head; honor tying implicitly.
        logger.info("no lm_head in checkpoint; tying to embeddings")
        tree["lm_head"] = {"kernel": np.ascontiguousarray(
            tree["embed"]["embedding"].T)}

    return _finalize(tree, cfg, mesh, rules)


def _finalize(tree: Params, cfg: ModelConfig, mesh, rules) -> Params:
    """Cast to model dtype and (optionally) shard leaf-by-leaf."""
    if mesh is not None and rules is not None:
        from jax.sharding import NamedSharding

        from ..parallel.sharding import tree_specs

        specs = tree_specs(tree, rules)

        def put(leaf, spec):
            return jax.device_put(jnp.asarray(leaf, cfg.dtype),
                                  NamedSharding(mesh, spec))

        return jax.tree.map(put, tree, specs)
    return jax.tree.map(lambda a: jnp.asarray(a, cfg.dtype), tree)


# ------------------------------------------------------- MoE checkpoints ----
def load_hf_deepseek_safetensors(ckpt_dir: str | Path, cfg: ModelConfig,
                                 mesh=None, rules=None) -> Params:
    """HF DeepSeek-V2 checkpoint -> the MoE family's stacked pytree
    (models/deepseek_moe.py): MLA projections are split/reshaped
    (`kv_a_proj_with_mqa` -> kv_down‖k_rope; `kv_b_proj` -> absorbed
    k_up/v_up), expert weights stack to [Lm, E, ...], layer 0's dense MLP
    (first_k_dense_replace) lands in the `dense_mlp` subtree."""
    from safetensors import safe_open

    ckpt_dir = Path(ckpt_dir)
    files = sorted(ckpt_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors in {ckpt_dir}")

    L, Ld = cfg.num_layers, cfg.first_dense_layers
    Lm, E = L - Ld, cfg.num_experts
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    mla = dc > 0

    tree: Params = {}
    # path -> [L]- or [Lm]- or [Lm][E]-indexed accumulators.
    lay: dict[str, list] = {}
    moe: dict[str, list] = {}
    dense_acc: dict[str, list] = {}
    exp: dict[str, list] = {}

    def acc(store: dict, path: str, n: int, idx: int, val) -> None:
        store.setdefault(path, [None] * n)[idx] = np.ascontiguousarray(val)

    def place(name: str, t: np.ndarray) -> None:
        if name in _HF_TOP_MAP:
            path, tr = _HF_TOP_MAP[name]
            _set_path(tree, path, np.ascontiguousarray(t.T if tr else t))
            return
        if not name.startswith("model.layers."):
            logger.warning("unmapped checkpoint tensor: %s", name)
            return
        idx_str, _, leaf = name[len("model.layers."):].partition(".")
        li = int(idx_str)
        mi = li - Ld                       # index into the MoE stack
        if leaf == "input_layernorm.weight":
            acc(lay, "input_norm/scale", L, li, t)
        elif leaf == "post_attention_layernorm.weight":
            acc(lay, "post_attn_norm/scale", L, li, t)
        elif leaf == "self_attn.o_proj.weight":
            acc(lay, "o_proj/kernel", L, li, t.T)
        elif leaf == "self_attn.q_proj.weight":
            acc(lay, "q_proj/kernel", L, li, t.T)
        elif mla and leaf == "self_attn.kv_a_proj_with_mqa.weight":
            # [dc+dr, D]: latent rows then the decoupled rope key rows.
            acc(lay, "kv_down/kernel", L, li, t[:dc].T)
            acc(lay, "k_rope/kernel", L, li, t[dc:dc + dr].T)
        elif mla and leaf == "self_attn.kv_a_layernorm.weight":
            acc(lay, "kv_norm/scale", L, li, t)
        elif mla and leaf == "self_attn.kv_b_proj.weight":
            # [H*(dn+dv), dc] -> per-head K-up [H, dn, dc] and V-up
            # [H, dc, dv] (absorbed at decode, see _mla_attention).
            kb = t.reshape(H, dn + dv, dc)
            acc(lay, "k_up/kernel", L, li, kb[:, :dn, :])
            acc(lay, "v_up/kernel", L, li,
                kb[:, dn:, :].transpose(0, 2, 1))
        elif not mla and leaf == "self_attn.k_proj.weight":
            acc(lay, "k_proj/kernel", L, li, t.T)
        elif not mla and leaf == "self_attn.v_proj.weight":
            acc(lay, "v_proj/kernel", L, li, t.T)
        elif leaf == "mlp.gate.weight":
            acc(moe, "router/kernel", Lm, mi, t.T.astype(np.float32))
        elif leaf == "mlp.gate.e_score_correction_bias":
            # DeepSeek-V3: enters the choice of experts only.
            acc(moe, "router/bias", Lm, mi, t.astype(np.float32))
        elif leaf.startswith("mlp.experts."):
            e_str, _, w = leaf[len("mlp.experts."):].partition(".")
            ei = int(e_str)
            proj = w.split(".")[0]         # gate_proj|up_proj|down_proj
            exp.setdefault(f"experts/{proj}/kernel",
                           [[None] * E for _ in range(Lm)])[mi][ei] = \
                np.ascontiguousarray(t.T)
        elif leaf.startswith("mlp.shared_experts."):
            proj = leaf[len("mlp.shared_experts."):].split(".")[0]
            acc(moe, f"shared/{proj}/kernel", Lm, mi, t.T)
        elif li < Ld and leaf.startswith("mlp."):
            proj = leaf[len("mlp."):].split(".")[0]
            acc(dense_acc, f"{proj}/kernel", Ld, li, t.T)
        else:
            logger.warning("unmapped layer tensor: %s", name)

    for f in files:
        with safe_open(str(f), framework="numpy") as sf:
            for name in sf.keys():
                place(name, sf.get_tensor(name))

    def stack_into(prefix: str, store: dict) -> None:
        for path, tensors in store.items():
            missing = [i for i, x in enumerate(tensors) if x is None]
            if missing:
                raise ValueError(
                    f"checkpoint missing entries {missing} for {path}")
            _set_path(tree, f"{prefix}/{path}", np.stack(tensors))

    stack_into("layers", lay)
    stack_into("moe", moe)
    if Ld:
        stack_into("dense_mlp", dense_acc)
    for path, per_layer in exp.items():
        stacked = []
        for mi, row in enumerate(per_layer):
            missing = [e for e, x in enumerate(row) if x is None]
            if missing:
                raise ValueError(f"moe layer {mi} missing experts "
                                 f"{missing} for {path}")
            stacked.append(np.stack(row))
        _set_path(tree, f"moe/{path}", np.stack(stacked))

    if "lm_head" not in tree:
        tree["lm_head"] = {"kernel": np.ascontiguousarray(
            tree["embed"]["embedding"].T)}
    return _finalize(tree, cfg, mesh, rules)


def load_hf_mixtral_safetensors(ckpt_dir: str | Path, cfg: ModelConfig,
                                mesh=None, rules=None) -> Params:
    """HF Mixtral checkpoint -> the MoE family pytree: block_sparse_moe
    gate/w1/w3/w2 map to router/gate_proj/up_proj/down_proj stacked over
    [L, E, ...] (no shared experts, no dense layers, GQA attention)."""
    from safetensors import safe_open

    ckpt_dir = Path(ckpt_dir)
    files = sorted(ckpt_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors in {ckpt_dir}")

    L, E = cfg.num_layers, cfg.num_experts
    tree: Params = {}
    lay: dict[str, list] = {}
    moe: dict[str, list] = {}
    exp: dict[str, list] = {}
    _W = {"w1": "gate_proj", "w3": "up_proj", "w2": "down_proj"}

    def place(name: str, t: np.ndarray) -> None:
        if name in _HF_TOP_MAP:
            path, tr = _HF_TOP_MAP[name]
            _set_path(tree, path, np.ascontiguousarray(t.T if tr else t))
            return
        if not name.startswith("model.layers."):
            logger.warning("unmapped checkpoint tensor: %s", name)
            return
        idx_str, _, leaf = name[len("model.layers."):].partition(".")
        li = int(idx_str)
        if leaf in _HF_LAYER_MAP:          # attention + norms
            path, tr = _HF_LAYER_MAP[leaf]
            lay.setdefault(path, [None] * L)[li] = np.ascontiguousarray(
                t.T if tr else t)
        elif leaf == "block_sparse_moe.gate.weight":
            moe.setdefault("router/kernel", [None] * L)[li] = \
                np.ascontiguousarray(t.T.astype(np.float32))
        elif leaf.startswith("block_sparse_moe.experts."):
            e_str, _, w = leaf[len("block_sparse_moe.experts."):] \
                .partition(".")
            proj = _W.get(w.split(".")[0])
            if proj is None:
                logger.warning("unmapped expert tensor: %s", name)
                return
            exp.setdefault(f"experts/{proj}/kernel",
                           [[None] * E for _ in range(L)])[li][int(e_str)] \
                = np.ascontiguousarray(t.T)
        else:
            logger.warning("unmapped layer tensor: %s", name)

    for f in files:
        with safe_open(str(f), framework="numpy") as sf:
            for name in sf.keys():
                place(name, sf.get_tensor(name))

    def checked_stack(prefix: str, store: dict) -> None:
        for path, tensors in store.items():
            missing = [i for i, x in enumerate(tensors) if x is None]
            if missing:
                raise ValueError(
                    f"checkpoint missing entries {missing} for {path}")
            _set_path(tree, f"{prefix}/{path}", np.stack(tensors))

    checked_stack("layers", lay)
    checked_stack("moe", moe)
    for path, per_layer in exp.items():
        stacked = []
        for li, row in enumerate(per_layer):
            missing = [e for e, x in enumerate(row) if x is None]
            if missing:
                raise ValueError(f"moe layer {li} missing experts "
                                 f"{missing} for {path}")
            stacked.append(np.stack(row))
        _set_path(tree, f"moe/{path}", np.stack(stacked))
    if "lm_head" not in tree:
        tree["lm_head"] = {"kernel": np.ascontiguousarray(
            tree["embed"]["embedding"].T)}
    return _finalize(tree, cfg, mesh, rules)


# ------------------------------------------------------ VL checkpoints ----
# visual.blocks.{i}.<leaf> -> (our vision/layers path, transpose?)
_HF_VISION_BLOCK_MAP = {
    "norm1.weight": ("norm1/scale", False),
    "norm1.bias": ("norm1/bias", False),
    "attn.qkv.weight": ("qkv/kernel", True),
    "attn.qkv.bias": ("qkv/bias", False),
    "attn.proj.weight": ("proj/kernel", True),
    "attn.proj.bias": ("proj/bias", False),
    "norm2.weight": ("norm2/scale", False),
    "norm2.bias": ("norm2/bias", False),
    "mlp.fc1.weight": ("fc1/kernel", True),
    "mlp.fc1.bias": ("fc1/bias", False),
    "mlp.fc2.weight": ("fc2/kernel", True),
    "mlp.fc2.bias": ("fc2/bias", False),
}
_HF_VISION_TOP_MAP = {
    "visual.merger.ln_q.weight": ("vision/merger/ln_q/scale", False),
    "visual.merger.ln_q.bias": ("vision/merger/ln_q/bias", False),
    "visual.merger.mlp.0.weight": ("vision/merger/fc1/kernel", True),
    "visual.merger.mlp.0.bias": ("vision/merger/fc1/bias", False),
    "visual.merger.mlp.2.weight": ("vision/merger/fc2/kernel", True),
    "visual.merger.mlp.2.bias": ("vision/merger/fc2/bias", False),
}


def load_hf_qwen2_vl_safetensors(ckpt_dir: str | Path, cfg: ModelConfig,
                                 mesh=None, rules=None) -> Params:
    """HF Qwen2-VL checkpoint -> qwen2_vl pytree: the LM maps like
    qwen2 (qkv-bias llama) and the `visual.*` tower onto
    models/qwen2_vl.py's encoder — the Conv3d patch embed flattens to the
    (c, t, ph, pw) linear the encoder applies, blocks map 1:1, and the
    PatchMerger's ln_q/mlp land under vision/merger."""
    from safetensors import safe_open

    ckpt_dir = Path(ckpt_dir)
    files = sorted(ckpt_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors in {ckpt_dir}")

    v = cfg.vision
    assert v is not None
    L, Lv = cfg.num_layers, v.num_layers
    tree: Params = {}
    lay: dict[str, list] = {}
    vlay: dict[str, list] = {}

    def place(name: str, t: np.ndarray) -> None:
        if name in _HF_TOP_MAP:
            path, tr = _HF_TOP_MAP[name]
            _set_path(tree, path, np.ascontiguousarray(t.T if tr else t))
            return
        if name in _HF_VISION_TOP_MAP:
            path, tr = _HF_VISION_TOP_MAP[name]
            _set_path(tree, path, np.ascontiguousarray(t.T if tr else t))
            return
        if name == "visual.patch_embed.proj.weight":
            # Conv3d [Dv, 3, tps, p, p] -> [3*tps*p*p, Dv] linear.
            _set_path(tree, "vision/patch_embed/kernel",
                      np.ascontiguousarray(t.reshape(t.shape[0], -1).T))
            return
        if name.startswith("visual.blocks."):
            idx_str, _, leaf = name[len("visual.blocks."):].partition(".")
            if leaf not in _HF_VISION_BLOCK_MAP:
                logger.warning("unmapped vision tensor: %s", name)
                return
            path, tr = _HF_VISION_BLOCK_MAP[leaf]
            vlay.setdefault(path, [None] * Lv)[int(idx_str)] = \
                np.ascontiguousarray(t.T if tr else t)
            return
        if name.startswith("model.layers."):
            idx_str, _, leaf = name[len("model.layers."):].partition(".")
            if leaf not in _HF_LAYER_MAP:
                logger.warning("unmapped layer tensor: %s", name)
                return
            path, tr = _HF_LAYER_MAP[leaf]
            lay.setdefault(path, [None] * L)[int(idx_str)] = \
                np.ascontiguousarray(t.T if tr else t)
            return
        logger.warning("unmapped checkpoint tensor: %s", name)

    for f in files:
        with safe_open(str(f), framework="numpy") as sf:
            for name in sf.keys():
                place(name, sf.get_tensor(name))

    for store, prefix in ((lay, "layers"), (vlay, "vision/layers")):
        for path, tensors in store.items():
            missing = [i for i, x in enumerate(tensors) if x is None]
            if missing:
                raise ValueError(
                    f"checkpoint missing entries {missing} for {path}")
            _set_path(tree, f"{prefix}/{path}", np.stack(tensors))

    if "lm_head" not in tree and not cfg.tie_embeddings:
        logger.info("no lm_head in checkpoint; tying to embeddings")
        tree["lm_head"] = {"kernel": np.ascontiguousarray(
            tree["embed"]["embedding"].T)}
    return _finalize(tree, cfg, mesh, rules)


# ---------------------------------------------------------------- orbax ----
def save_params(params: Params, path: str | Path) -> None:
    """Framework-native checkpoint (orbax)."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(Path(path).absolute(), params, force=True)


def load_params(path: str | Path, cfg: ModelConfig,
                mesh=None, rules=None) -> Params:
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        params = ckptr.restore(Path(path).absolute())
    return _finalize(params, cfg, mesh, rules)
