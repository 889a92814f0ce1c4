"""The TPU's own compiler, asked from a machine with no TPU.

`jax.experimental.topologies` describes a `v5e:2x2` host that is not
attached; compiling against its devices runs Mosaic and the XLA TPU
backend for real, so what the chip would refuse is refused here — which
interpret mode and the CPU backend never see (tensor-parallel serving and
the context-parallel kernel both passed every CPU test and could not
compile). Arms and helpers live in `benchmarks/compile_gate.py`.

The topology is described inside a module-scoped fixture, never at import:
the TPU library belongs to one process, and every xdist worker imports
this file. Keep these tests in this one file for the same reason.
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

_spec = importlib.util.spec_from_file_location(
    "compile_gate", REPO / "benchmarks" / "compile_gate.py")
gate = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gate)


@pytest.fixture(scope="module")
def chip():
    """The described devices, with the persistent compile cache off
    around the compiles (an entry written for a described chip cannot be
    read back without one, and every later run would warn)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            devices = gate.describe_devices()
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no {gate.TOPOLOGY} topology can be described "
                        f"here: {e}")
        yield devices
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.mark.parametrize("arm", [
    "paged_b16", "paged_b64", "gemma2_softcap", "gemma2_window",
    "paged_qwen25_7b", "paged_qwen25_3b", "paged_granite_h_micro",
    "ssm_update_granite_h_micro", "retention_update_brumby",
    "retention_prefill_brumby", "page_gather_l32",
    "page_scatter_l32", "cp_partial_stats", "paged_shard_map_tp4",
    "prefill_qwen25_7b", "prefill_qwen25_3b", "prefill_granite_h_micro",
    "prefill_kanana2_latent", "prefill_gemma2"])
def test_kernel_compiles_for_v5e(chip, arm):
    """Each served-path Pallas kernel at Llama-3-8B head shapes (the
    decode kernel also at the benchmark's two configurations' heads, batch
    and table width: chunks of 16 pages with the run copy in them; the
    prefill kernel at the four configurations' heads, table and top
    bucket, kanana-2's 32 heads over one latent head of 640 lanes among
    them) is accepted by Mosaic and stays a kernel in the compiled
    program."""
    compiled = dict(gate.kernel_arms(chip))[arm]()
    assert compiled.as_text().count("tpu_custom_call") >= 1


def _two_layer_cfg():
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.models.base import llama3_8b_config

    mcfg = dataclasses.replace(llama3_8b_config(), num_layers=2)
    return EngineConfig(model=mcfg, model_family="llama", num_pages=1024,
                        max_batch_size=16, max_seq_len=1024,
                        prefill_buckets=(128, 1024), decode_horizon=8)


def test_decode_step_full_width_one_chip(chip):
    """The engine's own decode program (`_build_programs`), Llama-3-8B
    widths, 2 layers, horizon 8: compiles for one chip with the kernel in
    it — one custom call per layer — and says so in the path record."""
    out = gate.compile_engine_programs(_two_layer_cfg(), device=chip[0],
                                       horizons=(8,), buckets=())
    prog = out["decode_multi_h8"]
    assert prog["tpu_custom_calls"] == 2
    assert prog["fits_hbm"]
    assert out["attention_paths"]["decode_multi"] == {
        "paged_attention": "pallas"}


def test_prefill_holds_one_attention_kernel_call_a_layer(chip):
    """`prefill_install` at Llama-3-8B widths, 2 layers: ONE kernel call a
    layer whatever the prefix (the XLA form compiles the attention up to
    five times a layer: a `cond` over a `switch` of span-bucketed
    gathers), and the record names the path."""
    out = gate.compile_engine_programs(_two_layer_cfg(), device=chip[0],
                                       horizons=(), buckets=(1024,))
    prog = out["prefill_install_nc_s1024"]
    assert prog["tpu_custom_calls"] == 2 and prog["whiles"] == 0
    assert prog["fits_hbm"]
    assert out["attention_paths"]["prefill_install"] == {
        "prefill_attention": "pallas"}


def test_prefill_kernel_runs_per_head_shard_tp4(chip):
    """The same program over the 4-device model mesh: the prefill kernel
    under shard_map on its device's heads, as the decode kernel."""
    out = gate.compile_engine_programs(
        _two_layer_cfg(), mesh=gate.model_mesh(chip, 4), horizons=(),
        buckets=(128,))
    assert out["prefill_install_nc_s128"]["tpu_custom_calls"] == 2
    assert out["attention_paths"]["prefill_install"] == {
        "prefill_attention": "pallas (shard_map model=4)"}


def test_decode_step_holds_no_second_pool(chip):
    """`qwen25-7b-int8` as the benchmark serves it (full depth, 2048
    pages, B 32, horizon 8): the decode program's temporaries are a small
    part of the 1.75 GiB pool. They were 1.84 GiB, the pool over again,
    while each layer was sliced out for the kernel and stacked back."""
    from chipbench.engine_setup import build_engine_config

    name = "qwen25-7b-int8"
    ecfg, _ = build_engine_config(REPO / "chipbench" / "configs" / name, 0,
                                  name)
    out = gate.compile_engine_programs(ecfg, device=chip[0],
                                       horizons=(ecfg.decode_horizon,),
                                       buckets=())
    prog = out[f"decode_multi_h{ecfg.decode_horizon}"]
    m = ecfg.model
    pool_gib = (m.num_layers * 2 * ecfg.num_pages * m.num_kv_heads
                * ecfg.page_size * m.head_dim * 2) / 2 ** 30
    assert prog["tpu_custom_calls"] == m.num_layers
    assert prog["temp_gib"] < 0.25 * pool_gib


def test_state_space_decode_step_keeps_its_state_in_place(chip):
    """`granite-4.0-h-micro` as the benchmark serves it (40 layers, B 32,
    horizon 8): one kernel call a layer (36 state updates, 4 paged
    attentions), both on `pallas`, and temporaries that are a small part
    of the 2.25 GiB of per-slot state: no copy of the state, of the pool
    (4 planes, keys held at 128 lanes) or of a weight stack."""
    from chipbench.engine_setup import build_engine_config

    name = "granite-4.0-h-micro"
    ecfg, _ = build_engine_config(REPO / "chipbench" / "configs" / name, 0,
                                  name)
    m = ecfg.model
    assert (m.kv_layers, m.kv_head_dim, m.num_layers) == (4, 128, 40)
    out = gate.compile_engine_programs(ecfg, device=chip[0],
                                       horizons=(ecfg.decode_horizon,),
                                       buckets=())
    prog = out[f"decode_multi_h{ecfg.decode_horizon}"]
    assert prog["tpu_custom_calls"] == m.num_layers
    assert prog["fits_hbm"] and prog["temp_gib"] < 0.25
    assert out["attention_paths"]["decode_multi"] == {
        "paged_attention": "pallas", "ssm_update": "pallas"}


def test_attention_free_decode_step_keeps_its_state_in_place(chip):
    """`brumby-14b-base` as the benchmark serves it (8 layers, B 12,
    horizon 8): one kernel call a layer, the retention update on `pallas`
    and neither attention kernel anywhere, a pool of no bytes, and
    temporaries that are a sixth of the 3.3 GB of per-slot state: no copy
    of the state or of a weight stack."""
    from chipbench.engine_setup import build_engine_config

    name = "brumby-14b-base"
    ecfg, _ = build_engine_config(REPO / "chipbench" / "configs" / name, 0,
                                  name)
    m = ecfg.model
    assert (m.kv_layers, m.num_layers, ecfg.prefill_chunk_tokens) == (
        0, 8, 1024)
    out = gate.compile_engine_programs(ecfg, device=chip[0],
                                       horizons=(ecfg.decode_horizon,),
                                       buckets=())
    prog = out[f"decode_multi_h{ecfg.decode_horizon}"]
    assert prog["tpu_custom_calls"] == m.num_layers
    assert prog["fits_hbm"] and prog["temp_gib"] < 0.7
    assert 10.8 < prog["argument_gib"] < 11.0
    assert out["attention_paths"]["decode_multi"] == {
        "retention_update": "pallas"}


def test_decode_step_full_width_tp4(chip):
    """The same program partitioned over the 4-device model mesh: GSPMD
    cannot partition a Mosaic kernel, so it sits under shard_map on a
    head-sharded pool (KV_PAGES_SPEC) beside the row-parallel matmuls'
    all-reduces."""
    out = gate.compile_engine_programs(
        _two_layer_cfg(), mesh=gate.model_mesh(chip, 4), horizons=(8,),
        buckets=())
    prog = out["decode_multi_h8"]
    assert prog["tpu_custom_calls"] == 2
    assert prog["all_reduces"] >= 2 * 2      # o_proj + down_proj per layer
    assert out["attention_paths"]["decode_multi"] == {
        "paged_attention": "pallas (shard_map model=4)"}


def test_instruction_counts_read_the_ops_a_program_executes():
    """`describe_compiled`'s op counts: the entry, loop bodies and called
    computations count; a fusion's body and a sort's comparator do not.
    (No compile: a hand-written module.)"""
    text = """HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %copy.9 = f32[8]{0} copy(%p)
}

%compare.2 (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%body.3 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.4 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %sort.5 = f32[8]{0} sort(%fusion.4), dimensions={0}, to_apply=%compare.2
  %copy.6 = f32[8]{0} copy(%sort.5)
  ROOT %tuple = (s32[], f32[8]{0}) tuple(%i, %copy.6)
}

%cond.7 (t: (s32[], f32[8])) -> pred[] {
  %t = (s32[], f32[8]{0}) parameter(0)
  ROOT %done = pred[] constant(false)
}

ENTRY %main.8 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.10 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.11 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_computation.1
  %while.12 = (s32[], f32[8]{0}) while(%init), condition=%cond.7, body=%body.3
  ROOT %out = f32[8]{0} get-tuple-element(%while.12), index=1
}
"""
    assert gate.instruction_counts(text) == {
        "fusions": 3, "whiles": 1, "sorts": 1, "copies": 1}


# ---- every device op of a served program lies in one block (ISSUE 39) ----
# What stays outside every block, by design: the embedding lookup, the
# page-table arithmetic, the unpacking of a prefill's packed upload, the
# scan's stacking of its outputs and a stateful family's install of the
# admitted slot's state (and, where its prefill carries state, the read of
# the slot's state the tokens start from). Each is a bare primitive at the program's top
# level: an op under any scope that is not a block's fails the test.
OUTSIDE_EVERY_BLOCK = {
    "gather", "slice", "select_n", "lt", "broadcast_in_dim",
    "dynamic_update_slice", "dynamic_slice", "bitcast_convert_type",
    "concatenate", "scatter"}
BLOCKS_OF = {
    "tiny-qwen2": {"attn", "mlp", "head", "sample"},
    "tiny-granite-hybrid": {"attn", "ssm", "mlp", "head", "sample"},
    "tiny-kanana-moe": {"attn", "mlp", "moe", "head", "sample"},
    "tiny-power-retention": {"ret", "mlp", "head", "sample"},
}
# The share of executed ops the compiler made itself (no `op_name`). The
# retention toy's head of 16 is outside its kernels' tiling, so the chip's
# compiler is given the plain form, whose stack of 9 rotations a head becomes
# a chain of update fusions it names itself; at the benchmark's head of 128
# the kernel builds them in VMEM (12 of the real `decode_multi`'s 257 ops).
UNNAMED_SHARE = {"tiny-power-retention": 0.3}


def executed_ops(hlo_text: str) -> list:
    """[(instruction, opcode, op_name)] of the fusions, products,
    convolutions and custom calls that execute as device ops: those of
    every computation that is not a fusion's body or a reducer."""
    import re

    rows, inner, current = {}, set(), None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = rows.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? "
                         r"(fusion|dot|convolution|custom-call)\(", line)
            if m:
                name = re.search(r'op_name="([^"]*)"', line)
                current.append((m.group(1), m.group(2),
                                name.group(1) if name else ""))
            if " call(" not in line:
                inner.update(re.findall(
                    r"(?:calls|to_apply)=%?([\w.\-]+)", line))
    return [r for comp, found in rows.items() if comp not in inner
            for r in found]


def block_of(op_name: str):
    """The outermost `blk.*` component of an op_name, "" for a bare
    primitive at the program's top level, None for an op under a scope
    that is no block's."""
    from xllm_service_tpu.models.base import BLOCK_PREFIX

    parts = [p for p in op_name.split("/")
             if not (p.startswith("jit(") or p in (
                 "while", "body", "cond", "closed_call", "branch_0_fun",
                 "branch_1_fun"))]
    for p in parts:
        if p.startswith(BLOCK_PREFIX):
            return p[len(BLOCK_PREFIX):]
    return "" if len(parts) <= 1 else None


@pytest.mark.parametrize("name", sorted(BLOCKS_OF))
def test_every_device_op_of_a_served_program_lies_in_one_block(chip, name):
    """The engine's real `decode_multi` and one `prefill_install` of the
    three families the cells run, compiled for the described chip: in the
    optimised module every executed fusion, product, convolution and
    custom call that carries an `op_name` lies under one `blk.*` scope
    (`models/base.block`) but for a named handful of bare primitives, each
    block the family has is there, and what the compiler made itself
    (no `op_name`: a bitcast, a relayout) stays a small share."""
    from chipbench.engine_setup import build_engine_config
    from xllm_service_tpu.models.base import BLOCKS

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    ecfg, _ = build_engine_config(
        REPO / "tests" / "chipbench" / "data" / "configs" / name, 0, name)
    with gate.steer_to_tpu():
        eng, params, d = gate.engine_shell(ecfg, device=chip[0])
        place = SingleDeviceSharding(chip[0])
        S = ecfg.prefill_buckets[0]
        packed = jax.ShapeDtypeStruct(
            (gate.prefill_packed_len(ecfg, S, False),), jnp.int32,
            sharding=place)
        mm = jax.ShapeDtypeStruct((1, 1, ecfg.model.hidden_size),
                                  ecfg.model.dtype, sharding=place)
        texts = {
            "decode_multi": eng._decode_multi.lower(
                params, d, ecfg.decode_horizon).compile().as_text(),
            "prefill_install": eng._prefill_install_nc.lower(
                params, d, packed, mm).compile().as_text()}
    for program, text in texts.items():
        ops = executed_ops(text)
        named = [(i, k, n) for i, k, n in ops if n]
        blocks = {(i, block_of(n)) for i, _, n in named}
        astray = [(i, n) for i, _, n in named if block_of(n) is None
                  or (block_of(n) == ""
                      and n.split("/")[-1] not in OUTSIDE_EVERY_BLOCK)]
        assert not astray, (program, astray[:10])
        seen = {b for _, b in blocks if b}
        assert seen <= set(BLOCKS) and seen == BLOCKS_OF[name], (program,
                                                                 seen)
        outside = [i for i, b in blocks if b == ""]
        assert len(outside) <= 0.12 * len(ops), (program, len(outside),
                                                 len(ops))
        assert len(ops) - len(named) <= UNNAMED_SHARE.get(name, 0.2) * len(
            ops), (program, len(ops) - len(named), len(ops))
        # the kernels are custom calls inside their block
        kernels = [(i, n) for i, k, n in named if k == "custom-call"
                   and "pallas_call" in n]
        assert all(block_of(n) for _, n in kernels), kernels
