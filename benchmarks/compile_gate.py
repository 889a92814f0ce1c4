"""Described-chip compile gate: ask the TPU's own compiler, from a machine
with no TPU, whether the served path's kernels and programs compile and
fit.

`jax.experimental.topologies` describes a `v5e:2x2` host that is not
attached; lowering against its devices with `jax.ShapeDtypeStruct`
arguments runs Mosaic and the XLA TPU backend for real, so a kernel the
chip would refuse is refused here, and `memory_analysis()` says whether a
program fits the chip's HBM. Nothing executes: a pass is not a chip run.

Arms are the Pallas kernels the served path can reach at Llama-3-8B head
shapes (32 q / 8 kv heads, head_dim 128, pages of 16) and at the benchmark's
configurations' (the state-update kernel of its state-space family among
them), the tensor-parallel
form of the decode kernel on the 4-device mesh, and the engine's own
decode / prefill-install programs built by `InferenceEngine._build_programs`
on a shell engine that holds shapes only. The dispatch gates in
`ops/attention.py` key on `jax.default_backend()`, which still reads "cpu"
here, so `steer_to_tpu` points their one hook at "tpu" for the compile.

    JAX_PLATFORMS=cpu python benchmarks/compile_gate.py

prints one JSON line per arm: the kernels within a minute, then the engine's
programs at `chip_smoke.py`'s one-chip and `--tp 4` configs and the
full-depth bf16 `--tp 4` fit (about a quarter of an hour; interrupt when the
kernels are what you came for). Exit code 1 when any arm is refused. `tests/test_chip_compile.py` keeps a
few arms in tier-1.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

TOPOLOGY = "v5e:2x2"
POOL_LAYERS = 2     # the kernel arms' pool: [L, 2, pages, n_kv, ps, hd]
HBM_BYTES = int(15.75 * 2 ** 30)      # what the compiler allows one v5e chip


def describe_devices():
    """The four devices of a described (not attached) v5e 2x2 host."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices


@contextlib.contextmanager
def steer_to_tpu():
    """Send the trace-time backend gates down their TPU branch."""
    from xllm_service_tpu.ops import attention

    prev = attention._backend
    attention._backend = lambda: "tpu"
    try:
        yield
    finally:
        attention._backend = prev


def model_mesh(devices, tp: int):
    from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(model=tp), devices=list(devices)[:tp])


def shaped(tree, shardings):
    """ShapeDtypeStructs of `tree` carrying `shardings` (one for all
    leaves, or a matching pytree)."""
    if not isinstance(shardings, (dict, list, tuple)):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=shardings), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, shardings)


def engine_shell(cfg, mesh=None, device=None):
    """An `InferenceEngine` that holds no arrays: programs built by the
    real `_build_programs`, plus (params, decode-state) as shapes placed
    on `mesh` or on `device`."""
    from xllm_service_tpu.engine.engine import (
        InferenceEngine, new_decode_state)
    from xllm_service_tpu.models.base import get_model_family
    from xllm_service_tpu.models.quant import quantize_tree
    from xllm_service_tpu.parallel.mesh import AXIS_SEQ
    from xllm_service_tpu.parallel.sharding import tree_specs

    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.mesh = cfg, mesh
    eng.family = get_model_family(cfg.model_family)
    eng.seq_parallel = int(mesh.shape[AXIS_SEQ]) if mesh is not None else 1
    eng._paths = {}
    eng._dstate_shardings = eng._decode_state_shardings()
    eng._build_programs()

    def init(rng):
        params = eng.family.init_params(cfg.model, rng)
        return quantize_tree(params) if cfg.model.quant else params

    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    dstate = jax.eval_shape(lambda: new_decode_state(cfg))
    if mesh is None:
        one = SingleDeviceSharding(device)
        return eng, shaped(params, one), shaped(dstate, one)
    specs = tree_specs(params, eng.family.sharding_rules)
    return (eng,
            shaped(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs)),
            shaped(dstate, eng._dstate_shardings))


def prefill_packed_len(cfg, bucket: int, with_counts: bool) -> int:
    """Length of `prefill_install`'s packed int32 upload (layout in its
    docstring) for a non-VL family."""
    from xllm_service_tpu.engine.engine import NUM_STOP_IDS
    from xllm_service_tpu.engine.sampling import NUM_BIAS

    n_ints = cfg.pages_per_seq + 4 + NUM_STOP_IDS + NUM_BIAS + 1
    n_floats = 6 + NUM_BIAS
    return (bucket + n_ints + n_floats
            + (cfg.model.vocab_size if with_counts else 0) + 2)


def compile_engine_programs(cfg, mesh=None, device=None, horizons=(1,),
                            buckets=None) -> dict:
    """Compile the engine's decode and prefill-install programs for the
    described chip(s). Returns {name: {compile_s, memory…, hlo facts}}."""
    with steer_to_tpu():
        eng, params, d = engine_shell(cfg, mesh=mesh, device=device)
        place = (NamedSharding(mesh, P()) if mesh is not None
                 else SingleDeviceSharding(device))
        out = {}
        progs = [(f"decode_multi_h{h}", eng._decode_multi, (params, d, h))
                 for h in horizons]
        for S in (cfg.prefill_buckets if buckets is None else buckets):
            packed = jax.ShapeDtypeStruct(
                (prefill_packed_len(cfg, S, False),), jnp.int32,
                sharding=place)
            mm = jax.ShapeDtypeStruct((1, 1, cfg.model.hidden_size),
                                      cfg.model.dtype, sharding=place)
            progs.append((f"prefill_install_nc_s{S}",
                          eng._prefill_install_nc, (params, d, packed, mm)))
        for name, fn, args in progs:
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            out[name] = describe_compiled(compiled,
                                          time.perf_counter() - t0)
        out["attention_paths"] = eng._paths
    return out


def instruction_counts(hlo_text: str) -> dict:
    """How many device operations a program is made of: the fusions, loops,
    sorts and copies of every computation of the optimised HLO that is not
    a fusion's or a reducer's body (the entry, and the loop bodies and
    branches it calls: at a horizon above 1 the step is a `while` body).
    Each executes as an op of its own, a few microseconds whatever its
    size: a step of hundreds of small ones is bound by their number."""
    kinds = {"fusion": "fusions", "while": "whiles", "sort": "sorts",
             "copy": "copies"}
    ops, inner, current = {}, set(), None    # per computation; bodies' names
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = ops.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            op = re.search(r" (fusion|while|sort|copy)\(", line)
            if op:
                current.append(kinds[op.group(1)])
            if " call(" not in line:        # a call's target runs as ops
                inner.update(re.findall(
                    r"(?:calls|to_apply)=%?([\w.\-]+)", line))
    counts = dict.fromkeys(kinds.values(), 0)
    for name, found in ops.items():
        if name not in inner:
            for kind in found:
                counts[kind] += 1
    return counts


def describe_compiled(compiled, seconds: float) -> dict:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return {
        "compile_s": round(seconds, 1),
        "argument_gib": round(m.argument_size_in_bytes / 2 ** 30, 2),
        "temp_gib": round(m.temp_size_in_bytes / 2 ** 30, 2),
        "total_gib": round(total / 2 ** 30, 2),
        "fits_hbm": total <= HBM_BYTES,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce("),
        **instruction_counts(text),
    }


def kernel_arms(devices):
    """Yield (name, thunk); each thunk compiles one kernel for the
    described chip(s) and returns the compiled executable."""
    from xllm_service_tpu.models.base import llama3_8b_config

    mcfg = llama3_8b_config()
    n_q, n_kv, hd, ps = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim, 16
    one = SingleDeviceSharding(devices[0])
    bf16, i32 = jnp.bfloat16, jnp.int32

    def f(shape, dtype, sharding=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def paged(B, max_pages, pool, heads=(n_q, n_kv), **kw):
        from xllm_service_tpu.ops.pallas_paged_attention import (
            paged_attention_pallas)

        def thunk():
            n_q, n_kv = heads
            fn = jax.jit(lambda q, kv, ly, pt, cl: paged_attention_pallas(
                q, kv, ly, pt, cl, **kw))
            return fn.lower(f((B, n_q, hd), bf16),
                            f((POOL_LAYERS, 2, pool, n_kv, ps, hd), bf16),
                            f((1,), i32),
                            f((B, max_pages), i32), f((B,), i32)).compile()
        return thunk

    yield "paged_b16", paged(16, 128, 2048)
    yield "paged_b64", paged(64, 128, 2048)
    yield "paged_b16_ctx8k", paged(16, 512, 8192)
    yield "gemma2_softcap", paged(16, 128, 2048, softcap=50.0,
                                  scale=256 ** -0.5)
    yield "gemma2_window", paged(16, 128, 2048, window=4096 // 8)
    # The benchmark's configurations: batch, table width, pool and heads
    # of chipbench/configs/*/engine.json (chunks of 16 pages, the run copy
    # in them), on the bfloat16 pool whose K and V the kernel hands to the
    # MXU as they lie: the 7B's groups of 7 rows sit on no (16, 128) tile.
    yield "paged_qwen25_7b", paged(32, 96, 2048, heads=(28, 4))
    yield "paged_qwen25_3b", paged(32, 128, 4096, heads=(16, 2))
    # granite-4.0-h-micro's four attention layers: a group of 4, heads of
    # 64 held at the lane width, a table of 64 pages.
    yield "paged_granite_h_micro", paged(32, 64, 2048, heads=(32, 8),
                                         scale=1 / 64)

    def prefill(S, max_pages, pool, heads, lanes=hd, **kw):
        from xllm_service_tpu.ops.pallas_prefill_attention import (
            prefill_attention_pallas)

        def thunk():
            n_q, n_kv = heads
            fn = jax.jit(lambda q, kv, ly, pt, pl_, sl:
                         prefill_attention_pallas(q, kv, ly, pt, pl_, sl,
                                                  **kw))
            return fn.lower(
                f((1, S, n_q, lanes), bf16),
                f((POOL_LAYERS, 2, pool, n_kv, ps, lanes), bf16),
                f((1,), i32), f((1, max_pages), i32), f((1,), i32),
                f((1,), i32)).compile()
        return thunk

    # The prefill kernel at the four configurations' heads, table width
    # and top bucket (one admission a call): groups of 7, 8 and 4 rows a
    # position, and kanana-2's 32 heads over one latent head of 640 lanes.
    yield "prefill_qwen25_7b", prefill(1536, 96, 2048, (28, 4))
    yield "prefill_qwen25_3b", prefill(2048, 128, 4096, (16, 2))
    yield "prefill_granite_h_micro", prefill(1024, 64, 2048, (32, 8),
                                             scale=1 / 64)
    yield "prefill_kanana2_latent", prefill(3072, 192, 4096, (32, 1),
                                            lanes=640, scale=192 ** -0.5)
    yield "prefill_gemma2", prefill(512, 128, 2048, (n_q, n_kv),
                                    softcap=50.0, window=4096 // 8,
                                    scale=256 ** -0.5)

    def ssm_update():
        # The state-update kernel at granite-4.0-h-micro's widths: 36
        # layers x 32 slots of [128, 4096] float32, updated in place.
        from xllm_service_tpu.ops.pallas_ssm_update import ssm_update_pallas

        L, B, N, K = 36, 32, 128, 4096
        f32 = jnp.float32
        return jax.jit(ssm_update_pallas, donate_argnums=(0,)).lower(
            f((L, B, N, K), f32), f((), i32), f((B,), jnp.bool_),
            f((B, K), f32), f((B, K), f32), f((B, N), f32),
            f((B, N), f32)).compile()

    yield "ssm_update_granite_h_micro", ssm_update

    def retention(which):
        # The two power-retention kernels at Brumby-14B's widths (40 query
        # and 8 KV heads of 128; 8 layers x 12 slots of float32 state, the
        # prefill's sub-chunk of 1024 tokens).
        from xllm_service_tpu.ops import pallas_retention as pr
        from xllm_service_tpu.ops.retention import slabs, z_rows

        L, B, Hq, Hk, d, C = 8, 12, 40, 8, 128, 1024
        M, Mz, G, f32 = slabs(d), z_rows(d), Hq // Hk, jnp.float32

        def update():
            return jax.jit(
                lambda s, z, *a: pr.retention_update_pallas(s, z, *a, 1e-6),
                donate_argnums=(0, 1)).lower(
                f((L, B, Hk, M, d, d), f32), f((L, B, Hk, Mz, d), f32),
                f((), i32), f((B,), jnp.bool_), f((B, Hq, d), bf16),
                f((B, Hk, d), bf16), f((B, Hk, d), bf16),
                f((B, Hk), f32)).compile()

        def prefill():
            return jax.jit(lambda *a: pr.retention_cross_pallas(
                *a, bf16)).lower(
                f((C, Hk, G, d), bf16), f((C, Hk, G, d), f32),
                f((C, Hk, d), bf16), f((C, Hk, d), f32), f((C, Hk, d), bf16),
                f((Hk,), f32), f((Hk, M, d, d), f32),
                f((Hk, Mz, d), f32)).compile()

        return {"update": update, "prefill": prefill}[which]

    yield "retention_update_brumby", retention("update")
    yield "retention_prefill_brumby", retention("prefill")

    def mover(which):
        from xllm_service_tpu.ops import pallas_page_dma as dma

        def thunk():
            L, n = mcfg.kv_layers, 8
            kv = f((L, 2, 1024, n_kv, ps, hd), bf16)
            ids = f((n,), i32)
            with steer_to_tpu():
                if which == "gather":
                    return jax.jit(dma.gather_kv_pages).lower(
                        kv, ids).compile()
                blk = f((L, 2, n, n_kv, ps, hd), bf16)
                return jax.jit(dma.scatter_kv_pages,
                               donate_argnums=(0,)).lower(
                    kv, ids, blk).compile()
        return thunk

    yield "page_gather_l32", mover("gather")
    yield "page_scatter_l32", mover("scatter")

    def cp_partial():
        from xllm_service_tpu.ops.cp_paged_attention import (
            _paged_partial_pallas)
        B, mp, pool = 16, 132, 16 * 128 + 64
        return jax.jit(lambda *a: _paged_partial_pallas(
            *a, scale=hd ** -0.5)).lower(
            f((B, n_q, hd), bf16), f((pool, n_kv, ps, hd), bf16),
            f((pool, n_kv, ps, hd), bf16), f((B, mp), i32),
            f((B, mp), i32), f((B,), i32), f((B,), i32)).compile()

    yield "cp_partial_stats", cp_partial

    def paged_tp4():
        from xllm_service_tpu.ops import attention

        mesh = model_mesh(devices, 4)
        heads = NamedSharding(mesh, P(None, "model", None))
        pool = NamedSharding(mesh, P(None, None, None, "model", None, None))
        rep = NamedSharding(mesh, P())

        def fn(q, kv, pt, cl):
            with attention.trace_program("paged_tp4", {}, mesh):
                return attention.paged_attention(q, kv, POOL_LAYERS - 1,
                                                 pt, cl)

        with steer_to_tpu():
            return jax.jit(fn).lower(
                f((16, n_q, hd), bf16, heads),
                f((POOL_LAYERS, 2, 2048, n_kv, ps, hd), bf16, pool),
                f((16, 128), i32, rep), f((16,), i32, rep)).compile()

    yield "paged_shard_map_tp4", paged_tp4


def smoke_engine_config(model_config: str = "llama3_8b", quant: str = "int8",
                        **kw):
    """The EngineConfig `chip_smoke.py`'s agent flags resolve to (the
    agent's own bucket ladder), for the whole-program arms."""
    from xllm_service_tpu.engine.config import (
        EngineConfig, prefill_bucket_ladder)
    from xllm_service_tpu.models import base

    mcfg = getattr(base, f"{model_config}_config")()
    if quant:
        mcfg = dataclasses.replace(mcfg, quant=quant)
    max_seq = kw.pop("max_seq_len", 1024)
    return EngineConfig(
        model=mcfg, model_family=mcfg.name, max_seq_len=max_seq,
        prefill_buckets=prefill_bucket_ladder(max_seq), **kw)


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    devices = describe_devices()
    report, failed = {}, []

    def run(name, thunk):
        t0 = time.perf_counter()
        try:
            res = thunk()
        except Exception as e:  # noqa: BLE001 — the refusal is the verdict
            report[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:600]}
            failed.append(name)
        else:
            report[name] = {"ok": True, **(
                res if isinstance(res, dict)
                else describe_compiled(res, time.perf_counter() - t0))}
        print(json.dumps({name: report[name]}), flush=True)

    for name, thunk in kernel_arms(devices):
        run(name, thunk)
    import chip_smoke

    run("engine_one_chip", lambda: compile_engine_programs(
        smoke_engine_config(**chip_smoke.ONE_CHIP_ENGINE),
        device=devices[0],
        horizons=(1, chip_smoke.ONE_CHIP_ENGINE["decode_horizon"])))
    run("engine_tp4", lambda: compile_engine_programs(
        smoke_engine_config(**chip_smoke.FOUR_CHIP_ENGINE),
        mesh=model_mesh(devices, 4),
        horizons=(chip_smoke.FOUR_CHIP_ENGINE["decode_horizon"],)))
    run("engine_tp4_full_depth_bf16", lambda: compile_engine_programs(
        smoke_engine_config(**{**chip_smoke.FOUR_CHIP_ENGINE,
                               "model_config": "llama3_8b"}),
        mesh=model_mesh(devices, 4),
        horizons=(chip_smoke.FOUR_CHIP_ENGINE["decode_horizon"],),
        buckets=(512,)))
    print(json.dumps({"topology": TOPOLOGY, "failed_arms": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
