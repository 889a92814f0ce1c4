"""Bring-up contracts: nothing may make a CPU run look like a chip run.

- the compile cache is placed from outside (JAX_COMPILATION_CACHE_DIR) or
  at one fixed path in the checkout;
- `chip_smoke.py` refuses to run without the chip;
- the trace-time kernel-or-XLA choice is on record.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu import utils
from xllm_service_tpu.ops import attention

REPO = Path(__file__).resolve().parent.parent


class TestCompileCachePlacement:
    @pytest.fixture()
    def dir_updates(self, monkeypatch):
        """Directories handed to jax.config by code (other keys pass)."""
        seen = []
        real = jax.config.update

        def spy(key, value):
            if key == "jax_compilation_cache_dir":
                seen.append(value)
            else:
                real(key, value)

        monkeypatch.setattr(jax.config, "update", spy)
        return seen

    def test_env_places_the_cache_and_code_sets_none(self, monkeypatch,
                                                     dir_updates, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert utils.enable_persistent_compile_cache() == str(tmp_path)
        assert dir_updates == []

    def test_unset_uses_the_fixed_path_in_the_checkout(self, monkeypatch,
                                                       dir_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_compile_cache")
        assert utils.enable_persistent_compile_cache() == want
        assert dir_updates == [want]

    def test_same_path_every_call_and_without_jax(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert utils.compile_cache_dir() == utils.compile_cache_dir() \
            == utils.DEFAULT_COMPILE_CACHE
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys; from xllm_service_tpu.utils import "
             "compile_cache_dir as d; print(d()); "
             "assert 'jax' not in sys.modules"],
            cwd=REPO, env=env, capture_output=True, text=True, check=True)
        assert out.stdout.strip() == utils.DEFAULT_COMPILE_CACHE


def test_chip_smoke_refuses_the_cpu():
    """Under JAX_PLATFORMS=cpu it exits non-zero and prints no result."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_master_and_actuator_stay_off_jax():
    """One process per chip: the master spawns engine processes
    (autoscaler/actuator.py), so importing it must not import JAX."""
    subprocess.run(
        [sys.executable, "-c",
         "import sys, xllm_service_tpu.master, "
         "xllm_service_tpu.autoscaler.actuator; "
         "assert 'jax' not in sys.modules"],
        cwd=REPO, check=True, timeout=120)


def _paged_case(seed=0, B=2, n_q=4, n_kv=2, hd=128, ps=16, pages=12, mp=4):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, n_q, hd)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(2, 2, pages, n_kv, ps, hd)),
                       jnp.float32)
    pt = jnp.asarray(rng.permutation(pages - 1)[:B * mp].reshape(B, mp) + 1,
                     jnp.int32)
    lens = jnp.asarray([ps * mp - 3, ps + 5], jnp.int32)
    return q, pool, 1, pt, lens       # read at the pool's second layer


class TestAttentionPathRecord:
    def test_cpu_takes_xla_and_says_so(self):
        rec = {}
        with attention.trace_program("prog", rec):
            attention.paged_attention(*_paged_case())
        assert rec == {"prog": {"paged_attention": "xla (cpu backend)"}}

    def test_interpret_mode_takes_the_kernel(self, monkeypatch):
        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        rec, args = {}, _paged_case()
        with attention.trace_program("prog", rec):
            got = attention.paged_attention(*args)
        assert rec == {"prog": {"paged_attention": "pallas"}}
        np.testing.assert_allclose(
            got, attention.paged_attention_xla(*args), rtol=2e-5, atol=2e-5)

    def test_model_mesh_runs_the_kernel_per_head_shard(self, monkeypatch):
        """Tensor parallel: the kernel under shard_map over `model`, pool
        sharded by KV head — same numbers as one device."""
        from jax.sharding import NamedSharding

        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh
        from xllm_service_tpu.parallel.sharding import KV_PAGES_SPEC

        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        mesh = build_mesh(MeshConfig(model=2), devices=jax.devices()[:2])
        q, pool, layer, pt, lens = _paged_case(seed=1)
        pool_s = jax.device_put(pool, NamedSharding(mesh, KV_PAGES_SPEC))
        rec = {}

        def step(q, pool, pt, lens):
            with attention.trace_program("prog", rec, mesh):
                return attention.paged_attention(q, pool, layer, pt, lens)

        got = jax.jit(step)(q, pool_s, pt, lens)
        assert rec == {"prog": {
            "paged_attention": "pallas (shard_map model=2)"}}
        np.testing.assert_allclose(
            got, attention.paged_attention_xla(q, pool, layer, pt, lens),
            rtol=2e-5, atol=2e-5)

    def test_engine_stats_carry_the_record(self):
        from test_pallas_engine_routing import (
            PROMPT, _greedy, _pallas_capable_engine)

        engine = _pallas_capable_engine()
        _greedy(engine, PROMPT, n=3)
        paths = engine.stats()["attention_paths"]
        assert paths["decode_multi"] == {
            "paged_attention": "xla (cpu backend)"}
        assert paths["prefill_install"] == {
            "prefill_attention": "xla-dense (cpu backend)"}

    def test_one_context_routes_ring_and_context_parallel(self):
        """`trace_program` alone carries what the dispatchers need: the
        ring field sends `prefill_attention` round the seq axis, a mesh
        with a seq axis sends `paged_attention` through the CP op."""
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(seq=2), devices=jax.devices()[:2])
        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.normal(size=(1, 32, n, 128)), jnp.float32)
                   for n in (4, 2, 2))
        zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32)
        want = attention.prefill_attention(q, k, v, None, None, None,
                                           zero, full)
        rec = {}

        def prefill(q, k, v):
            with attention.trace_program("prefill_sp", rec, mesh, ring=True):
                return attention.prefill_attention(q, k, v, None, None, None,
                                                   zero, full)

        np.testing.assert_allclose(jax.jit(prefill)(q, k, v), want,
                                   rtol=2e-5, atol=2e-5)
        qd, pool, layer, pt, lens = _paged_case(seed=2)

        def decode(q, pool, pt, lens):
            with attention.trace_program("decode", rec, mesh):
                return attention.paged_attention(q, pool, layer, pt, lens)

        np.testing.assert_allclose(
            jax.jit(decode)(qd, pool, pt, lens),
            attention.paged_attention_xla(qd, pool, layer, pt, lens),
            rtol=2e-5, atol=2e-5)
        assert rec == {"prefill_sp": {"prefill_attention": "ring"},
                       "decode": {"paged_attention": "cp-xla-dense (seq)"}}


_TILING = "xla (shape outside the kernel's tiling: "


@pytest.mark.parametrize("backend,interpret,hd,heads,kv,dtype,tp,cp,path", [
    ("tpu", False, 128, 28, 4, "bfloat16", 1, False, "pallas"),
    ("tpu", False, 128, 16, 2, "float32", 1, False, "pallas"),
    ("tpu", False, 128, 28, 4, "bfloat16", 2, False,
     "pallas (shard_map model=2)"),
    ("tpu", False, 128, 28, 4, "bfloat16", 4, False,
     "pallas (shard_map model=4)"),
    ("tpu", False, 128, 16, 2, "bfloat16", 2, False,
     "pallas (shard_map model=2)"),
    ("tpu", False, 128, 16, 2, "bfloat16", 4, False,
     "xla (kv heads 2 do not divide over tp=4)"),
    ("tpu", False, 64, 16, 2, "bfloat16", 1, False,
     _TILING + "hd=64 heads=16/2 dtype=bfloat16)"),
    ("tpu", False, 576, 28, 4, "bfloat16", 4, False,
     _TILING + "hd=576 heads=28/4 dtype=bfloat16)"),
    ("tpu", False, 128, 4, 3, "bfloat16", 1, False,
     _TILING + "hd=128 heads=4/3 dtype=bfloat16)"),
    ("tpu", False, 128, 28, 4, "float16", 1, False,
     _TILING + "hd=128 heads=28/4 dtype=float16)"),
    ("cpu", False, 128, 28, 4, "bfloat16", 4, False, "xla (cpu backend)"),
    ("cpu", False, 576, 4, 3, "float32", 1, False, "xla (cpu backend)"),
    ("cpu", True, 128, 28, 4, "bfloat16", 4, False,
     "pallas (shard_map model=4)"),
    ("cpu", True, 128, 16, 2, "float32", 4, False,
     "xla (kv heads 2 do not divide over tp=4)"),
    ("cpu", True, 64, 16, 2, "float32", 1, False, "xla (cpu backend)"),
    ("tpu", False, 128, 28, 4, "bfloat16", 1, True, "cp-pallas (seq)"),
    ("tpu", False, 128, 16, 2, "bfloat16", 4, True, "cp-pallas (seq)"),
    ("tpu", False, 64, 16, 2, "bfloat16", 1, True, "cp-xla-dense (seq)"),
    ("cpu", False, 128, 28, 4, "float32", 1, True, "cp-xla-dense (seq)"),
    ("cpu", True, 128, 28, 4, "float32", 2, True, "cp-pallas (seq)"),
    ("cpu", True, 128, 4, 3, "float32", 1, True, "cp-xla-dense (seq)"),
])
def test_attention_path_names_the_recorded_string(
        backend, interpret, hd, heads, kv, dtype, tp, cp, path):
    """The one decision over what the code observes, and the exact string
    `/stats`.attention_paths carries (chipbench's `decode_paths` check
    and chip_smoke.py compare against it)."""
    assert attention.attention_path(
        backend, interpret, hd, heads, kv, dtype, tp,
        context_parallel=cp) == path
