"""The KV pool is one buffer through every forward.

Two checks that need no chip: (1) every family's greedy tokens through the
engine's decode, install, verify and mixed decode+chunk programs equal a
cache-free dense reference; (2) the optimised HLO of `decode_multi` and
`prefill_install` holds no copy, slice or concatenate as large as one
layer of the pool, and aliases the pool argument to the pool result. The
TPU compiler's own verdict on the real widths is in test_chip_compile.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config
from xllm_service_tpu.models.gemma import gemma2_tiny_config
from xllm_service_tpu.models.mixtral import mixtral_tiny_config

from test_engine import Collector, naive_greedy

MODELS = {
    "llama": lambda: ("llama", tiny_config(dtype=jnp.float32)),
    "qwen2": lambda: ("qwen2", tiny_config(name="qwen2", qkv_bias=True,
                                           dtype=jnp.float32)),
    "gemma2": lambda: ("gemma", gemma2_tiny_config(dtype=jnp.float32)),
    "mixtral": lambda: ("mixtral", mixtral_tiny_config(dtype=jnp.float32)),
}
# The engine programs each path adds to decode_multi + prefill_install.
PATHS = {
    "plain": {},
    "verify": {"speculate_k": 2},                 # spec_multi: verify_forward
    "mixed": {"prefill_chunk_tokens": 32},        # chunks ride decode steps
}
SHORT = [11, 12, 13, 11, 12, 13, 11, 12, 13, 11]   # repeats: drafts to verify
LONG = list(range(5, 105))                          # 100 tokens: 4 chunks


def _engine(model: str, num_pages: int = 64, **kw) -> InferenceEngine:
    family, mcfg = MODELS[model]()
    return InferenceEngine(EngineConfig(
        model_family=family, model=mcfg, num_pages=num_pages, page_size=16,
        hash_block_size=32, max_batch_size=2, max_seq_len=256,
        prefill_buckets=(32, 64, 256), decode_horizon=4, **kw))


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("model", list(MODELS))
def test_greedy_tokens_equal_the_dense_reference(model, path):
    """A short request decodes while a long prompt is admitted beside it,
    so installs, decode calls and (where the path has them) verify blocks
    and riding chunks all write the one pool in place; every served token
    equals the reference that keeps no cache."""
    engine = _engine(model, **PATHS[path])
    want_short = naive_greedy(engine, SHORT, 12)
    want_long = naive_greedy(engine, LONG, 4)
    short, long_ = Collector(), Collector()
    engine.submit(EngineRequest(
        "short", token_ids=SHORT, on_output=short,
        sampling=SamplingParams(max_tokens=12, temperature=0.0,
                                ignore_eos=True)))
    engine.step()
    engine.submit(EngineRequest(
        "long", token_ids=LONG, on_output=long_,
        sampling=SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True)))
    for _ in range(300):
        engine.step()
        if short.done.is_set() and long_.done.is_set():
            break
    engine.stop()
    assert short.tokens == want_short
    assert long_.tokens == want_long


_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                    r"(copy|slice|concatenate|dynamic-slice)\(")
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "pred": 1}


def _layer_sized_copies(hlo: str, layer_bytes: int) -> list[str]:
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        size = _BYTES.get(m.group(1), 4) * int(np.prod(
            [int(d) for d in m.group(2).split(",") if d] or [1]))
        if size >= layer_bytes:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["decode_multi", "prefill_install"])
def test_no_layer_of_the_pool_is_copied(program):
    """Lower the engine's own program for a small float32 configuration
    (no chip: the CPU compiler) and read its optimised HLO. 2048 pages:
    one layer of the pool (8 MiB) dwarfs every activation, so an array
    that large is the pool's."""
    engine = _engine("llama", num_pages=2048)
    d = engine._dstate
    pool = d["kv"]
    layer_bytes = pool[0, 0].nbytes
    if program == "decode_multi":
        args = (engine.params, d, engine.cfg.decode_horizon)
        fn = engine._decode_multi
    else:
        import benchmarks.compile_gate as gate
        packed = jnp.zeros((gate.prefill_packed_len(engine.cfg, 64, False),),
                           jnp.int32)
        mm = jnp.zeros((1, 1, engine.cfg.model.hidden_size), jnp.float32)
        args = (engine.params, d, packed, mm)
        fn = engine._prefill_install_nc
    compiled = fn.lower(*args).compile()
    engine.stop()
    assert _layer_sized_copies(compiled.as_text(), layer_bytes) == []
    mem = compiled.memory_analysis()
    # The donated pool comes back in the buffer it arrived in, and nothing
    # pool-sized is held beside it while the program runs.
    assert mem.alias_size_in_bytes >= pool.nbytes
    assert mem.temp_size_in_bytes < pool.nbytes // 2


def test_empty_slots_do_not_buy_the_batch_a_sort():
    """A slot that holds no request keeps a stale temperature (1.0 from
    boot): with `live` it no longer sends an all-greedy batch down the
    sampling branch (two full-vocabulary sorts a step) or the
    log-probability branch; live rows decide, and get what they got."""
    from xllm_service_tpu.engine.sampling import SamplingState, sample_tokens

    B, V = 3, 64
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, V))
    st = SamplingState(
        jnp.asarray([1.0, 0.0, 0.7]), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,)), jnp.zeros((B,)), jnp.zeros((B,)), jnp.ones((B,)),
        jnp.zeros((B, V), jnp.int32), jnp.full((B, 2), -1, jnp.int32),
        jnp.zeros((B, 2)))
    keys = jnp.zeros((B, 2), jnp.uint32)
    steps = jnp.arange(B, dtype=jnp.int32)
    want_lp = jnp.asarray([True, False, False])
    greedy = np.asarray(jnp.argmax(logits, -1))

    def run(live):
        toks, lp = sample_tokens(logits, st, keys, steps,
                                 want_logprobs=want_lp,
                                 live=jnp.asarray(live))
        return np.asarray(toks), np.asarray(lp)

    every, lp_every = run([True, True, True])
    assert every[1] == greedy[1] and lp_every.any()
    # Rows 0 and 2 empty: nothing sampled, no log-probabilities computed.
    toks, lp = run([False, True, False])
    np.testing.assert_array_equal(toks, greedy)
    assert not lp.any()
    # Row 2 live: it samples exactly what it sampled in the full batch.
    toks, _ = run([False, True, True])
    assert toks[1] == greedy[1] and toks[2] == every[2]
