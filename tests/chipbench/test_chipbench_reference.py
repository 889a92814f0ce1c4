"""The plain reference against the program at a tiny size on the CPU, the
control (one precision lower must read far above a sound run), and
`correct` coming out false when the timed path is broken underneath."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.engine_setup import build_engine_config

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests/chipbench/data"
TINY = DATA / "configs/tiny-qwen2"
HF = json.loads((TINY / "config.json").read_text())
SEED = 2 ** 31 + 77
LOWER = {"bfloat16": "int8", "int8": "int4"}


def _program_logits(served, toks, dtype):
    """Every position's logits from the program's own qwen2 prefill."""
    from xllm_service_tpu.models import llama

    ecfg, _ = build_engine_config(TINY, SEED, "t")
    mcfg = dataclasses.replace(ecfg.model, dtype=dtype,
                               quant="int8" if served == "int8" else "")
    params = weights.make_params(SEED, HF, served)
    S = len(toks)
    kv = jnp.zeros((mcfg.num_layers, 2, 64, mcfg.num_kv_heads, 16,
                    mcfg.head_dim), dtype)
    pt = jnp.arange(1, 1 + S // 16, dtype=jnp.int32)[None, :]
    x = llama._embed(params, mcfg, jnp.asarray([toks])).astype(dtype)
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.prefill_from_embeddings(
            params, mcfg, x, jnp.arange(S)[None, :], kv, pt,
            jnp.zeros((1,), jnp.int32), jnp.full((1,), S, jnp.int32),
            all_logits=True)
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("served", ["int8", "bfloat16"])
def test_reference_agrees_with_the_programs_qwen2_in_float32(served):
    toks = np.random.default_rng(1).integers(256, 1024, 96).tolist()
    got = _program_logits(served, toks, jnp.float32)
    want = reference.logits_at(SEED, HF, served, [toks], [list(range(96))])[0]
    assert want.std() > 0.5                      # logits of O(1), not zeros
    assert np.max(np.abs(got - want)) < 1e-4


@pytest.mark.parametrize("served", ["int8", "bfloat16"])
def test_one_layers_leaves_are_the_stacked_trees_slice(served):
    root = weights.root_key(SEED)
    tree = weights.make_params(SEED, HF, served)
    one = jax.jit(lambda k: weights.layer_leaves(k, HF, served))(
        weights.layer_key(root, 1))
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda x: x[1],
                                                 tree["layers"]))):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    kinds = {str(x.dtype) for x in jax.tree.leaves(tree)}
    assert kinds == ({"int8", "float32", "bfloat16"} if served == "int8"
                     else {"bfloat16"})
    again = weights.make_params(SEED, HF, served)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    other = weights.make_params(SEED + 1, HF, served)
    assert not np.array_equal(np.asarray(tree["embed"]["embedding"]),
                              np.asarray(other["embed"]["embedding"]))


def _served_bf16(served, prompt, n_out, k=5):
    """Greedy tokens, and their top-k log-probabilities, from the program's
    forward in bfloat16, the type it is served in: what a sound timed path
    produces."""
    toks, tops = list(prompt), []
    for _ in range(n_out):
        pad = toks + [0] * (-len(toks) % 16)
        logits = _program_logits(served, pad, jnp.bfloat16)
        ids, vals = reference.top_logprobs(logits[len(toks) - 1][None], k)
        tops.append((ids[0], vals[0]))
        toks.append(int(ids[0][0]))
    return toks[len(prompt):], (np.stack([t[0] for t in tops]),
                                np.stack([t[1] for t in tops]))


@pytest.mark.parametrize("served", ["int8", "bfloat16"])
def test_control_reads_above_the_sound_path_and_a_broken_token_fails(served):
    from chipbench import run

    rng = np.random.default_rng(5)
    prompts = [rng.integers(256, 1024, n).tolist() for n in (40, 24)]
    outs, tops = zip(*[_served_bf16(served, p, 12) for p in prompts])
    seqs, pos = reference.teacher_forced(prompts, outs)
    ref = reference.logits_at(SEED, HF, served, seqs, pos)
    low = reference.logits_at(SEED, HF, served, seqs, pos, LOWER[served])
    sound = run.measure(ref, outs, list(tops))
    control = run.measure(ref, [lg.argmax(-1) for lg in low],
                          [reference.top_logprobs(lg, 5) for lg in low])
    assert sound["gap_max"] >= 0 and sound["lp_values"] == 2 * 12 * 5
    assert control["gap_mean"] > 3 * max(sound["gap_mean"], 1e-4)
    # bfloat16 against int8 weights: the served logits are themselves rounded
    # to bfloat16, so the two noises lie a factor of two to three apart (PERF.md
    # section 2); the limit goes between them
    assert control["lp_rms"] > 2 * sound["lp_rms"] > 0
    limits = {"gap_max": max(3 * sound["gap_max"], 0.05),
              "gap_mean": max(3 * sound["gap_mean"], 0.005),
              "lp_rms": (sound["lp_rms"] * control["lp_rms"]) ** 0.5}

    def verdict(cmp_, **kw):
        args = dict(failed=0, compiled=0, loaded=0,
                    paths={"paged_attention": "pallas"},
                    required={"paged_attention": "pallas"}, blocks=None)
        args.update(kw)
        return run.decide(limits, cmp_=cmp_, **args)[0]

    assert verdict(sound) is True
    assert verdict(control) is False
    # the log-probabilities alone: the control's with the sound run's tokens
    assert verdict(dict(sound, lp_rms=control["lp_rms"])) is False
    # a token altered where it is produced: one served id shifted by one
    broken = [list(o) for o in outs]
    broken[0][3] = 256 + (broken[0][3] + 1 - 256) % 768
    assert verdict(run.measure(ref, broken, list(tops))) is False
    # a compared request that came without its log-probabilities
    assert verdict(run.measure(ref, outs, [tops[0], None])) is False
    # the other things `correct` rests on
    assert verdict(sound, failed=1) is False
    assert verdict(sound, compiled=1) is False
    assert verdict(sound, loaded=1) is False
    assert verdict(sound,
                   paths={"paged_attention": "xla (cpu backend)"}) is False
    assert verdict(sound, blocks=[3, 4]) is False
    assert verdict(sound, blocks=[4, 4]) is True
    assert verdict(None) is False


def _rehearse(capsys, monkeypatch, workload, extra=()):
    from chipbench import run

    run.main(["--workload", workload, "--seed", str(SEED), "--seconds", "5",
              "--trace", "0", "--rehearse", "--bench-file",
              "tests/chipbench/data/BENCHMARK.json", *extra])
    return json.loads(capsys.readouterr().out.strip().split("\n")[-1])


@pytest.mark.slow
def test_rehearsal_runs_the_whole_command_on_the_cpu(capsys, monkeypatch):
    """coordination server + master + agent_main as processes, the client,
    the reference: everything but the chip. Spawns processes for ~20 s, so
    it is not in tier-1."""
    res = _rehearse(capsys, monkeypatch, "tiny-qwen2.tiny-prefix")
    assert res["device"]["platform"] == "cpu"
    assert res["correct"] is True and res["failed"] == 0
    from chipbench import harness

    bench = json.loads(
        (ROOT / "tests/chipbench/data/BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in harness.metrics_for(
        bench, "end_to_end", "tiny-qwen2.tiny-prefix")}
    assert "ttft_ms.mean" not in res["metrics"]


@pytest.mark.slow
def test_rehearsal_with_a_broken_engine_is_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: an agent whose engine alters a
    token where it is produced. Everything else of the run is the same."""
    from chipbench import harness

    monkeypatch.setattr(harness, "AGENT_SCRIPT",
                        ROOT / "tests/chipbench/broken_agent.py")
    res = _rehearse(capsys, monkeypatch, "tiny-qwen2.tiny-chat")
    assert res["failed"] == 0 and res["correct"] is False
