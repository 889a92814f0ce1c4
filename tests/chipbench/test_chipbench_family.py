"""The family seam: everything of the harness that depends on a model's shape
(seeded weights, plain reference, byte counts) is files found by name, the
default family is what it was, and a family the default tree cannot make
(the toy sparse-experts one under data/families/) lands without a line of
chipbench/ knowing it."""

import dataclasses
import hashlib
import inspect
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.engine_setup import build_engine_config, read_engine_json

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests/chipbench/data"
SEED = 2 ** 31 + 77


def _configs():
    """(search, config dir) of every configuration of the benchmark and of
    the tests' own BENCHMARK.json, and of any `BENCHMARK.<name>.json` a
    later PR puts beside that one for its toys (it may not edit a file
    that is here)."""
    out = []
    for bench_file in (ROOT / "BENCHMARK.json",
                       *sorted(DATA.glob("BENCHMARK*.json"))):
        bench, search = harness.load_bench(bench_file)
        out += [(search, (ROOT / c["file"]).parent) for c in bench["configs"]]
    return out


CONFIGS = _configs()
TOYS = [c for c in CONFIGS if DATA in c[1].parents]
# chipbench/, then the tests' data
SEARCH = harness.load_bench(DATA / "BENCHMARK.json")[1]


def _family(search, config_dir):
    hf = json.loads((config_dir / "config.json").read_text())
    served = read_engine_json(config_dir)["weights"]
    return harness.family_of(search, hf), hf, served


def tree_hash(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# Recorded from the parent commit (6c58c46, chipbench/weights.py before the
# seam): the default family makes the very tree it made.
PARENT_HASH = {
    "int8": "ae93cc087070c0e09e33c10ca67c63486548d3ea22aadf892d7ede8d53ed8c9b",
    "bfloat16":
        "b85503919b187dca00d56ac4cbc55ada49da6d72f626d00ae0051ec242993973"}


@pytest.mark.parametrize("served", sorted(PARENT_HASH))
def test_default_familys_tree_is_the_parent_commits(served):
    hf = json.loads((DATA / "configs/tiny-qwen2/config.json").read_text())
    family = harness.family_of(SEARCH, hf)
    assert family.name == "" and family.weights.__name__ == "chipbench.weights"
    assert family.reference.__name__ == "chipbench.reference"
    assert family.bytes.__name__ == "chipbench.bytes_model"
    assert tree_hash(family.weights.make_params(SEED, hf, served)) == \
        PARENT_HASH[served]


def test_a_family_is_found_under_the_second_search_path_and_loaded_once():
    hf = json.loads((DATA / "configs/tiny-moe/config.json").read_text())
    family = harness.family_of(SEARCH, hf)
    assert family.name == "toy-moe" and SEARCH[1] == DATA
    for part in harness.FAMILY_PARTS:
        mod = getattr(family, part)
        assert Path(mod.__file__) == (
            DATA / "families/toy-moe" / (part + ".py"))
        # another holder of the same family gets the same module: one jit
        # cache, one set of weights functions
        assert getattr(harness.family_of(SEARCH, hf), part) is mod
    with pytest.raises(AttributeError):
        family.tokenizer


@pytest.mark.parametrize("part", sorted(harness.FAMILY_PARTS))
def test_a_missing_part_is_a_failure_that_names_the_file(tmp_path, part):
    for p in harness.FAMILY_PARTS:
        if p != part:
            f = tmp_path / "b" / "families" / "half" / (p + ".py")
            f.parent.mkdir(parents=True, exist_ok=True)
            f.write_text("X = 1\n")
    family = harness.Family([tmp_path / "a", tmp_path / "b"], "half")
    with pytest.raises(harness.Failure, match=f"families/half/{part}.py"):
        getattr(family, part)
    others = [p for p in harness.FAMILY_PARTS if p != part]
    assert getattr(family, others[0]).X == 1


def test_a_file_that_fails_to_load_is_not_kept(tmp_path):
    f = tmp_path / "families" / "bad" / "bytes.py"
    f.parent.mkdir(parents=True)
    f.write_text("raise RuntimeError('first')\n")
    with pytest.raises(RuntimeError, match="first"):
        harness.Family([tmp_path], "bad").bytes
    f.write_text("def kv_bytes_per_token(hf):\n    return 7\n")
    assert harness.Family([tmp_path], "bad").bytes.kv_bytes_per_token({}) == 7


# ------------------------------------------------------------ the contract
IDS = [c[1].name for c in CONFIGS]


def _families():
    """The default family, and every `families/<name>/` under the paths of
    any of those BENCHMARK files, whether a configuration names it or not."""
    found = {"": CONFIGS[0][0]}
    for search, _ in CONFIGS:
        for d in search:
            for f in sorted(Path(d, "families").glob("*")):
                if f.is_dir() and f.name != "__pycache__":
                    found.setdefault(f.name, search)
    return [harness.Family(search, name) for name, search in found.items()]


FAMILIES = _families()


def test_every_family_a_configuration_names_is_among_those_found():
    names = {f.name for f in FAMILIES}
    assert {"", "toy-moe"} <= names
    assert {_family(*c)[0].name for c in CONFIGS} <= names


@pytest.mark.parametrize("family", FAMILIES,
                         ids=[f.name or "default" for f in FAMILIES])
def test_contract_signatures_and_independence(family):
    def params(fn):
        return [(p.name, p.default) for p in
                inspect.signature(fn).parameters.values()]

    E = inspect.Parameter.empty
    assert params(family.weights.make_params) == [
        ("seed", E), ("hf", E), ("served", E), ("out_shardings", None)]
    assert params(family.weights.param_shapes) == [("hf", E), ("served", E)]
    assert params(family.reference.logits_at) == [
        ("seed", E), ("hf", E), ("served", E), ("sequences", E),
        ("positions", E), ("lower", ""), ("pad_len", 0), ("pad_pos", 0)]
    assert params(family.bytes.decode_weight_stream_bytes) == [
        ("hf", E), ("served", E)]
    assert params(family.bytes.kv_bytes_per_token) == [("hf", E)]
    # the yardstick takes nothing of the program
    for part in harness.FAMILY_PARTS:
        src = Path(getattr(family, part).__file__).read_text()
        assert not re.search(r"^\s*(from|import)\s+xllm_service_tpu", src,
                             re.M), part


@pytest.mark.parametrize("search,config_dir", CONFIGS, ids=IDS)
def test_contract_shapes_and_bytes(search, config_dir):
    """`param_shapes` is the tree the program's family takes (its own
    `init_params`, quantised as the engine would for the served type), and
    the byte counts are None or a count."""
    from xllm_service_tpu.models.base import get_model_family
    from xllm_service_tpu.models.quant import quantize_tree

    family, hf, served = _family(search, config_dir)
    ecfg, _ = build_engine_config(config_dir, 0, "t")

    def init(rng):
        tree = get_model_family(ecfg.model_family).init_params(ecfg.model, rng)
        return quantize_tree(tree) if served == "int8" else tree

    def shapes(tree):
        return {jax.tree_util.keystr(p): x.shape for p, x in
                jax.tree_util.tree_leaves_with_path(tree)}

    got = family.weights.param_shapes(hf, served)
    assert shapes(got) == shapes(jax.eval_shape(init, jax.random.PRNGKey(0)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(got))
    stream = family.bytes.decode_weight_stream_bytes(hf, served)
    per_token = family.bytes.kv_bytes_per_token(hf)
    assert stream is None or 0 < stream <= weights
    assert per_token is None or (isinstance(per_token, int) and per_token > 0)


@pytest.mark.parametrize("search,config_dir", TOYS,
                         ids=[c[1].name for c in TOYS])
@pytest.mark.parametrize("served", ["int8", "bfloat16"])
def test_contract_weights_are_a_pure_function_of_their_arguments(
        search, config_dir, served):
    family, hf, _ = _family(search, config_dir)
    tree = family.weights.make_params(SEED, hf, served)
    want = family.weights.param_shapes(hf, served)
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert tree_hash(family.weights.make_params(SEED, hf, served)) == \
        tree_hash(tree)
    assert tree_hash(family.weights.make_params(SEED + 1, hf, served)) != \
        tree_hash(tree)


# --------------------------------- the toy family against the program's own
TINY_MOE = DATA / "configs/tiny-moe"


def _moe_program_logits(family, hf, served, toks):
    """Every position's logits from the program's own mixtral forward in
    float32 (`deepseek_moe.verify_forward`, which mixtral.py registers)."""
    from xllm_service_tpu.models import deepseek_moe

    ecfg, _ = build_engine_config(TINY_MOE, SEED, "t")
    assert ecfg.model_family == "mixtral"
    mcfg = dataclasses.replace(ecfg.model, dtype=jnp.float32,
                               quant="int8" if served == "int8" else "")
    params = family.weights.make_params(SEED, hf, served)
    S = len(toks)
    kv = jnp.zeros((mcfg.num_layers, 2, 64, mcfg.num_kv_heads, 16,
                    mcfg.head_dim), jnp.float32)
    pt = jnp.arange(1, 1 + S // 16, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        logits, _ = deepseek_moe.verify_forward(
            params, mcfg, jnp.asarray([toks]), jnp.arange(S)[None, :], kv, pt,
            jnp.zeros((1,), jnp.int32), jnp.full((1,), S, jnp.int32))
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("served", ["int8", "bfloat16"])
def test_toy_familys_reference_agrees_with_the_programs_forward(served):
    """Float32 on both sides at `highest` precision, so what is left is the
    order of float32 sums: 1e-4 on logits of O(1), the tolerance the Qwen2
    reference is held to (1e-5 read). The router is float32 on both sides,
    so no expert flips; a flip would read O(1)."""
    family, hf, _ = _family(SEARCH, TINY_MOE)
    toks = np.random.default_rng(1).integers(256, 1024, 96).tolist()
    got = _moe_program_logits(family, hf, served, toks)
    want = family.reference.logits_at(SEED, hf, served, [toks],
                                      [list(range(96))])[0]
    assert want.std() > 0.5
    assert np.max(np.abs(got - want)) < 1e-4
    # the experts matter: with the gates of another seed's router the
    # reference reads far off
    other = family.reference.logits_at(SEED + 1, hf, served, [toks],
                                       [list(range(96))])[0]
    assert np.max(np.abs(other - want)) > 0.5
    # and the control (one precision lower) moves it, padded or not
    low = family.reference.logits_at(SEED, hf, served, [toks], [[5, 95]],
                                     "int4" if served == "int8" else "int8",
                                     pad_len=256, pad_pos=8)[0]
    assert low.shape == (2, hf["vocab_size"])
    assert 1e-3 < np.max(np.abs(low - want[[5, 95]])) < 5.0


def test_toy_familys_bytes_leave_the_weight_stream_out():
    family, hf, served = _family(SEARCH, TINY_MOE)
    assert family.bytes.decode_weight_stream_bytes(hf, served) is None
    assert family.bytes.kv_bytes_per_token(hf) == 2 * 2 * 1 * 128 * 2
    read = harness.load_reader(SEARCH, "device.decode_weight_bw_pct")
    ir = json.loads((DATA / "trace_head_tpu_v5e.json").read_text())
    ctx = {"trace": ir, "engine": {"decode_horizon": 8, "weights": served},
           "hf": hf, "device": {"kind": "TPU v5 lite"}, "family": family}
    assert read(ctx) is None       # nothing to read, never a 0


@pytest.mark.slow
def test_rehearsal_of_the_toy_familys_cell(capsys):
    """The whole command on the CPU for a cell whose family is files under
    the tests' data: its earlier lines name the family it loaded, the
    `engine_config` it passed and the decode paths it held."""
    from chipbench import run

    run.main(["--workload", "tiny-moe.tiny-chat", "--seed", str(SEED),
              "--seconds", "5", "--trace", "0", "--rehearse", "--bench-file",
              "tests/chipbench/data/BENCHMARK.json"])
    cap = capsys.readouterr()
    lines = [json.loads(x) for x in cap.out.strip().split("\n")]
    res = lines[-1]
    assert res["correct"] is True and res["failed"] == 0
    by_phase = {x["phase"]: x for x in lines if "phase" in x}
    assert by_phase["family"]["family"] == "toy-moe"
    assert by_phase["family"]["engine_config"] == {"admission_horizon": 4}
    assert by_phase["paths"]["required"] == {"paged_attention": "pallas"}
    assert by_phase["paths"]["decode_multi"]["paged_attention"] == "pallas"
    # each number compared beside its limit: the result's last key, and
    # the last lines of stderr
    assert list(res)[-1] == "compared"
    assert res["compared"]["decode_multi.paged_attention"] == [
        "pallas", "pallas*"]
    assert set(res["compared"]) >= {"failed_requests", "gap_max", "gap_mean"}
    err = cap.err.strip().split("\n")[-len(res["compared"]) - 1:]
    assert err[-1] == "chipbench: correct True"
    assert [x.split()[1] for x in err[:-1]] == list(res["compared"])
    assert f"chipbench: gap_max {res['compared']['gap_max'][0]} limit 2.0" in err
