"""BENCHMARK.json against the contract's written rules, and every file a
cell, a configuration, a mix or a per-layer metric needs, found by name."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness, loadgen

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               and (ROOT / p).is_dir() for p in BENCH["paths"])
    assert len(BENCH["command"]) <= 32 and all(map(_line, BENCH["command"]))
    files = [w for w in BENCH["command"] if "/" in w]
    assert files and all(any(f.startswith(p + "/") for p in BENCH["paths"])
                         for f in files)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["source"]) and _line(cfg["why"])
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    assert len(cfg["reduced"]) <= 16
    assert not any(WIDTH.search(k) for k in cfg["reduced"])
    hf = json.loads((ROOT / cfg["file"]).read_text())
    assert hf["chipbench"]["source"] == cfg["source"]
    assert hf["chipbench"]["reduced"] == cfg["reduced"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    assert [c["file"] for c in BENCH["configs"]].count(cfg["file"]) == 1


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_its_files(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(wl[k]) for k in ("name", "config", "traffic"))
    assert wl["chips"] in (1, 4) and _line(wl["why"])
    bench, search = harness.load_bench(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(bench, search, wl["name"])
    assert cell.rate > 0 and cell.engine["tp"] * cell.engine["replicas"] <= wl["chips"]
    # the served tokens are always held to the reference; log-probabilities
    # are, where the cell's compared requests ask for them
    assert {"gap_max", "gap_mean"} <= set(cell.limits) <= {
        "gap_max", "gap_mean", "lp_rms"}
    assert ("lp_rms" in cell.limits) == (cell.check_logprobs > 0)
    assert {r.logprobs for r in harness.warmup_requests(cell)} == {
        cell.check_logprobs}
    warm = harness.warmup_requests(cell)
    eng = cell.engine["prefill_buckets"]
    met = {harness.bucket_for(eng, len(r.prompt)) for r in warm}
    want = {harness.bucket_for(eng, n) for lo, hi in
            loadgen.prefill_ranges(cell.mix) for n in range(lo, hi + 1)}
    assert met == want                     # every bucket the mix can meet
    assert {r.max_tokens for r in warm} >= {2, 3, 5, 9}   # every horizon
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert pairs.count((wl["config"], wl["traffic"])) == 1


def test_four_chip_cells_are_at_most_a_quarter():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entry(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1


def test_setup_s_is_an_end_to_end_metric():
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    names = [m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry_and_its_reader(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and _line(m["layer"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
    for w in m.get("workloads", ()):
        assert w in [x["name"] for x in BENCH["workloads"]]
    _, search = harness.load_bench(ROOT / "BENCHMARK.json")
    read = harness.load_reader(search, m["name"])
    # a reader that finds nothing to read returns nothing
    assert read({"trace": None, "agent_stats": {}, "hotpath": {}}) is None


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_cell_reports_what_the_contract_asks_of_it(wl):
    """`setup_s`, another end-to-end metric, a per-layer metric, and no
    per-layer metric whose `moves` the cell does not report: a metric
    judged in some cells only (`ttft_ms.mean`) takes its unlisted per-layer
    metrics out of the others."""
    e2e = [m["name"] for m in harness.metrics_for(BENCH, "end_to_end",
                                                  wl["name"])]
    layers = harness.metrics_for(BENCH, "per_layer", wl["name"])
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    assert all(m["moves"] in e2e for m in layers)
    for kind in ("end_to_end", "per_layer"):
        got = harness.metrics_for(BENCH, kind, wl["name"])
        for m in BENCH[kind]:
            if "workloads" in m:
                assert (m in got) == (wl["name"] in m["workloads"])
    for m in BENCH["end_to_end"]:
        for w in m.get("workloads", ()):
            assert w in [x["name"] for x in BENCH["workloads"]]


def test_files_under_paths_are_named_from_the_allowed_characters():
    out = subprocess.run(["git", "ls-files", "-co", "--exclude-standard",
                          *BENCH["paths"]], cwd=ROOT, capture_output=True,
                         text=True, check=True).stdout.split("\n")
    bad = [f for f in out if f and not PATH.match(f)]
    assert not bad


def test_token_text_round_trip():
    ids = [0, 1, 63, 64, 4095, 4096, 151935, 152063, 262143]
    text = "".join(harness.token_text(i) for i in ids)
    assert harness.text_tokens(text) == ids
    with pytest.raises(ValueError):
        harness.text_tokens("ab")


def _copy_benchmark(tmp: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp / p, ignore=shutil.ignore_patterns(
            "__pycache__"))


def test_new_cell_config_mix_and_metric_are_files_and_one_entry_each(tmp_path):
    """A later PR adds a configuration (its `engine.json` with
    `engine_config`), its family under `chipbench/families/`, a mix, a cell
    and a per-layer metric by dropping files in and appending to
    BENCHMARK.json; no file that is there is edited, the harness resolves
    all five by name, and the tests under `paths` that walk BENCHMARK.json,
    which that PR may not edit either, pass in the copy."""
    _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    data = ROOT / "tests/chipbench/data"
    shutil.copytree(data / "configs/tiny-moe",
                    tmp_path / "chipbench/configs/later-model")
    shutil.copytree(data / "families/toy-moe",
                    tmp_path / "chipbench/families/later-family")
    cfg = tmp_path / "chipbench/configs/later-model/config.json"
    hf = json.loads(cfg.read_text())
    hf["chipbench"]["family"] = "later-family"
    cfg.write_text(json.dumps(hf))
    shutil.copy(data / "traffic/tiny-chat.json",
                tmp_path / "chipbench/traffic/later-mix.json")
    (tmp_path / "chipbench/cells/later-model.later-mix.json").write_text(
        json.dumps({"config": "later-model", "traffic": "later-mix",
                    "rate_per_s": 2.0,
                    "limits": {"gap_max": 2.0, "gap_mean": 0.5}}))
    (tmp_path / "chipbench/layers/later.generated_total.py").write_text(
        "def read(ctx):\n"
        "    n = ctx['agent_stats'].get('total_generated')\n"
        "    return None if n is None else float(n)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "later-model", "source": hf["chipbench"]["source"],
        "reduced": [], "why": "x",
        "file": "chipbench/configs/later-model/config.json"})
    bench["workloads"].append({
        "name": "later-model.later-mix", "config": "later-model",
        "traffic": "later-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "later.generated_total", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "out_tok_per_s", "workloads": ["later-model.later-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # resolve with the copy's own harness, from the copy's root
    code = (
        "import json, sys; sys.path.insert(0, '.')\n"
        "from chipbench import harness\n"
        "b, s = harness.load_bench(harness.ROOT / 'BENCHMARK.json')\n"
        "c = harness.resolve_cell(b, s, 'later-model.later-mix')\n"
        "r = harness.load_reader(s, 'later.generated_total')\n"
        "names = [m['name'] for m in harness.metrics_for(b, 'per_layer', c.name)]\n"
        "old = [m['name'] for m in harness.metrics_for(b, 'per_layer', "
        "'qwen25-7b-int8.chat')]\n"
        "import jax\n"
        "tree = c.family.weights.param_shapes(c.hf, c.engine['weights'])\n"
        "print(json.dumps([str(harness.ROOT), c.rate, c.hf['hidden_size'], "
        "r({'agent_stats': {'total_generated': 5}}), "
        "'later.generated_total' in names, 'later.generated_total' in old, "
        "c.family.name, c.family.weights.__file__, "
        "list(tree['moe']['experts']['up_proj']['kernel'].shape), "
        "c.family.bytes.kv_bytes_per_token(c.hf), "
        "c.engine['engine_config'], c.decode_paths]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT),
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    (root, rate, hidden, value, in_new, in_old, family, weights_file, experts,
     kv_bytes, engine_config, decode_paths) = json.loads(
        out.stdout.strip().split("\n")[-1])
    assert Path(root) == tmp_path
    assert (rate, hidden, value, in_new, in_old) == (2.0, 256, 5.0, True, False)
    assert family == "later-family" and Path(weights_file) == (
        tmp_path / "chipbench/families/later-family/weights.py")
    assert experts == [2, 4, 256, 128] and kv_bytes == 1024
    assert engine_config == {"admission_horizon": 4}
    assert decode_paths == {"paged_attention": "pallas"}
    # The copy holds the tests too (`tests/chipbench` is under `paths`): the
    # ones that walk BENCHMARK.json and the families, run there. Left out:
    # this test, the one that asks git, the two that start run.py, and the
    # toys' numerics, which the new files do not touch.
    walk = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-m", "not slow",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-k",
         "not (new_cell_config or files_under_paths or directory_with_only "
         "or without_a_tpu or reference_agrees or pure_function "
         "or parent_commits)",
         *(f"tests/chipbench/test_chipbench_{f}.py"
           for f in ("files", "units", "family"))],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT),
             "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1"})
    assert walk.returncode == 0, walk.stdout[-4000:] + walk.stderr[-2000:]
    for ran in ("test_config_entry[later-model]",
                "test_workload_entry_and_its_files[later-model.later-mix]",
                "test_per_layer_entry_and_its_reader[later.generated_total]",
                "test_contract_signatures_and_independence[later-family]",
                "test_contract_shapes_and_bytes[later-model]",
                "test_the_two_engine_files_of_pr_23_hold_the_eleven_keys_"
                "alone[qwen25-7b-int8]",
                "test_a_family_is_found_under_the_second_search_path_and_"
                "loaded_once"):
        assert ran + " PASSED" in walk.stdout, ran
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    _copy_benchmark(tmp_path)
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env={"PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
