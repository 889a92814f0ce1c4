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


# The entries of BENCHMARK.json that the newest `benchmark` PR edited or took
# away on purpose, by name (PR 44: the first token is judged in cells 3 and 5
# too, so `ttft_ms.mean` lists them and the six wrapper entries through
# which they read it per layer are gone). The rule below lets exactly these
# differ from the parent's. The next `benchmark` PR replaces the contents
# with its own (README, "Adding things"); no other PR touches the tuple.
BENCHMARK_EDITS = (
    "ttft_ms.mean",
    "client.ttft_mean_ms.chat-short", "engine.queue_ms.chat-short",
    "engine.prefill_ms.chat-short",
    "client.ttft_mean_ms.doc-long", "engine.queue_ms.doc-long",
    "engine.prefill_ms.doc-long")
KINDS = ("configs", "workloads", "end_to_end", "per_layer")


def _cells_due(bench: dict, entry: dict) -> list[str]:
    """The cells of `bench`, in its order, that report its per-layer `entry`."""
    return [w["name"] for w in bench["workloads"]
            if entry in harness.metrics_for(bench, "per_layer", w["name"])]


def edits_of_what_stood(parent: dict, tree: dict, edits=()) -> list[str]:
    """The one rule for what an addition may do to BENCHMARK.json, as a list
    of what `tree` breaks of it against `parent` (empty: an addition alone).
    `command`, `paths` and `run_seconds` are equal; the parent's `configs`,
    `workloads`, `end_to_end` and `per_layer` are each a prefix of the
    tree's, entry for entry equal, but that a per-layer entry that had no
    `workloads` list may have gained the list of exactly the parent's cells
    that were due it (the driver's own rule for "no change"; what PR 41 did
    to the six readers of keys). Entries named in `edits` are set aside on
    both sides: a `benchmark` PR's, which alone may edit or remove."""
    faults = [f"`{k}` differs" for k in ("command", "paths", "run_seconds")
              if parent[k] != tree[k]]
    for kind in KINDS:
        stood = [m for m in parent[kind] if m["name"] not in edits]
        now = [m for m in tree[kind] if m["name"] not in edits]
        for was, m in zip(stood, now):
            if m["name"] != was["name"]:       # what follows is out of step
                faults.append(f"{kind}: `{was['name']}` is gone or moved "
                              f"(`{m['name']}` stands in its place)")
                break
            due = _cells_due(parent, was) if kind == "per_layer" else None
            if m != was and ("workloads" in was
                             or m != dict(was, workloads=due)):
                faults.append(f"{kind}: `{was['name']}` was edited")
        else:
            faults += [f"{kind}: `{m['name']}` is gone"
                       for m in stood[len(now):]]
    return faults


def _parents_benchmark():
    """HEAD's BENCHMARK.json: the parent's in a PR's working tree. None
    where git gives nothing (an archive, a copy, no git)."""
    try:
        out = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                             capture_output=True, text=True, timeout=60)
        return json.loads(out.stdout) if out.returncode == 0 else None
    except (OSError, ValueError, subprocess.TimeoutExpired):
        return None


def test_the_tree_adds_to_the_parents_benchmark_and_edits_what_is_named():
    """Where git gives the parent's BENCHMARK.json (a PR's working tree),
    the tree's is an addition to it by `edits_of_what_stood`, with
    `BENCHMARK_EDITS` set aside. Where git gives nothing (an archive, a
    copy) there is nothing to check and the test passes: the count of passes
    does not move between a working tree and an archive."""
    parent = _parents_benchmark()
    if parent is not None:
        assert edits_of_what_stood(parent, BENCH, BENCHMARK_EDITS) == []


def _metric(name, moves=None, cells=None, **keys):
    m = dict({"name": name, "unit": "ms", "better": "lower",
              "source": "host_clock"}, **keys)
    m.update({"bound": 0.05} if moves is None else {
        "layer": "engine loop", "moves": moves})
    return m if cells is None else dict(m, workloads=cells)


# A benchmark small enough to read: the first token judged in cell `a.x`
# alone, so `parts` (no list) is due there and not in `b.y`.
SMALL = {
    "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
    "run_seconds": 50,
    "configs": [{"name": "a"}, {"name": "b"}],
    "workloads": [{"name": "a.x", "config": "a"}, {"name": "b.y", "config": "b"}],
    "end_to_end": [_metric("ttft", cells=["a.x"]), _metric("pace"),
                   _metric("setup_s")],
    "per_layer": [_metric("step", "pace"), _metric("parts", "ttft"),
                  _metric("keys", "pace", ["a.x"])],
}


def _without(bench, kind, name):
    return dict(bench, **{kind: [m for m in bench[kind]
                                 if m["name"] != name]})


def _with(bench, kind, name, **keys):
    return dict(bench, **{kind: [dict(m, **keys) if m["name"] == name else m
                                 for m in bench[kind]]})


@pytest.mark.parametrize("later,fault", [
    (lambda b: b, None),
    (lambda b: dict(b, per_layer=b["per_layer"] + [
        _metric("later", "pace", ["b.y"])]), None),
    (lambda b: dict(b, workloads=b["workloads"] + [{"name": "c.z"}],
                    configs=b["configs"] + [{"name": "c"}]), None),
    (lambda b: _with(b, "per_layer", "step", workloads=["a.x", "b.y"]), None),
    (lambda b: _with(b, "per_layer", "parts", workloads=["a.x"]), None),
    (lambda b: _with(b, "per_layer", "step", workloads=["a.x"]),
     "per_layer: `step` was edited"),
    (lambda b: _with(b, "per_layer", "parts", workloads=["a.x", "b.y"]),
     "per_layer: `parts` was edited"),
    (lambda b: _with(b, "per_layer", "keys", workloads=["a.x", "b.y"]),
     "per_layer: `keys` was edited"),
    (lambda b: _with(b, "per_layer", "step", unit="us"),
     "per_layer: `step` was edited"),
    (lambda b: _with(b, "per_layer", "step", moves="ttft"),
     "per_layer: `step` was edited"),
    (lambda b: _with(b, "end_to_end", "pace", bound=0.1),
     "end_to_end: `pace` was edited"),
    (lambda b: _with(b, "end_to_end", "pace", workloads=["a.x", "b.y"]),
     "end_to_end: `pace` was edited"),
    (lambda b: _with(b, "end_to_end", "ttft", workloads=["a.x", "b.y"]),
     "end_to_end: `ttft` was edited"),
    (lambda b: _without(b, "workloads", "a.x"),
     "workloads: `a.x` is gone or moved"),
    (lambda b: _without(b, "workloads", "b.y"), "workloads: `b.y` is gone"),
    (lambda b: _without(b, "per_layer", "parts"),
     "per_layer: `parts` is gone or moved"),
    (lambda b: dict(b, per_layer=b["per_layer"][::-1]),
     "per_layer: `step` is gone or moved"),
    (lambda b: dict(b, run_seconds=51), "`run_seconds` differs"),
    (lambda b: dict(b, paths=b["paths"] + ["tests"]), "`paths` differs"),
], ids=["equal", "an-entry-appended", "a-cell-appended",
        "the-list-of-the-cells-that-were-due", "the-list-where-one-was-due",
        "a-list-short-of-a-cell", "a-list-with-a-cell-that-was-not-due",
        "a-list-that-stood-grew", "a-unit", "a-moves", "a-bound",
        "an-end-to-end-metric-given-a-list", "a-judged-cell-more",
        "a-cell-dropped", "the-last-cell-dropped", "an-entry-dropped",
        "entries-reordered", "run-seconds", "paths"])
def test_the_rule_takes_additions_and_names_every_edit(later, fault):
    """`edits_of_what_stood` with `SMALL` as the parent: what a later PR may
    do passes, each thing it may not is named, and an edit passes once its
    entry's name is among `edits`, and only then."""
    tree = later(json.loads(json.dumps(SMALL)))
    faults = edits_of_what_stood(SMALL, tree)
    if fault is None:
        assert faults == []
        return
    assert len(faults) == 1 and faults[0].startswith(fault)
    assert edits_of_what_stood(SMALL, tree, ("setup_s",)) == faults
    if "was edited" in fault or "is gone" in fault and "step" not in fault:
        assert edits_of_what_stood(SMALL, tree, (fault.split("`")[1],)) == []


@pytest.mark.parametrize("later,fault", [
    (lambda b: _with(b, "per_layer", "prog.decode_step_ms", unit="us"),
     "per_layer: `prog.decode_step_ms` was edited"),
    (lambda b: _with(b, "per_layer", "prog.prefill_call_ms",
                     moves="tpot_ms.p90"),
     "per_layer: `prog.prefill_call_ms` was edited"),
    (lambda b: _without(b, "workloads", "qwen25-3b-bf16.agent-prefix"),
     "workloads: `qwen25-3b-bf16.agent-prefix` is gone or moved"),
], ids=["a-unit", "a-moves", "a-cell-dropped"])
def test_the_rule_names_an_edit_of_this_benchmark(later, fault):
    """The same on a copy of this tree's BENCHMARK.json, the tree's as the
    parent: the copy unedited passes, the edited one is named."""
    copy = json.loads(json.dumps(BENCH))
    assert edits_of_what_stood(BENCH, copy) == []
    (got,) = edits_of_what_stood(BENCH, later(copy))
    assert got.startswith(fault)


def test_each_name_in_benchmark_edits_is_needed():
    """In the working tree of the `benchmark` PR that wrote the tuple, each
    name is needed: with it taken out the tree breaks the rule against the
    parent, in that entry and no other. A name whose entry the parent and
    the tree hold alike, or lack alike, is an accepted PR's and is passed
    over (the tuple stands until the next `benchmark` PR replaces it), as
    is everything where git gives no parent."""
    parent = _parents_benchmark() or BENCH

    def entry(bench, name):
        return [m for k in KINDS for m in bench[k] if m["name"] == name]

    for name in BENCHMARK_EDITS:
        if entry(parent, name) != entry(BENCH, name):
            faults = edits_of_what_stood(parent, BENCH, tuple(
                n for n in BENCHMARK_EDITS if n != name))
            assert faults and all(f"`{name}`" in f for f in faults), faults


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


# The standing per-layer metrics that read keys or a pool of them. Each lists
# its cells (since PR 41), so a later cell whose model holds keys reads them
# through entries of its own, `<metric>.<mix>`: three lines that load the
# reader beside them (README, "Adding things").
READERS_OF_KEYS = ("kernel.paged_attn_ms", "kernel.paged_attn_kv_bw_pct",
                   "kernel.paged_attn_run_chunk_pct", "block.attn_ms",
                   "block.prefill_attn_ms", "kernel.prefill_attn_ms")
LATER_CELL = "later-model.later-mix"


def _drop_in(tmp: Path) -> dict:
    """What a later `model_config` PR brings, put into the copy under `tmp`
    by adding files and appending entries alone, and made the awkward case:
    the toy Mixtral (keys in the pool, no dense MLP and no shared expert, so
    no `blk.mlp`) as a configuration with its family, a mix, a cell, an
    entry of its own that lists the cell alone, a wrapper entry and file for
    each of the six readers of keys, and the one edit of a standing entry
    there is: `block.mlp_ms`, which named no cell and would find nothing to
    read here, gains the list of the cells that were due it. Returns the
    copy's BENCHMARK.json as written."""
    data = ROOT / "tests/chipbench/data"
    shutil.copytree(data / "configs/tiny-moe",
                    tmp / "chipbench/configs/later-model")
    shutil.copytree(data / "families/toy-moe",
                    tmp / "chipbench/families/later-family")
    cfg = tmp / "chipbench/configs/later-model/config.json"
    hf = json.loads(cfg.read_text())
    assert hf["model_type"] == "mixtral" and hf["num_key_value_heads"] >= 1
    hf["chipbench"]["family"] = "later-family"
    cfg.write_text(json.dumps(hf))
    shutil.copy(data / "traffic/tiny-chat.json",
                tmp / "chipbench/traffic/later-mix.json")
    (tmp / f"chipbench/cells/{LATER_CELL}.json").write_text(
        json.dumps({"config": "later-model", "traffic": "later-mix",
                    "rate_per_s": 2.0,
                    "limits": {"gap_max": 2.0, "gap_mean": 0.5}}))
    (tmp / "chipbench/layers/later.generated_total.py").write_text(
        "def read(ctx):\n"
        "    n = ctx['agent_stats'].get('total_generated')\n"
        "    return None if n is None else float(n)\n")
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    (mlp,) = [m for m in bench["per_layer"] if m["name"] == "block.mlp_ms"]
    assert "workloads" not in mlp
    mlp["workloads"] = _cells_due(bench, mlp)
    bench["configs"].append({
        "name": "later-model", "source": hf["chipbench"]["source"],
        "reduced": [], "why": "x",
        "file": "chipbench/configs/later-model/config.json"})
    bench["workloads"].append({
        "name": LATER_CELL, "config": "later-model",
        "traffic": "later-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "later.generated_total", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "out_tok_per_s", "workloads": [LATER_CELL]})
    for name in READERS_OF_KEYS:
        (standing,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert LATER_CELL not in standing["workloads"]
        bench["per_layer"].append(dict(standing, name=name + ".later-mix",
                                       workloads=[LATER_CELL]))
        (tmp / f"chipbench/layers/{name}.later-mix.py").write_text(
            "from pathlib import Path\n\nfrom chipbench import harness\n\n"
            "read = harness.load_file(Path(__file__).with_name("
            f"{name + '.py'!r})).read\n")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1) + "\n")
    return bench


# Left out of the walk, by what a test's name holds, because no entry or file
# a later PR adds can turn them red and together they take minutes: this test
# (`new_cell_config`, `a_pin_on`), the one that lists files through git
# (`files_under_paths`), the two that start run.py (`directory_with_only`,
# `without_a_tpu`), and the toys' numerics, which build weights or serve
# tokens at the toy sizes under tests/chipbench/data that no entry of
# BENCHMARK.json reaches (`reference_agrees`, `pure_function`,
# `parent_commits`, `control_reads_above`, `int8_control_fails`,
# `departs_from_the_equations`).
NOT_WALKED = ("new_cell_config", "a_pin_on", "files_under_paths",
              "directory_with_only", "without_a_tpu", "reference_agrees",
              "pure_function", "parent_commits", "control_reads_above", "int8_control_fails",
              "departs_from_the_equations")


def _pytest_in(tmp: Path, *args: str):
    """pytest in the copy under `tmp`, on the copy's tests and this tree's
    program."""
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-m", "not slow",
         "-p", "no:cacheprovider", "-p", "no:randomly", *args],
        cwd=tmp, capture_output=True, text=True, timeout=900,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT),
             "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1"})


def _walk(tmp: Path, *more: str):
    """Every test file of the benchmark (a glob: a file a later PR brings is
    walked the day it lands), run in the copy under `tmp`."""
    files = sorted(str(f.relative_to(tmp)) for f in
                   (tmp / "tests/chipbench").glob("test_chipbench_*.py"))
    assert len(files) >= 11
    return _pytest_in(tmp, "-k", "not (" + " or ".join(NOT_WALKED) + ")",
                      *more, *files)


def test_new_cell_config_mix_and_metric_are_files_and_one_entry_each(tmp_path):
    """A later PR adds a configuration (its `engine.json` with
    `engine_config`), its family under `chipbench/families/`, a mix, a cell,
    per-layer metrics and wrappers for the readers of keys by dropping files
    in and appending to BENCHMARK.json (`_drop_in`); no file that is there
    is edited, the harness resolves them by name, the copy's BENCHMARK.json
    is an addition to this one by the one rule (`edits_of_what_stood`), and
    EVERY test file under `paths`, none of which that PR may edit, passes in
    the copy: a test that pins the benchmark's tail fails here, in the PR
    that writes it, and not in the next one, which could not repair it."""
    _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    bench = _drop_in(tmp_path)
    assert edits_of_what_stood(BENCH, bench) == []
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
        "w = harness.load_reader(s, 'kernel.paged_attn_run_chunk_pct.later-mix')\n"
        "walked = w({'agent_stats': {'engine_trace': {'recent': "
        "{'walk_chunks': 8, 'walk_run_chunks': 2}}}})\n"
        "import jax\n"
        "tree = c.family.weights.param_shapes(c.hf, c.engine['weights'])\n"
        "print(json.dumps([str(harness.ROOT), c.rate, c.hf['hidden_size'], "
        "r({'agent_stats': {'total_generated': 5}}), "
        "'later.generated_total' in names, 'later.generated_total' in old, "
        "c.family.name, c.family.weights.__file__, "
        "list(tree['moe']['experts']['up_proj']['kernel'].shape), "
        "c.family.bytes.kv_bytes_per_token(c.hf), "
        "c.engine['engine_config'], c.decode_paths, walked, "
        "sorted(n for n in names if n.endswith('.later-mix')), "
        "sorted(n for n in old if n.endswith('.later-mix')), "
        "'block.mlp_ms' in names, 'block.mlp_ms' in old]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(ROOT),
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    (root, rate, hidden, value, in_new, in_old, family, weights_file, experts,
     kv_bytes, engine_config, decode_paths, walked, wrapped, wrapped_old,
     mlp_new, mlp_old) = json.loads(out.stdout.strip().split("\n")[-1])
    assert Path(root) == tmp_path
    assert (rate, hidden, value, in_new, in_old) == (2.0, 256, 5.0, True, False)
    assert family == "later-family" and Path(weights_file) == (
        tmp_path / "chipbench/families/later-family/weights.py")
    assert experts == [2, 4, 256, 128] and kv_bytes == 1024
    assert engine_config == {"admission_horizon": 4}
    assert decode_paths == {"paged_attention": "pallas"}
    # the readers of keys read in the new cell under its own names, and the
    # block no layer of the model enters is not due there
    assert walked == 25.0 and wrapped_old == [] and wrapped == sorted(
        n + ".later-mix" for n in READERS_OF_KEYS)
    assert (mlp_new, mlp_old) == (False, True)
    walk = _walk(tmp_path)
    assert walk.returncode == 0, walk.stdout[-6000:] + walk.stderr[-2000:]
    for ran in ("test_config_entry[later-model]",
                "test_workload_entry_and_its_files[later-model.later-mix]",
                "test_per_layer_entry_and_its_reader[later.generated_total]",
                "test_per_layer_entry_and_its_reader[block.attn_ms.later-mix]",
                "test_contract_signatures_and_independence[later-family]",
                "test_contract_shapes_and_bytes[later-model]",
                "test_the_two_engine_files_of_pr_23_hold_the_eleven_keys_"
                "alone[qwen25-7b-int8]",
                "test_a_family_is_found_under_the_second_search_path_and_"
                "loaded_once"):
        assert ran + " PASSED" in walk.stdout, ran
    walked_files = {line.split("::")[0] for line in walk.stdout.split("\n")
                    if "::" in line and " PASSED" in line}
    assert walked_files == {
        f"tests/chipbench/{f.name}" for f in
        (ROOT / "tests/chipbench").glob("test_chipbench_*.py")}
    after = {p: p.read_bytes() for p in before}
    assert after == before


PINS = {
    "the-last-entry": "assert BENCH['per_layer'][-1]['name'] == {last!r}",
    "the-number-of-entries": "assert len(BENCH['per_layer']) == {count}",
    "every-cell": ("assert [w['name'] for w in BENCH['workloads']] "
                   "== {cells!r}"),
}


@pytest.mark.parametrize("pin", sorted(PINS))
def test_a_pin_on_the_benchmarks_tail_fails_the_walk(tmp_path, pin):
    """The test of the test above: a test file that a PR brings, true of the
    benchmark on the day it is written and a pin on what comes after (the
    last entry, the number of entries, every cell), is written into the
    copy and passes there; after the drop-in the walk, which is green
    without that file, is red in that test (`-x`: the walk stops at its
    first failure, and the file sorts first)."""
    _copy_benchmark(tmp_path)
    (tmp_path / "tests/chipbench/test_chipbench_a_pin.py").write_text(
        "import json\nfrom pathlib import Path\n\n"
        "BENCH = json.loads((Path(__file__).resolve().parents[2] / "
        "'BENCHMARK.json').read_text())\n\n\n"
        "def test_the_entry_was_appended():\n    " + PINS[pin].format(
            last=BENCH["per_layer"][-1]["name"],
            count=len(BENCH["per_layer"]),
            cells=[w["name"] for w in BENCH["workloads"]]) + "\n")
    pinned = _pytest_in(tmp_path, "tests/chipbench/test_chipbench_a_pin.py")
    assert pinned.returncode == 0, pinned.stdout[-2000:]   # true as written
    _drop_in(tmp_path)
    walk = _walk(tmp_path, "-x")
    assert walk.returncode == 1
    assert "test_chipbench_a_pin.py::test_the_entry_was_appended FAILED" in (
        walk.stdout)


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
