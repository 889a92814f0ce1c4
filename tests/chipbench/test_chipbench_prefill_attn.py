"""`kernel.prefill_attn_ms` (chipbench/layers/kernel.prefill_attn_ms.py):
the prefill kernel's events inside each `prefill_install*` execution of a
synthetic trace, and nothing on a trace without the kernel (the parent)."""

from pathlib import Path

import pytest

from chipbench import harness
from chipbench_entries import but_its_list, due, per_layer, stands_after

ROOT = Path(__file__).resolve().parents[2]
BENCH, SEARCH = harness.load_bench(ROOT / "BENCHMARK.json")
METRIC = "kernel.prefill_attn_ms"


def _read(metric, ctx):
    return harness.load_reader(SEARCH, metric)(ctx)


def _ir(calls):
    """`calls`: (program, [(op, seconds)]) in time order, one device plane."""
    mods, ops, t = [], [], 0.0
    for n, (program, durs) in enumerate(calls):
        start = t
        for name, dur in durs:
            ops.append({"name": name, "start": t, "dur": dur})
            t += dur
        mods.append({"name": f"jit_{program}({100 + n})", "start": start,
                     "dur": t - start + 1e-7})
        t += 1e-3
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def _prefill(kernel_us, layers=3, name="_prefill_attention_impl"):
    """One prefill call: a product, the kernel and the page write a layer."""
    return [op for l in range(layers) for op in (
        (f"fusion.{l}", 40e-6), (f"{name}.{l + 7}", kernel_us * 1e-6),
        (f"scatter.{l}", 5e-6))]


DECODE = ("decode_multi", [("fusion.1", 30e-6),
                           ("_paged_attention_impl.3", 9e-6)])


def test_the_kernels_events_are_summed_per_prefill_call():
    ir = _ir([("prefill_install", _prefill(10)), DECODE,
              ("prefill_install", _prefill(30)), DECODE,
              ("prefill_install", _prefill(20))])
    # three layers a call; the median call's kernels: 3 x 20 us
    assert _read(METRIC, {"trace": ir}) == pytest.approx(0.060)
    # what shares `block.prefill_attn_ms` with it is not in it
    assert _read("prog.prefill_call_ms", {"trace": ir}) == pytest.approx(
        (3 * 65e-6 + 1e-7) * 1e3)


def test_every_prefill_install_program_is_pooled():
    ir = _ir([("prefill_install", _prefill(10)),
              ("prefill_install_sp", _prefill(50)),
              ("prefill_chunk", _prefill(500)),         # another program
              ("prefill_install", _prefill(20))])
    assert _read(METRIC, {"trace": ir}) == pytest.approx(0.060)


@pytest.mark.parametrize("ctx", [
    {}, {"trace": None}, {"trace": {}},
    {"trace": _ir([DECODE, DECODE])},
    # the parent: prefill attends through XLA, no event bears the name
    {"trace": _ir([("prefill_install", _prefill(10, name="fusion")),
                   DECODE])},
], ids=["no-trace-key", "no-trace", "empty-trace", "no-prefill",
        "prefill-without-the-kernel"])
def test_nothing_to_read_is_none_never_zero(ctx):
    assert _read(METRIC, ctx) is None


def test_neither_kernels_reader_takes_the_others_events():
    """The decode kernel's pattern (`^_?paged_attention`) does not match
    the prefill kernel's jit, nor this reader's the decode kernel."""
    mixed = ("decode_multi", [("_prefill_attention_impl.5", 80e-6),
                              ("_paged_attention_impl.3", 9e-6)])
    ir = _ir([mixed, ("prefill_install",
                      _prefill(10) + [("_paged_attention_impl.9", 70e-6)])])
    ctx = {"trace": ir, "engine": {"decode_horizon": 1}}
    assert _read("kernel.paged_attn_ms", ctx) == pytest.approx(0.009)
    assert _read(METRIC, ctx) == pytest.approx(0.030)


def test_the_entry_is_a_kernels_metric_of_every_cell():
    """The entry as PR 40 wrote it but for its list (it named no cell then;
    since a cell whose model holds no key it lists the cells that read it),
    appended after the entries that stood before it, and due in each cell
    of its list, the four it was written for among them."""
    entry = per_layer(BENCH, METRIC)
    assert but_its_list(entry) == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "gap_ms.p95"}
    assert stands_after(BENCH, [METRIC], [
        "block.prefill_attn_ms", "block.known_ops_pct",
        "engine.prefill_padding_pct"])       # appended, nothing moved
    cells = entry.get("workloads", [w["name"] for w in BENCH["workloads"]])
    assert cells[:4] == [w["name"] for w in BENCH["workloads"][:4]]
    assert all(due(BENCH, METRIC, cell) for cell in cells)
