"""The `block.*` readers and their helper (chipbench/layers/blocks.py): the
walk of a trace file's device-plane event metadata on a hand-encoded
XSpace, the sums on synthetic traces, the readers on a `ctx` whose trace
file lies where run.py has the agent write it, and on a trace recorded on
the chip (`data/trace_blocks_tpu_v5e.json`)."""

import json
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH, SEARCH = harness.load_bench(ROOT / "BENCHMARK.json")
blocks = harness.load_file(ROOT / "chipbench/layers/blocks.py")
PID = 8237887495720909885
SCAN = "jit(decode_multi)/while/body/closed_call/"
BLOCK_METRICS = [m["name"] for m in BENCH["per_layer"]
                 if m["name"].startswith("block.")]


# ------------------------------------------- a hand-encoded .xplane.pb
def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


STATS = {1: "program_id", 2: "tf_op", 3: "hlo_category",
         4: "jit(decode_multi)/blk.head/dot_general:"}


def _stat(key: int, *, u64=None, text=None, ref=None) -> bytes:
    body = _int(1, key)
    if u64 is not None:
        body += _int(3, u64)
    if text is not None:
        body += _msg(5, text.encode())
    if ref is not None:
        body += _int(7, ref)
    return _msg(5, body)


def _plane(name: str, events: list) -> bytes:
    """`events`: (HLO line, program id or None, stats' bytes)."""
    body = _int(1, 7) + _msg(2, name.encode())
    body += _msg(3, b"\x12\x07XLA Ops" + b"\x00" * 64)   # a line: skipped
    for i, (line, program, stats) in enumerate(events, 1):
        meta = _int(1, i) + _msg(2, line.encode()) + stats
        if program is not None:
            meta += _stat(1, u64=program)
        body += _msg(4, _int(1, i) + _msg(2, meta))
    for sid, sname in STATS.items():
        body += _msg(5, _int(1, sid) + _msg(
            2, _int(1, sid) + _msg(2, sname.encode())))
    return _msg(1, body)


EVENTS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", PID,
     _stat(3, text="loop fusion")
     + _stat(2, text=SCAN + "blk.attn/dot_general:")),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", PID,
     _stat(2, ref=4)),                       # the name by reference
    ("%copy.3 = f32[8]{0} copy(f32[8]{0} %x)", PID, b""),    # no op_name
    ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %y), kind=kLoop", 99,
     _stat(2, text="jit(prefill_install)/blk.mlp/mul:")),
    ("SFence", None, b""),                   # no instruction of a program
]


def write_xplane(dirpath: Path, events=EVENTS) -> Path:
    dirpath.mkdir(parents=True, exist_ok=True)
    path = dirpath / "host.xplane.pb"
    path.write_bytes(_plane("/host:CPU", events)
                     + _plane("/device:TPU:0", events))
    return path


def test_the_walk_reads_each_instructions_op_name_off_the_device_plane(
        tmp_path):
    names = blocks.op_names(write_xplane(tmp_path))
    assert names == {
        str(PID): {"fusion.1": SCAN + "blk.attn/dot_general",
                   "fusion.2": "jit(decode_multi)/blk.head/dot_general",
                   "copy.3": ""},
        "99": {"fusion.1": "jit(prefill_install)/blk.mlp/mul"}}
    # only the device plane: the host plane of the same records adds none
    host = tmp_path / "host_only"
    host.mkdir()
    (host / "h.xplane.pb").write_bytes(_plane("/host:CPU", EVENTS))
    assert blocks.op_names(host / "h.xplane.pb") == {}


# -------------------------------------------------------------- the sums
def _ir(calls, program="decode_multi", pid=PID):
    mods, ops, t = [], [], 0.0
    for durs in calls:
        start = t
        for name, dur in durs:
            ops.append({"name": name, "start": t, "dur": dur})
            if name.split(".")[0] != "while":        # a body follows inside
                t += dur
        mods.append({"name": f"jit_{program}({pid})", "start": start,
                     "dur": t - start + 1e-7})
        t += 1e-3
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


NAMES = {str(PID): {
    "fusion.1": SCAN + "blk.attn/dot_general",
    "kernel.2": SCAN + "blk.attn/mla.decode/jit(_paged_attention_impl)/"
                       "pallas_call",
    "fusion.3": SCAN + "blk.moe/moe.experts/blk.mlp/mul",   # nested
    "fusion.4": SCAN + "blk.mlp/dot_general",
    "fusion.5": SCAN + "blk.head/dot_general",
    "fusion.6": SCAN + "blk.sample/argmax",
    "gather.7": SCAN + "gather",
    "copy.8": "",
}}
CALL = [("while.9", 300e-6), ("fusion.1", 10e-6), ("kernel.2", 30e-6),
        ("fusion.3", 50e-6), ("fusion.4", 20e-6), ("fusion.5", 8e-6),
        ("fusion.6", 2e-6), ("gather.7", 3e-6), ("copy.8", 1e-6)]


def test_block_of_is_the_outermost_block_of_an_op_name():
    assert blocks.block_of(NAMES[str(PID)]["kernel.2"]) == "attn"
    assert blocks.block_of(NAMES[str(PID)]["fusion.3"]) == "moe"
    assert blocks.block_of(SCAN + "gather") == blocks.UNSCOPED
    assert blocks.block_of("") == blocks.UNSCOPED


def test_a_call_is_summed_by_block_and_a_loop_is_its_body():
    calls = blocks.call_sums(_ir([CALL, CALL]), NAMES, "decode_multi")
    assert len(calls) == 2
    row = {k: (round(s * 1e6, 3), n) for k, (s, n) in calls[0].items()}
    assert row == {"attn": (40.0, 2), "moe": (50.0, 1), "mlp": (20.0, 1),
                   "head": (8.0, 1), "sample": (2.0, 1), "": (4.0, 2),
                   "(call)": (124.1, 0)}        # the `while` is in no row
    assert blocks.median_ms(calls, "attn", 8) == pytest.approx(0.005)
    assert blocks.median_ms(calls, "ssm", 8) is None
    assert blocks.names_a_block(calls)


def test_an_instruction_the_source_does_not_know_lands_in_no_block():
    call = CALL + [("fusion.77", 6e-6)]
    calls = blocks.call_sums(_ir([call]), NAMES, "decode_multi")
    assert calls[0][None] == [pytest.approx(6e-6), 1]
    assert sum(s for k, (s, _) in calls[0].items()
               if k not in (None, "(call)")) == pytest.approx(124e-6)


def test_an_instruction_is_found_under_another_program_of_the_same_name():
    """Two buckets of one program: the profiler keeps one metadata record
    for their identical lines, under the id of the first."""
    names = {"5": {"fusion.1": "jit(prefill_install)/blk.attn/dot"},
             "6": {"fusion.2": "jit(prefill_install)/blk.mlp/dot"}}
    ir = _ir([[("fusion.1", 2e-6), ("fusion.2", 3e-6)]], "prefill_install", 6)
    ir["/device:TPU:0"]["XLA Modules"] += [
        {"name": "jit_prefill_install(5)", "start": 1.0, "dur": 1e-6}]
    calls = blocks.call_sums(ir, names, "prefill_install")
    assert calls[0]["attn"][1] == 1 and calls[0]["mlp"][1] == 1
    # a program of another name lends nothing
    names = {"5": names["5"], "6": names["6"], "7": {"fusion.9": "x"}}
    ir = _ir([[("fusion.9", 2e-6)]], "prefill_install", 6)
    assert None in blocks.call_sums(ir, names, "prefill_install")[0]


# ----------------------------------------------------------- the readers
def _ctx(tmp_path, monkeypatch, names_events, calls, cell="a-cell",
         program="decode_multi"):
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    write_xplane(tmp_path / ".chipbench_work" / cell / "trace",
                 names_events)
    return {"trace": _ir(calls, program), "cell": cell,
            "engine": {"decode_horizon": 8}, "agent_stats": {},
            "hotpath": {}}


def _events(names):
    return [(f"%{inst} = f32[8]{{0}} fusion()", PID,
             _stat(2, text=op_name + ":") if op_name else b"")
            for inst, op_name in names[str(PID)].items()]


def _read(metric, ctx):
    return harness.load_reader(SEARCH, metric)(ctx)


def test_the_readers_read_a_run_and_walk_its_file_once(tmp_path, monkeypatch):
    call = CALL + [("fusion.77", 6e-6)]
    ctx = _ctx(tmp_path, monkeypatch, _events(NAMES), [call, call, call])
    walked = len(blocks.PARSED)
    got = {m: _read(m, ctx) for m in BLOCK_METRICS}
    assert len(blocks.PARSED) == walked + 1        # ten readers, one walk
    assert got["block.attn_ms"] == pytest.approx(0.005)
    assert got["block.mlp_ms"] == pytest.approx(0.0025)
    assert got["block.moe_ms"] == pytest.approx(0.00625)
    assert got["block.head_ms"] == pytest.approx(0.001)
    assert got["block.sample_ms"] == pytest.approx(0.00025)
    assert got["block.unscoped_ms"] == pytest.approx(0.0005)
    assert got["block.ssm_ms"] is None             # the family has none
    assert got["block.known_ops_pct"] == pytest.approx(100 * 8 / 9)
    assert got["block.prefill_attn_ms"] is None    # no prefill was traced
    # the blocks, what lies in none and what is not known are the call
    parts = sum(v for m, v in got.items() if m.endswith("_ms") and v)
    assert parts + 6e-3 / 8 == pytest.approx(130e-3 / 8)


def test_a_prefill_is_read_per_call_over_all_its_buckets(tmp_path,
                                                          monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, _events(NAMES), [CALL, CALL],
               program="prefill_install_nc")
    assert _read("block.prefill_attn_ms", ctx) == pytest.approx(0.040)
    assert _read("block.attn_ms", ctx) is None     # no decode call


@pytest.mark.parametrize("metric", BLOCK_METRICS)
def test_a_trace_that_names_no_block_gives_nothing_not_zero(
        tmp_path, monkeypatch, metric):
    """The parent's side: every op known, every op_name without a block."""
    names = {str(PID): {k: v.replace("blk.", "") for k, v in
                        NAMES[str(PID)].items()}}
    ctx = _ctx(tmp_path, monkeypatch, _events(names), [CALL, CALL],
               cell="parent-" + metric)
    assert _read(metric, ctx) is None
    # and a prefill alike
    ctx = _ctx(tmp_path, monkeypatch, _events(names), [CALL],
               cell="parent-prefill-" + metric, program="prefill_install")
    assert _read(metric, ctx) is None


@pytest.mark.parametrize("metric", BLOCK_METRICS)
def test_without_a_trace_file_a_reader_gives_nothing(tmp_path, monkeypatch,
                                                     metric):
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    ctx = {"trace": _ir([CALL]), "cell": "no-file",
           "engine": {"decode_horizon": 8}}
    assert _read(metric, ctx) is None
    assert _read(metric, {"trace": None, "cell": "no-file"}) is None


def test_the_helpers_prefix_is_the_programs():
    from xllm_service_tpu.models import base

    assert blocks.PREFIX == base.BLOCK_PREFIX
    listed = {m[len("block."):-len("_ms")] for m in BLOCK_METRICS
              if m.endswith("_ms")} - {"unscoped", "prefill_attn"}
    assert listed == set(base.BLOCKS)


# ------------------------------------------------ a trace off the chip
def _chip_fixture():
    """(trace, op_names, horizon) of `data/trace_blocks_tpu_v5e.json`: an
    op event is (index into `instructions`, start in ns as a difference
    from the event before, duration in ps); an instruction's op_name an
    index into `op_name_strings`."""
    import itertools

    d = json.loads((ROOT / "tests/chipbench/data/trace_blocks_tpu_v5e.json")
                   .read_text())
    ops = d["ops"]
    events = [{"name": d["instructions"][i], "start": t * 1e-9,
               "dur": ps * 1e-12} for i, t, ps in zip(
        ops["instruction"], itertools.accumulate(ops["start_ns_delta"]),
        ops["dur_ps"])]
    names = {pid: {inst: d["op_name_strings"][k] for inst, k in table.items()}
             for pid, table in d["op_names"].items()}
    trace = {d["plane"]: {"XLA Modules": d["modules"], "XLA Ops": events}}
    return trace, names, d["decode_horizon"]


def test_a_call_recorded_on_the_chip_sums_to_its_blocks():
    """One `decode_multi` execution and one `prefill_install` of
    `qwen25-7b-int8.chat` on a TPU v5e, this PR's tree, with the op_names
    the profiler wrote (my chip run, PR 39, call B, seed 3900000401; that
    run's medians over 38 calls: attn 2.536, mlp 7.737, head 0.732, sample
    0.075, unscoped 0.271 of a step of 11.361 ms). The numbers are this
    call's own, pasted from the chip's trace."""
    trace, names, horizon = _chip_fixture()
    (call,) = blocks.call_sums(trace, names, "decode_multi")
    ms = {k: 1e3 * s / horizon for k, (s, _) in call.items()}
    assert ms == pytest.approx({
        "attn": 2.536226, "mlp": 7.737597, "head": 0.731583,
        "sample": 0.075368, "": 0.271278, "(call)": 11.360825}, rel=1e-5)
    assert {k: n for k, (_, n) in call.items()} == {
        "attn": 4478, "mlp": 1584, "head": 34, "sample": 115, "": 7495,
        "(call)": 0}                      # every op is known: no None
    parts = sum(v for k, v in ms.items() if k != "(call)")
    assert parts == pytest.approx(ms["(call)"], rel=2e-3)   # within 0.2%
    # the kernel is inside its block: 28 layers x 8 steps
    kernel = blocks.call_sums(
        trace, names, "decode_multi",
        lambda n: "kernel" if "jit(_paged_attention_impl)" in n else "rest")
    assert kernel[0]["kernel"][1] == 28 * 8
    assert 1e3 * kernel[0]["kernel"][0] / horizon < ms["attn"]
    # the prefill that followed it, ms a call
    (pre,) = blocks.call_sums(trace, names, "prefill_install")
    assert {k: round(1e3 * s, 3) for k, (s, _) in pre.items()} == {
        "attn": 20.019, "mlp": 63.046, "head": 0.724, "sample": 0.049,
        "": 0.211, "(call)": 84.074}


# -------------------------------------------------- the padding counter
@pytest.mark.parametrize("recent,want", [
    ({"prefill_padded_tokens": 300, "prompt_tokens": 1000,
      "prefix_hit_tokens": 300}, 30.0),
    ({"prefill_padded_tokens": 0, "prompt_tokens": 10,
      "prefix_hit_tokens": 0}, 0.0),
    ({"prompt_tokens": 10, "prefix_hit_tokens": 0}, None),   # no counter
    ({"prefill_padded_tokens": 0, "prompt_tokens": 0,
      "prefix_hit_tokens": 0}, None),                        # no admission
    (None, None),
])
def test_prefill_padding_is_the_padded_share_of_the_rows_computed(recent,
                                                                  want):
    stats = {} if recent is None else {"engine_trace": {"recent": recent}}
    got = _read("engine.prefill_padding_pct", {"agent_stats": stats})
    assert got == (want if want is None else pytest.approx(want))
