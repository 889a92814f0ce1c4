"""benchmarks/scope_table.py: a device trace summed per traced scope, by
the `op_name`s the trace itself holds (the benchmark's helper,
chipbench/layers/blocks.py, does the summing)."""

from benchmarks import scope_table as st

PID = "8237887495720909885"
NAMES = {PID: {
    "fusion.1": "jit(decode_multi)/while/body/closed_call/blk.moe/"
                "moe.experts/moe.plan/reduce_sum",
    "_moe_experts_impl.2": "jit(decode_multi)/while/body/closed_call/blk.moe/"
                           "moe.experts/jit(_moe_experts_impl)/pallas_call",
    "fusion.3": "jit(decode_multi)/while/body/closed_call/blk.moe/moe.route/"
                "logistic",
    "fusion.5": "jit(decode_multi)/while/body/closed_call/blk.moe/add",
    "copy.4": "",
}}


def _ir(calls):
    mods, ops, t = [], [], 0.0
    for durs in calls:
        mods.append({"name": f"jit_decode_multi({PID})", "start": t,
                     "dur": sum(durs.values()) + 1e-6})
        for name, dur in durs.items():
            ops.append({"name": name, "start": t, "dur": dur})
            t += dur
        t += 1e-3
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def test_the_trace_is_summed_by_the_scope_an_instruction_was_traced_under():
    assert st.MARKERS[-7:] == ("blk.attn", "blk.mlp", "blk.moe", "blk.ssm",
                               "blk.ret", "blk.head", "blk.sample")
    assert st.scope_of(NAMES[PID]["fusion.1"]) == "moe.plan"
    assert st.scope_of(NAMES[PID]["fusion.5"]) == "blk.moe"
    assert st.scope_of("") == "-"
    call = {"fusion.1": 2e-6, "_moe_experts_impl.2": 200e-6,
            "fusion.3": 1e-6, "copy.4": 3e-6, "fusion.99": 4e-6,
            "fusion.5": 5e-6}
    t = st.table(_ir([call, dict(call, **{"fusion.1": 4e-6}), call]),
                 NAMES, "decode_multi", per_call=2)
    assert t["calls"] == 3 and abs(t["known"] - 5 / 6) < 1e-9
    rows = {s: (round(ms, 6), n) for s, (ms, n) in t["rows"].items()}
    assert rows["moe.plan"] == (0.001, 0.5)            # the median call's
    assert rows["jit(_moe_experts_impl):kernel"] == (0.1, 0.5)
    assert rows["moe.route"] == (0.0005, 0.5)
    assert rows["blk.moe"] == (0.0025, 0.5)     # the block outside them
    assert rows["-"] == (0.0015, 0.5) and rows["?"] == (0.002, 0.5)
    assert t["top"][0][0].endswith("pallas_call")
    assert t["top"][0][2] == "jit(_moe_experts_impl):kernel"
    # another program's executions are not this one's
    assert st.table(_ir([call]), NAMES, "prefill_install", 1)["calls"] == 0
