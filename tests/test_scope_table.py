"""benchmarks/scope_table.py: a device trace joined to the executable's own
HLO by instruction name, summed per traced scope."""

from benchmarks import scope_table as st

HLO = """HloModule jit_decode_multi

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = (f32[8]{0}, f32[8]{0:T(128)}) fusion(%x), kind=kLoop, calls=%f.1, metadata={op_name="jit(decode_multi)/moe.experts/moe.plan/reduce_sum"}
  %_moe_experts_impl.2 = f32[8]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_multi)/moe.experts/jit(_moe_experts_impl)/pallas_call"}
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%f.3, metadata={op_name="jit(decode_multi)/moe.route/logistic"}
  ROOT %copy.4 = f32[8]{0} copy(%fusion.3)
}
"""


def _ir(calls):
    mods, ops, t = [], [], 0.0
    for durs in calls:
        mods.append({"name": "jit_decode_multi(1)", "start": t,
                     "dur": sum(durs.values()) + 1e-6})
        for name, dur in durs.items():
            ops.append({"name": name, "start": t, "dur": dur})
            t += dur
        t += 1e-3
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def test_the_trace_is_summed_by_the_scope_an_instruction_was_traced_under():
    scopes = st.instruction_scopes(HLO)
    assert scopes["fusion.1"] == (
        "fusion", "jit(decode_multi)/moe.experts/moe.plan/reduce_sum")
    assert scopes["_moe_experts_impl.2"][0] == "custom-call"
    assert scopes["copy.4"] == ("copy", "")
    call = {"fusion.1": 2e-6, "_moe_experts_impl.2": 200e-6,
            "fusion.3": 1e-6, "copy.4": 3e-6, "fusion.99": 4e-6}
    t = st.table(_ir([call, dict(call, **{"fusion.1": 4e-6}), call]),
                 scopes, "decode_multi", per_call=2)
    assert t["calls"] == 3 and abs(t["known"] - 0.8) < 1e-9
    rows = {s: (round(ms, 6), n) for s, (ms, n) in t["rows"].items()}
    assert rows["moe.plan"] == (0.001, 0.5)            # the median call's
    assert rows["jit(_moe_experts_impl):kernel"] == (0.1, 0.5)
    assert rows["moe.route"] == (0.0005, 0.5)
    assert rows["-"] == (0.0015, 0.5) and rows["?"] == (0.002, 0.5)
    assert t["top"][0][0] == "_moe_experts_impl.2"
    # another program's executions are not this one's
    assert st.table(_ir([call]), scopes, "prefill_install", 1)["calls"] == 0
