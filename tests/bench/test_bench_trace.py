"""The trace reduction on the CPU: on hand-made events with known answers,
and on a small trace recorded on a TPU v5e (a tiny 2:4 engine, one layer,
8 slots, prompts of 8: two requests admitted, then two decode steps)."""
from __future__ import annotations

import bench_testing
import pytest

from bench import harness, peaks, trace
from bench.trace import Ev

RECORDED = bench_testing.ROOT / "tests" / "bench" / "data" / \
    "small_serve.xplane.pb"
DEV, OPS, MODS = "/device:TPU:0", trace.OPS_LINE, trace.MODULES_LINE
NM = ("%nm_matmul.3 = bf16[8,128]{1,0} custom-call(bf16[4,8,64]{2,1,0} %a, "
      "bf16[2,128,64]{2,1,0} %b, s8[1,128,64]{2,1,0} %c), "
      "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
      "{bf16[9,9,9]{2,1,0}}")


def _events():
    host = "/host:CPU"
    return [
        Ev(host, "python", trace.WINDOW, 0, 100),
        Ev(host, "python", "bench.pump", 0, 100),
        Ev(host, "python", "PjitFunction(step)", 40, 20),
        Ev(DEV, MODS, "jit_step(11)", 10, 30),
        Ev(DEV, OPS, NM, 10, 10),
        Ev(DEV, OPS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 15, 10),
        Ev(DEV, MODS, "jit_prefill(22)", 60, 30),
        Ev(DEV, OPS, "%while.2 = (s32[]) while(s32[] %i)", 60, 30),
        Ev(DEV, OPS, "%late.9 = f32[8]{0} fusion()", 95, 20),   # clipped
    ]


def test_busy_is_the_union_of_ops_clipped_to_the_window():
    r = trace.reduce(_events(), kernels={"nm_matmul": "nm_matmul"})
    # ops cover [10, 25), [60, 90), [95, 100) of the window [0, 100)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["devices"] == 1


def test_programs_loops_and_kernel_calls():
    r = trace.reduce(_events(), kernels={"nm_matmul": "nm_matmul"})
    assert r["programs"]["jit_step(11)"] == {
        "name": "jit_step", "count": 1, "seconds": pytest.approx(30e-9),
        "loops": False}
    assert r["programs"]["jit_prefill(22)"]["loops"]
    (dur, shapes), = r["kernel_calls"]["nm_matmul"]
    assert dur == pytest.approx(10e-9)
    assert shapes == [("bf16", (8, 128)), ("bf16", (4, 8, 64)),
                      ("bf16", (2, 128, 64)), ("s8", (1, 128, 64))]
    assert trace.heaviest_program(r, loops=True)["name"] == "jit_prefill"
    assert trace.heaviest_program(r, loops=False)["name"] == "jit_step"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = trace.reduce(_events())
    gaps = dict(r["idle_gaps"])
    # idle: [0, 10) and [90, 95) under bench.pump only; [25, 60), whose
    # middle lies in the dispatch span [40, 60), under that span
    assert gaps["PjitFunction(step)"] == pytest.approx(35e-9)
    assert gaps["bench.pump"] == pytest.approx(15e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_a_trace_without_its_window_or_device_is_refused():
    with pytest.raises(ValueError, match="no 'bench.traced_window'"):
        trace.reduce([e for e in _events() if e.name != trace.WINDOW])
    with pytest.raises(ValueError, match="no device ops"):
        trace.reduce([e for e in _events() if e.plane != DEV])


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(trace.load(str(RECORDED)),
                        kernels={"nm_matmul": "nm_matmul"})


def test_recorded_trace_programs(recorded):
    decode = trace.heaviest_program(recorded, loops=False)
    prefill = trace.heaviest_program(recorded, loops=True)
    assert (decode["name"], decode["count"]) == ("jit__unknown", 2)
    assert (prefill["name"], prefill["count"]) == ("jit__unknown", 2)
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    gaps = sum(s for _, s in recorded["idle_gaps"])
    assert gaps <= recorded["window_s"] - recorded["busy_s"] + 1e-9


def test_recorded_trace_kernel_calls(recorded):
    """One layer: 7 kernel calls per decode step with the 8 slots as rows,
    7 per prompt token of each admission with one row."""
    calls = recorded["kernel_calls"]["nm_matmul"]
    rows = sorted({shapes[0][1][0] for _, shapes in calls})
    assert rows == [1, 8]
    assert sum(s[0][1][0] == 8 for _, s in calls) == 2 * 7
    assert sum(s[0][1][0] == 1 for _, s in calls) == 2 * 8 * 7
    rec = {"trace": recorded, "peak": peaks.peaks("TPU v5 lite")}
    share = harness.metric("nm_matmul_roofline").read(rec)
    assert 0 < share <= 100
    assert rec["notes"]["nm_matmul_roofline"] == "bound: memory"
    for m in ("decode_step_ms", "prefill_ms", "device_idle_pct.serve"):
        assert harness.metric(m).read(rec) > 0
