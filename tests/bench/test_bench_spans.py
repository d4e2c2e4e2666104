"""The program-span reduction (bench/spans.py) on the CPU: on hand-made
spans and device events with known answers, and on the recorded v5e trace
of a program that had no spans yet."""
from __future__ import annotations

import bench_testing
import pytest

from bench import spans, trace
from bench.spans import Span
from bench.trace import Ev

RECORDED = bench_testing.ROOT / "tests" / "bench" / "data" / \
    "small_serve.xplane.pb"
DEV, OPS = "/device:TPU:0", trace.OPS_LINE
MS = 1e6                                 # ns per ms


def S(name, start_ms, end_ms, **args):
    return Span(name, start_ms * MS, (end_ms - start_ms) * MS,
                tuple(args.items()))


def _pump(k, t, admit=False):
    """Pump ``k`` from ``t`` ms: an optional 20-ms admission, then decode
    dispatched over [+1, +3) ms, the host's wait for the sample until
    +15 ms, and the absorb loop until +17 ms."""
    out, a = [], t
    if admit:
        out.append(S("serve.admit", t, t + 20, uid=100 + k, slot=0,
                     tokens=32))
        a = t + 20
    return out + [S("serve.pump", t, a + 17, step=k),
                  S("serve.decode", a + 1, a + 3),
                  S("serve.sample", a + 3, a + 15),
                  S("serve.absorb", a + 15, a + 17)]


def _serve():
    # window [0, 100) ms; pumps start at 0, 20, 40 (with an admission:
    # it ends at 77) and 80
    return [S(trace.WINDOW, 0, 100),
            *_pump(0, 0), *_pump(1, 20), *_pump(2, 40, admit=True),
            *_pump(3, 80),
            S("serve.submit", -5, -4, uid=101),        # before the window
            S("serve.admit", -3, 10, uid=101, slot=1, tokens=32),
            S("serve.submit", 30, 31, uid=102)]


def test_step_host_time_skips_a_step_after_an_admission():
    # pump 0 → 1: decode ends at 23, sample ended at 15: 8 ms; pump 2 holds
    # an admission, so 1 → 2 is left out; 2 → 3: 83 − 75 = 8 ms
    r = spans.reduce(_serve())
    assert r["step_host_ms"] == pytest.approx(8.0)
    late = [s for s in _serve() if not (s.name == "serve.decode"
                                        and s.start == 81 * MS)]
    late.append(S("serve.decode", 81, 85))
    assert spans.reduce(late)["step_host_ms"] == pytest.approx(9.0)


def test_admissions_are_clipped_to_the_window():
    # the admission over [-3, 10) crosses the window's start: only the
    # 20-ms one inside counts
    assert spans.reduce(_serve())["admit_ms"] == pytest.approx(20.0)


def test_a_submit_outside_the_window_drops_its_admission_from_the_wait():
    # uid 101 was submitted before the window and is left out; uid 102 was
    # submitted at 30 ms and admitted at 40 ms
    assert spans.reduce(_serve())["queue_wait_ms"] == pytest.approx(10.0)
    early = [s if s.arg("uid") != 102 or s.name != "serve.submit"
             else S("serve.submit", -2, -1, uid=102) for s in _serve()]
    assert spans.reduce(early)["queue_wait_ms"] is None


def test_idle_time_is_named_by_the_innermost_program_span():
    evs = [S(trace.WINDOW, 0, 100), S("serve.pump", 0, 50),
           S("serve.sample", 10, 30), S("python.gc", 20, 30, generation=2)]
    dev = [Ev(DEV, OPS, "%fusion.1 = f32[8]{0} fusion()", 5e6, 10e6),
           Ev(DEV, OPS, "%fusion.2 = f32[8]{0} fusion()", 26e6, 4e6),
           Ev(DEV, OPS, "%fusion.3 = f32[8]{0} fusion()", 40e6, 10e6)]
    # idle: [0, 5) under the pump; [15, 26), middle 20.5 in the collection;
    # [30, 40) under the pump; [50, 100) outside every program span
    got = dict(spans.reduce(evs, dev)["idle_by_span"])
    assert got == pytest.approx({"serve.pump": 15e-3, "python.gc": 11e-3,
                                 spans.OUTSIDE: 50e-3})
    assert spans.reduce(evs)["idle_by_span"] is None


def test_counts_and_a_window_from_the_spans_themselves():
    r = spans.reduce([s for s in _serve() if s.name != trace.WINDOW])
    assert r["window_s"] == pytest.approx(102e-3)         # [-5, 97) ms
    assert r["counts"]["serve.pump"] == 4
    assert r["counts"]["serve.admit"] == 2
    with pytest.raises(ValueError, match="no program spans"):
        spans.reduce([])


def test_recorded_trace_of_a_program_without_spans():
    got = spans.load(str(RECORDED))
    assert [s.name for s in got] == [trace.WINDOW]
    r = spans.reduce(got, trace.load(str(RECORDED)))
    assert r["counts"] == {}
    assert r["step_host_ms"] is r["admit_ms"] is r["queue_wait_ms"] is None
    (name, idle), = r["idle_by_span"]
    tr = trace.reduce(trace.load(str(RECORDED)))
    assert name == spans.OUTSIDE
    assert idle == pytest.approx(tr["window_s"] - tr["busy_s"])


def test_cli(capsys):
    assert spans.main([]) == 2
    assert spans.main([str(RECORDED)]) == 0
    assert '"counts": {}' in capsys.readouterr().out
