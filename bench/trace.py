"""Profiler trace capture and its reduction to device metrics.

A traced run wraps the part of its window it traces in a host span named
``WINDOW``; the reduction clips every device event to that span.  TPU
device planes are named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per executed operation (a Pallas kernel is one operation,
named after its kernel function), and ``XLA Modules`` one event per run
of a compiled program, named ``jit_<function>(<id>)``.  Host planes hold
the spans the harness and JAX's dispatch write (``PjitFunction(<fn>)``).

* busy: the union of the op intervals of each device, averaged over the
  devices used; idle share = 1 − busy / window;
* per program (module name with its fingerprint): runs wholly inside the
  window, their device time, and whether the program holds a loop
  (a ``%while`` op);
* per kernel: every call inside the window, with its device time and the
  shapes of its result and operands, which the op's name spells out;
* idle gaps: each stretch of the window in which no op runs on device 0,
  named by the innermost host span that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"


@dataclasses.dataclass(frozen=True)
class Ev:
    plane: str
    line: str
    name: str
    start: float      # ns, on the trace's common clock
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def options():
    """Profiler options: host spans, no Python function tracing (it would
    slow every Python call of the server it traces), no HLO protos."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    o.enable_hlo_proto = False
    return o


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> list[Ev]:
    """Every event of every line of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Ev(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns)))
    return out


def module_name(event_name: str) -> str:
    """``jit__decode_fn(123)`` → ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_shapes(op: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of an op's result, then of each operand, read from
    the HLO text a TPU trace names the op by."""
    head = op.split("custom_call_target")[0]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", head)]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(events: list[Ev]) -> tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    w = max(spans, key=lambda e: e.dur)
    return w.start, w.end


def device_planes(events: list[Ev]) -> list[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE})


def reduce(events: list[Ev], kernels: dict[str, str] | None = None,
           top: int = 10) -> dict:
    """Device metrics of the traced window (seconds).

    ``kernels`` maps a kernel's name to the name its ops carry in the
    trace (``%<name>.<n> = ...``); each call is kept with its duration and
    the shapes of its result and operands.
    """
    kernels = kernels or {}
    w0, w1 = window_of(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("trace holds no device ops")
    clip = [e for e in events if e.end > w0 and e.start < w1]

    busy, gaps0 = [], []
    for p in planes:
        iv = _union([(max(e.start, w0), min(e.end, w1)) for e in clip
                     if e.plane == p and e.line == OPS_LINE])
        busy.append(sum(e - s for s, e in iv))
        if p == planes[0]:
            edges = [w0] + [x for se in iv for x in se] + [w1]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]

    ops0 = sorted((e for e in clip
                   if e.plane == planes[0] and e.line == OPS_LINE),
                  key=lambda e: e.start)
    starts = np.array([e.start for e in ops0])
    ops: dict[str, float] = defaultdict(float)
    for e in ops0:
        ops[e.name] += e.dur
    # programs (by module name with its fingerprint) run wholly inside the
    # window, and whether each holds a loop
    programs: dict[str, dict] = {}
    for m in clip:
        if m.plane != planes[0] or m.line != MODULES_LINE or m.start < w0 \
                or m.end > w1:
            continue
        row = programs.setdefault(m.name, {"name": module_name(m.name),
                                           "count": 0, "seconds": 0.0,
                                           "loops": False})
        row["count"] += 1
        row["seconds"] += m.dur * 1e-9
        if not row["loops"]:
            lo, hi = np.searchsorted(starts, [m.start, m.end])
            row["loops"] = any(e.name.startswith("%while")
                               for e in ops0[lo:hi])
    calls = {k: [(e.dur * 1e-9, op_shapes(e.name)) for e in ops0
                 if e.start >= w0 and e.end <= w1 and re.match(
                     rf"%{re.escape(pat)}(\.\d+)? =", e.name)]
             for k, pat in kernels.items()}

    host = [e for e in clip if not e.plane.startswith(DEVICE_PREFIX)
            and e.name != WINDOW and e.dur > 0]
    hs = np.array([h.start for h in host])
    he = np.array([h.end for h in host])
    hd = np.array([h.dur for h in host])
    named: dict[str, float] = defaultdict(float)
    for s, e in gaps0:
        mid = 0.5 * (s + e)
        cover = np.flatnonzero((hs <= mid) & (he >= mid))
        name = (host[cover[np.argmin(hd[cover])]].name if cover.size
                else "(no span)")
        named[name] += e - s

    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(planes),
        "programs": programs,
        "kernel_calls": calls,
        "device_ops": sorted(([k, v * 1e-9] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * 1e-9] for k, v in named.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def heaviest_program(reduced: dict, loops: bool) -> dict | None:
    """The program with the most device time among those that hold a loop
    (``loops``) or among those that do not.  Serving runs its decode step
    as a loop-free program every step and its prefill as one loop over the
    prompt per admission, so these find them whatever they are named."""
    rows = [r for r in reduced.get("programs", {}).values()
            if r["loops"] == loops and r["count"]]
    return max(rows, key=lambda r: r["seconds"]) if rows else None
