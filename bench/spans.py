"""The program's own host spans in a profiler trace, and what they measure.

The serving engine and the prune job open ``jax.profiler.TraceAnnotation``
spans at each layer boundary (``serve.*``, ``prune.*``) and the
``repro.obs`` collection hook one per garbage collection (``python.gc``).
They land in the same ``.xplane.pb`` as the device's ops, on the same
clock.  ``trace.load`` keeps names and times only; a span's arguments
(``uid``, ``slot``, ``tokens``, ``step``, ``block``, ``path``,
``generation``) arrive as event stats, which ``load`` here keeps.

``reduce`` gives, over the traced window (the ``bench.traced_window``
span; the whole trace when there is none):

* ``step_host_ms``: host time per decode step with nothing enqueued on the
  device: over consecutive pumps with no ``serve.admit`` in the later one,
  the mean of the end of ``serve.decode`` in pump k+1 less the end of
  ``serve.sample`` in pump k;
* ``admit_ms``: the mean duration of the ``serve.admit`` spans wholly
  inside the window;
* ``queue_wait_ms``: the mean of ``serve.admit`` start less ``serve.submit``
  start of the same ``uid``, over admissions whose submit is in the window;
* ``idle_by_span``: the device-idle seconds of the window grouped by the
  innermost program span that covers each idle stretch's middle, the rest
  under ``(outside program spans)``;
* ``counts``: how many of each program span the window holds.

A number with nothing to read is None: a trace of a program without the
spans gives None throughout and an empty ``counts``.

    python3 bench/spans.py <file.xplane.pb>

prints the reduction of one trace as JSON.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from collections import Counter, defaultdict
from pathlib import Path

import numpy as np

if __package__ in (None, ""):                   # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

PROGRAM = ("serve.", "prune.", "python.gc")
OUTSIDE = "(outside program spans)"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float      # ns, on the trace's common clock
    dur: float        # ns
    args: tuple = ()  # (name, value) pairs

    @property
    def end(self) -> float:
        return self.start + self.dur

    def arg(self, key: str):
        return dict(self.args).get(key)


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def load(path: str) -> list[Span]:
    """The program spans and the traced-window span of an ``.xplane.pb``,
    with their arguments, from every host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if is_program(e.name) or e.name == trace.WINDOW:
                    out.append(Span(e.name, float(e.start_ns),
                                    float(e.duration_ns), tuple(e.stats)))
    return out


def window(spans: list[Span]) -> tuple[float, float]:
    if any(s.name == trace.WINDOW for s in spans):
        return trace.window_of(spans)
    if not spans:
        raise ValueError("trace holds no program spans")
    return min(s.start for s in spans), max(s.end for s in spans)


def _within(outer: Span, spans: list[Span]) -> list[Span]:
    return [s for s in spans if s.start >= outer.start and s.end <= outer.end]


def step_host_ms(spans: list[Span], w0: float, w1: float) -> float | None:
    pumps = sorted((s for s in spans if s.name == "serve.pump"
                    and s.start >= w0 and s.end <= w1),
                   key=lambda s: s.start)
    kids = {"serve.admit": [], "serve.decode": [], "serve.sample": []}
    for s in sorted(spans, key=lambda s: s.start):
        if s.name in kids:
            kids[s.name].append(s)
    inside = [{k: _within(p, v) for k, v in kids.items()} for p in pumps]
    gaps = [nxt["serve.decode"][0].end - cur["serve.sample"][-1].end
            for cur, nxt in zip(inside, inside[1:])
            if cur["serve.sample"] and nxt["serve.decode"]
            and not nxt["serve.admit"]]
    return 1e-6 * float(np.mean(gaps)) if gaps else None


def admit_ms(spans: list[Span], w0: float, w1: float) -> float | None:
    durs = [s.dur for s in spans if s.name == "serve.admit"
            and s.start >= w0 and s.end <= w1]
    return 1e-6 * float(np.mean(durs)) if durs else None


def queue_wait_ms(spans: list[Span], w0: float, w1: float) -> float | None:
    submitted = {s.arg("uid"): s.start for s in spans
                 if s.name == "serve.submit" and w0 <= s.start <= w1}
    waits = []
    for s in sorted((s for s in spans if s.name == "serve.admit"),
                    key=lambda s: s.start):
        t = submitted.pop(s.arg("uid"), None)    # the first admission only
        if t is not None and s.start >= t:
            waits.append(s.start - t)
    return 1e-6 * float(np.mean(waits)) if waits else None


def idle_by_span(spans: list[Span], events: list[trace.Ev], w0: float,
                 w1: float) -> list[list] | None:
    """Device-idle seconds of device 0 within [w0, w1], by the innermost
    program span covering each idle stretch's middle."""
    planes = trace.device_planes(events)
    if not planes:
        return None
    busy = trace._union([(max(e.start, w0), min(e.end, w1)) for e in events
                         if e.plane == planes[0] and e.line == trace.OPS_LINE
                         and e.end > w0 and e.start < w1])
    edges = [w0] + [x for se in busy for x in se] + [w1]
    prog = [s for s in spans if is_program(s.name)
            and s.end > w0 and s.start < w1]
    named: dict[str, float] = defaultdict(float)
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        cover = [s for s in prog if s.start <= mid <= s.end]
        name = min(cover, key=lambda s: s.dur).name if cover else OUTSIDE
        named[name] += (b - a) * 1e-9
    return sorted(([k, v] for k, v in named.items()), key=lambda kv: -kv[1])


def reduce(spans: list[Span], events: list[trace.Ev] | None = None) -> dict:
    """The span metrics of the traced window; ``events`` (``trace.load``
    of the same file) adds ``idle_by_span``."""
    w0, w1 = window(spans)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "step_host_ms": step_host_ms(spans, w0, w1),
        "admit_ms": admit_ms(spans, w0, w1),
        "queue_wait_ms": queue_wait_ms(spans, w0, w1),
        "idle_by_span": (idle_by_span(spans, events, w0, w1)
                         if events else None),
        "counts": dict(Counter(s.name for s in spans if is_program(s.name)
                               and s.end > w0 and s.start < w1)),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        print("usage: python3 bench/spans.py <file.xplane.pb>",
              file=sys.stderr)
        return 2
    print(json.dumps(reduce(load(argv[0]), trace.load(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
