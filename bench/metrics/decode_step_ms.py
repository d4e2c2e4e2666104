"""Mean device time of one run of the decode program in the trace: the
loop-free program with the most device time (the engine's jitted decode
step, run once per step)."""
from bench import trace

UNIT = "ms"


def read(rec):
    row = trace.heaviest_program(rec.get("trace") or {}, loops=False)
    return None if row is None else 1e3 * row["seconds"] / row["count"]
