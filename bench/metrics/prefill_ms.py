"""Mean device time of one run of the prefill program in the trace: the
program with a loop that has the most device time (the engine's jitted
prefill, a loop over the prompt, run once per admission)."""
from bench import trace

UNIT = "ms"


def read(rec):
    row = trace.heaviest_program(rec.get("trace") or {}, loops=True)
    return None if row is None else 1e3 * row["seconds"] / row["count"]
