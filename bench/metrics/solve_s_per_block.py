"""The solver's share of a block: Σ ``LayerReport.seconds`` (host time
around each linear's solve, ending in a host read of its loss) over the
window's calls, per block."""
UNIT = "s"


def read(rec):
    return sum(c["solve_s"] for c in rec["calls"]) / (
        len(rec["calls"]) * rec["blocks"])
