"""The prune driver's share of a block: call wall time less the solves
(calibration forwards, Hessian capture, propagation), per block."""
UNIT = "s"


def read(rec):
    return sum(c["wall_s"] - c["solve_s"] for c in rec["calls"]) / (
        len(rec["calls"]) * rec["blocks"])
