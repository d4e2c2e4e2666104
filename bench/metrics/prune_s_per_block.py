"""All the window's ``prune_model`` wall time over the blocks it pruned."""
UNIT = "s"


def read(rec):
    return sum(c["wall_s"] for c in rec["calls"]) / (
        len(rec["calls"]) * rec["blocks"])
