"""99th percentile of the gaps between consecutive tokens of a request,
over every request, in the window."""
import numpy as np

UNIT = "ms"


def read(rec):
    return float(np.percentile(rec["itl_s"], 99)) * 1e3 if rec["itl_s"] \
        else None
