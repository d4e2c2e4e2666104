"""90th percentile of ``t_first - t_submit`` over the window's requests."""
import numpy as np

UNIT = "ms"


def read(rec):
    return float(np.percentile(rec["ttft_s"], 90)) * 1e3 if rec["ttft_s"] \
        else None
