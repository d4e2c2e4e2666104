"""Set-up: weights, calibration set or engine, and the warm-up that
compiles (or loads from the persistent cache) every program the window
runs."""
UNIT = "s"


def read(rec):
    return rec["setup_s"]
