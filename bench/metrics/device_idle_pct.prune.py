"""Share of the traced prune call in which no operation ran on the
device."""
UNIT = "%"


def read(rec):
    tr = rec.get("trace")
    return None if tr is None else 100.0 * (1.0 - tr["busy_s"]
                                            / tr["window_s"])
