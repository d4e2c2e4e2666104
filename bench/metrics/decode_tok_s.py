"""Generated tokens in the window over the window."""
UNIT = "tokens/s"


def read(rec):
    return rec["tokens"] / rec["window_s"]
