"""Seconds of the window per outer sweep completed in it (host clock).

The window runs whole solves back to back, so it covers every solve's own
validation, sorts and layouts as well as its sweeps."""


def read(record):
    if not record.get("sweeps"):
        return None
    return record["window_s"] / record["sweeps"]
