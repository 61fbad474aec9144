"""Programs compiled, or loaded from the persistent cache, while the window
was open (a ``jax.monitoring`` listener the benchmark registers)."""


def read(record):
    return record["compiles_in_window"]
