"""Backend compiles inside the window, counted by a ``jax.monitoring``
listener on ``/jax/core/compile/backend_compile_duration``."""


def read(r):
    return len(r.counters["compiles_in_window"])
