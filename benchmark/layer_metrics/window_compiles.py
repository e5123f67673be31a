"""Programs compiled (or loaded from the cache) inside the window, counted by
a ``jax.monitoring`` listener on the backend-compile event. Must read 0."""


def read(trace, stats, facts):
    return facts["compiles"]
