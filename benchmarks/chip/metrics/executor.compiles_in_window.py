"""Programs compiled, or loaded from the persistent compile cache, while
the window ran: JAX's backend-compile events. Warm-up serves every batch
of the pool, so a replayed pool should compile nothing."""


def read(ctx):
    return float(len(ctx["compiles"]))
