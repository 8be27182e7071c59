"""window_compiles.fanout: compilations and persistent-cache loads
(jax.monitoring's backend-compile event) inside the measured window."""


def read(ctx):
    return ctx.window_compiles
