"""Seconds of backend compiles and compile-cache loads during set-up, as
``jax.monitoring`` reports them."""


def read(ctx):
    return ctx.setup.get("compile_s")
