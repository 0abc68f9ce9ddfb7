"""Executables compiled or loaded from the persistent cache inside the
window (jax.monitoring's backend-compile events); should read 0."""


def read(ctx):
    return ctx["compiles"]
