"""Backend compiles (JAX's `/jax/core/compile/backend_compile_duration`
events, persistent-cache loads among them) between the window's start
and its last served read.  Every shape should have been warmed: 0."""


def read(ctx):
    return ctx.compiles_in_window
