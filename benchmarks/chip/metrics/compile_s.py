"""compile_s: seconds of tracing, lowering and backend compiling (cache
reads included) that JAX reported through ``jax.monitoring`` during
set-up."""


def read(ctx):
    return ctx.compile_setup["compile_s"]
