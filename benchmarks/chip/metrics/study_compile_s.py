"""study_compile_s: seconds per study in the window that JAX spends
tracing, lowering and compiling, loads from the persistent compilation
cache included (JAX's own jax.monitoring duration events).
Moves ``study_makespan_s``."""


def read(ctx):
    return ctx.get("study_compile_s")
