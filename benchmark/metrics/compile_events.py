"""``jax.monitoring`` events as the harness's CompileLog counts them:
``cache_hits_in_setup`` — programs the persistent cache served during
set-up; ``in_window`` — backend compiles inside the measured window (0, or
the run is incorrect)."""


def read(ctx, key):
    return float(ctx["compile"][key])
