"""Seconds the set-up spent obtaining compiled programs, from the
persistent cache or by compiling: JAX's
``/jax/core/compile/backend_compile_duration`` events, summed."""


def read(run):
    return run.setup["compile_s"]
