"""Seconds of backend compilation during set-up, summed from JAX's
``/jax/core/compile/backend_compile_duration`` events; 0 where every program
came from the persistent compilation cache."""


def read(rec):
    return rec.setup_compile_s
