"""Seconds of XLA compiles in set-up, as ``jax.monitoring`` reports them
(``/jax/core/compile/backend_compile_duration``): tile programs, eager
staging ops and the seeded noise, whether compiled or loaded from the
persistent cache."""


def read(rec):
    return rec["setup_compile_s"]
