"""Seconds that set-up's programs spent in the backend compile step
(compiled by XLA or read back from the persistent cache), from
``jax.monitoring`` through ``repro.core.instrument.count_compiles``."""


def read(run):
    return run.setup_compile_s
