"""Core power-saving library (the paper's contribution).

Times are float64 seconds: microsecond-scale transitions over 1000+ second
simulations exceed f32 resolution, so the simulator enables x64.
"""
import jax

jax.config.update("jax_enable_x64", True)

from repro.core.eee import (  # noqa: E402,F401
    EEE_STATES, FAST_WAKE, DEEP_SLEEP, LinkState, Policy, PowerModel,
)
