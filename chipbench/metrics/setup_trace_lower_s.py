"""Seconds of Python tracing and of lowering to StableHLO before the
program was ready: jax's ``trace`` and ``lower`` stages, inside a ``program``
span of the compile registry or not."""


def read(facts):
    from chipbench.lib import startup

    return startup.phase_s(("trace", "lower"))
