"""Seconds of the program's package import, first line to last (span
``import``; jax's import is inside it unless the process had it already)."""


def read(facts):
    from chipbench.lib import startup

    return startup.phase_s(("import",))
