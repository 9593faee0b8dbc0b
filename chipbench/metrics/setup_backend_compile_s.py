"""Seconds inside the backend's compile before the program was ready
(jax's ``backend_compile`` stage): XLA's compile on a miss of jax's persistent
cache, the retrieval on a hit."""


def read(facts):
    from chipbench.lib import startup

    return startup.phase_s(("backend_compile",))
