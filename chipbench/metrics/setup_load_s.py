"""Self seconds the program spent writing and reading the artifact and
building the engine (parameters into its layout, page pool, slots), or
building the trainer: spans ``artifact_write``, ``artifact_read``,
``engine_build``, ``trainer_build``, their compiles left to the stages."""


def read(facts):
    from chipbench.lib import startup

    return startup.phase_s(startup.LOAD)
