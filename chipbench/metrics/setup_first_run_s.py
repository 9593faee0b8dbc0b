"""Self seconds of the programs' first runs (span ``first_run``: from
dispatch to the fetched result, less the compile inside it): program load
and first execution on the device."""


def read(facts):
    from chipbench.lib import startup

    return startup.phase_s(("first_run",))
