"""Mean milliseconds a traced lap of the decode scheduler spends in its
``build`` phase: the numpy build of the decode batch."""


def read(facts):
    from chipbench.lib import laps

    return laps.phase_ms(facts, "build")
