"""Mean milliseconds a traced lap of the decode scheduler spends in its
``decode_dispatch`` phase: handing the decode step its host arrays, until
the executable call returns."""


def read(facts):
    from chipbench.lib import laps

    return laps.phase_ms(facts, "decode_dispatch")
