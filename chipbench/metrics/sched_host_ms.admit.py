"""Mean milliseconds a traced lap of the decode scheduler spends in its
``admit`` phase: queue, deadline and page housekeeping of admission, the
engine's prefill call excluded."""


def read(facts):
    from chipbench.lib import laps

    return laps.phase_ms(facts, "admit")
