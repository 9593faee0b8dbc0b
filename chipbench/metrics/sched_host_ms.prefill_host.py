"""Mean milliseconds a traced lap of the decode scheduler spends in its
``prefill_host`` phase: padding, argument build and dispatch of the
prefills, until the executable call returns."""


def read(facts):
    from chipbench.lib import laps

    return laps.phase_ms(facts, "prefill_host")
