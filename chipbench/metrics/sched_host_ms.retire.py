"""Mean milliseconds a traced lap of the decode scheduler spends in its
``retire`` phase: the per-sequence loops around the step (tokens appended,
finished or expired sequences resolved, their pages freed)."""


def read(facts):
    from chipbench.lib import laps

    return laps.phase_ms(facts, "retire")
