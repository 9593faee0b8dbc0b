"""Mean wall of the fused trainer's step bracket over the traced steps (ms):
``step_batch`` from entry to return, which on the asynchronous step is the
host's dispatch and not the device's work."""


def read(facts):
    from chipbench.lib import laps

    recs = laps.traced(facts, "dist")
    if recs is None:
        return None
    return 1e3 * sum(map(laps.wall, recs)) / len(recs)
