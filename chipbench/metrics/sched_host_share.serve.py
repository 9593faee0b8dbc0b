"""1 - (seconds the scheduler was blocked on the device) / (seconds of its
laps), over the traced laps, in %: what ``device_idle_share.serve`` should come
to if every idle gap of the device is the scheduler's."""


def read(facts):
    from chipbench.lib import laps

    recs = laps.traced(facts, "serve")
    if recs is None:
        return None
    return 100.0 * sum(map(laps.host, recs)) / sum(map(laps.wall, recs))
