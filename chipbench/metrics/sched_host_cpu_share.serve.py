"""CPU seconds of the scheduler thread (``time.thread_time()``) over the host's
part of its traced laps (wall less the waits on the device), in %: under 100
the thread was waiting for the GIL or a lock, not running."""


def read(facts):
    from chipbench.lib import laps

    recs = laps.traced(facts, "serve")
    if recs is None:
        return None
    return 100.0 * sum(r["cpu_s"] for r in recs) / sum(map(laps.host, recs))
