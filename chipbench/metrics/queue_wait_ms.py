"""Mean time a request admitted in the traced laps had waited in the
scheduler's queue (ms): sum of the laps' ``queue_wait_s`` over their
``admitted``."""


def read(facts):
    from chipbench.lib import laps

    recs = laps.traced(facts, "serve")
    admitted = sum(r.get("admitted", 0) for r in recs or ())
    if not admitted:
        return None
    return 1e3 * sum(r["queue_wait_s"] for r in recs) / admitted
