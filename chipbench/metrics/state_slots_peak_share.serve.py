"""Peak of the state slots in use over the slots held (%): the scheduler
writes ``state_slots``, the slots taken as the lap's decode step ran, into
each lap's record, and the pool's size into the gauge
``mxtpu_serve_state_slots_total``.  Read over the traced laps.  A program
without state slots (or a model without recurrent layers) reads as None."""


def read(facts):
    from chipbench.lib import laps

    used = [r["state_slots"] for r in laps.traced(facts, "serve") or ()
            if "state_slots" in r]
    if not used:
        return None
    from mxnet_tpu import telemetry

    total = telemetry.gauge("mxtpu_serve_state_slots_total",
                            {"model": "lm/1"}).value
    return 100.0 * max(used) / total if total else None
