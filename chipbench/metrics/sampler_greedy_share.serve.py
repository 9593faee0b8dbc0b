"""Share of the traced laps that stepped whose decode batch held no row with a
temperature (%): the scheduler writes ``sampled``, the number of such rows, into
each lap's record, and at 0 the step's sampler takes the argmax and sorts
nothing.  A program whose laps carry no such field reads as None."""


def read(facts):
    from chipbench.lib import laps

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and "sampled" in r]
    if not stepped:
        return None
    return 100.0 * sum(1 for r in stepped if not r["sampled"]) / len(stepped)
