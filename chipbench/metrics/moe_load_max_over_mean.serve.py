"""Routing imbalance (x): the busiest expert's pairs in a decode step (the
largest count of any expert layer, ``moe_load_max``) over the mean pairs an
expert held (``moe_pairs`` over expert layers x experts held), mean over the
traced decode steps.  1.0 is perfectly even routing.  A program whose laps
carry no such fields reads as None."""


def read(facts):
    from chipbench.lib import laps, lfm2_work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and r.get("moe_pairs")]
    sizes = lfm2_work.sizes_of(facts)
    if not stepped or sizes is None:
        return None
    places = lfm2_work.layer_counts(sizes)["experts"] \
        * lfm2_work.experts_held(sizes)
    return sum(r["moe_load_max"] * places / r["moe_pairs"]
               for r in stepped) / len(stepped)
