"""Routing imbalance (x) of a window-and-full model's expert layers: the
busiest expert's pairs in a decode step (the largest count of any layer,
``moe_load_max``) over the mean pairs an expert held (``moe_pairs`` over
layers x experts held: 8 x 64 places at the published cut), mean over the
traced decode steps.  1.0 is perfectly even routing.  Counts from
``chipbench/lib/smallthinker_work.py``; another configuration's sizes, or
laps without the expert counts (another program), read as None."""


def read(facts):
    from chipbench.lib import laps, smallthinker_work as work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and r.get("moe_pairs") and "moe_load_max" in r]
    sizes = work.sizes_of(facts)
    if not stepped or sizes is None:
        return None
    places = work.layer_counts(sizes)["layers"] * work.experts_held(sizes)
    return sum(r["moe_load_max"] * places / r["moe_pairs"]
               for r in stepped) / len(stepped)
