"""Routing imbalance (x) on a holder of a share of a latent-attention model's
routed experts: the busiest held expert's pairs in a decode step (the largest
count of any expert layer, ``moe_load_max``) over the mean pairs a held
expert got (``moe_pairs`` over expert layers x experts held), mean over the
traced decode steps that sent a pair here.  1.0 is perfectly even routing
over the held experts; group-limited routing, which reaches this holder only
through the groups its experts lie in, reads higher.  Counts from
``chipbench/lib/mla_moe_work.py``; another configuration's sizes, or laps
without the expert counts (another program), read as None."""


def read(facts):
    from chipbench.lib import laps, mla_moe_work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and r.get("moe_pairs") and "moe_load_max" in r]
    sizes = mla_moe_work.sizes_of(facts)
    if not stepped or sizes is None:
        return None
    places = mla_moe_work.layer_counts(sizes)["experts"] \
        * mla_moe_work.experts_held(sizes)
    return sum(r["moe_load_max"] * places / r["moe_pairs"]
               for r in stepped) / len(stepped)
