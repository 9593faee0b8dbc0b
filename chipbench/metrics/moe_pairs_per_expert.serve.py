"""(token, expert) pairs a held expert that was hit, mean over the traced
decode steps: how many rows share one read of an expert's weights.  The
scheduler writes ``moe_pairs`` and ``moe_experts_hit`` (both summed over the
expert layers; they ride out of the step with its tokens) into each lap's
record.  A program whose laps carry no such fields reads as None."""


def read(facts):
    from chipbench.lib import laps

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and r.get("moe_experts_hit")]
    if not stepped:
        return None
    return sum(r["moe_pairs"] / r["moe_experts_hit"]
               for r in stepped) / len(stepped)
