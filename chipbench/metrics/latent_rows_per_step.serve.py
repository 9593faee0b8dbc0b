"""Cached rows a decode step attends over, mean over the traced laps that
stepped: the scheduler writes ``context_tokens``, the sum of its rows'
lengths, into each lap's record.  In a latent-attention model this is how
much of the pool a step streams, a layer at a time: the number that says
whether the latent kernel is being worked.  Another configuration (no
``kv_lora_rank`` among its sizes), or a program whose laps carry no
``context_tokens``, reads as None."""


def read(facts):
    from chipbench.lib import laps, mla_moe_work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and "context_tokens" in r]
    if not stepped or mla_moe_work.sizes_of(facts) is None:
        return None
    return sum(r["context_tokens"] for r in stepped) / len(stepped)
