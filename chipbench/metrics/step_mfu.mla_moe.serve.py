"""Model FLOP/s of the traced laps over the chip's bfloat16 peak (%), for a
latent-attention model that holds a share of its routed experts: every
prompt token prefilled and every token generated through the parameters
outside the routed experts, the (token, expert) pairs really computed here
(``moe_pairs`` of the decode step, ``prefill_moe_pairs`` of the prompts)
through one expert each, a decode row's absorbed attention over its whole
cache and a prompt's expanded attention over half its square, over the laps'
wall seconds.  The whole serving step's share of the peak: idle time, host
time and memory-bound time all lower it.  FLOPs from
``chipbench/lib/mla_moe_work.py``; another configuration's sizes, or laps
without ``prefill_moe_pairs`` (another program), read as None."""


def read(facts):
    from chipbench.lib import laps, mla_moe_work, peaks

    recs = [r for r in laps.traced(facts, "serve") or ()
            if "prefill_tokens" in r]
    sizes = mla_moe_work.sizes_of(facts)
    if facts.get("platform") == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    wall = sum(laps.wall(r) for r in recs)
    if not recs or wall <= 0 or sizes is None \
            or not any("prefill_moe_pairs" in r or "moe_pairs" in r
                       for r in recs):
        return None
    flops = sum(mla_moe_work.lap_flops(
        sizes, r["prefill_tokens"], r.get("prefills", 0), r.get("n", 0),
        r.get("context_tokens", 0),
        r.get("moe_pairs", 0) + r.get("prefill_moe_pairs", 0))
        for r in recs)
    peak = peaks.peak(facts["device_kind"], "bf16_flops") * facts["chips"]
    return 100.0 * flops / wall / peak
