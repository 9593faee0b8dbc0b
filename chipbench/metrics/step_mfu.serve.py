"""Model FLOP/s of the traced laps over the chip's bfloat16 peak (%): 2 x
active parameters for every prompt token prefilled and every token
generated, plus attention over the live context (a decode row attends over
its whole cache, a prompt token over half its prompt on average), over the
laps' wall seconds.  The whole serving step's share of the peak: idle time,
host time and memory-bound time all lower it.  FLOPs from
``chipbench/lib/lfm2_work.py``; laps without ``prefill_tokens`` and
``context_tokens`` (another program) read as None."""


def read(facts):
    from chipbench.lib import laps, lfm2_work, peaks

    recs = [r for r in laps.traced(facts, "serve") or ()
            if "prefill_tokens" in r]
    sizes = lfm2_work.sizes_of(facts)
    if facts.get("platform") == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    wall = sum(laps.wall(r) for r in recs)
    if not recs or wall <= 0 or sizes is None:
        return None
    flops = 0.0
    for r in recs:
        tokens = r["prefill_tokens"] + r.get("n", 0)
        mean_prompt = r["prefill_tokens"] / max(1, r.get("prefills", 0))
        flops += tokens * lfm2_work.token_flops(sizes) \
            + lfm2_work.attention_flops(
                sizes, r.get("context_tokens", 0)
                + r["prefill_tokens"] * mean_prompt / 2.0)
    peak = peaks.peak(facts["device_kind"], "bf16_flops") * facts["chips"]
    return 100.0 * flops / wall / peak
