"""Model FLOP/s of the traced laps over the chip's bfloat16 peak (%), for a
model of sliding-window and full-attention layers over sparse experts: 2 x
the active parameters of every layer for every prompt token prefilled and
every token generated, the head for the rows that are sampled, a decode
row's attention over its cache (``context_tokens`` in the full layers,
``window_tokens`` in the window layers) and a prompt's over its triangle (the
band, in a window layer), over the laps' wall seconds.  The whole serving
step's share of the peak: idle time, host time and memory-bound time all
lower it.  FLOPs from ``chipbench/lib/smallthinker_work.py``; another
configuration's sizes, or laps without ``window_tokens`` (another program),
read as None."""


def read(facts):
    from chipbench.lib import laps, peaks, smallthinker_work as work

    recs = [r for r in laps.traced(facts, "serve") or ()
            if "prefill_tokens" in r]
    sizes = work.sizes_of(facts)
    if facts.get("platform") == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    wall = sum(laps.wall(r) for r in recs)
    if not recs or wall <= 0 or sizes is None \
            or not any("window_tokens" in r for r in recs):
        return None
    flops = sum(work.lap_flops(
        sizes, r["prefill_tokens"], r.get("prefills", 0), r.get("n", 0),
        r.get("context_tokens", 0), r.get("window_tokens", 0))
        for r in recs)
    peak = peaks.peak(facts["device_kind"], "bf16_flops") * facts["chips"]
    return 100.0 * flops / wall / peak
