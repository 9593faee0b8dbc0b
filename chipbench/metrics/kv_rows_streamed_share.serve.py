"""Cached rows the decode steps stream, over what they would stream were
every layer a full-attention layer (%): (full layers x ``context_tokens`` +
window layers x ``window_tokens``) / (layers x ``context_tokens``), summed
over the traced laps that stepped.  The scheduler writes ``window_tokens``,
the sum over the step's rows of min(length, window), beside
``context_tokens`` into each lap's record.  100% is a batch no row of which
has outgrown the window; lower is what the window saves.  Another
configuration's sizes, or laps without ``window_tokens`` (another program),
read as None."""


def read(facts):
    from chipbench.lib import laps, smallthinker_work as work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and r.get("context_tokens")
               and "window_tokens" in r]
    sizes = work.sizes_of(facts)
    if not stepped or sizes is None:
        return None
    return work.kv_rows_streamed_share(
        sizes, sum(r["context_tokens"] for r in stepped),
        sum(r["window_tokens"] for r in stepped))
