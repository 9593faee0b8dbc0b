"""Bytes a decode step of a window-and-full model has to read over the time
of the engine's ``decode_step`` call and the chip's memory bandwidth (%): the
weights outside the experts and the embedding, ``moe_experts_hit`` x one
expert's bytes, the full layers' live KV (``context_tokens`` x 4096 B at the
published sizes) and the window layers' (``window_tokens`` x 12 288 B),
summed over the traced laps that stepped, over their ``decode_dispatch`` +
``decode_wait`` phases x 819 GB/s.  The span holds the host's dispatch, so
this is a floor on the device's own share and cannot read over it.  Bytes
from ``chipbench/lib/smallthinker_work.py``; another configuration's sizes,
or laps without ``window_tokens`` (another program), read as None."""


def read(facts):
    from chipbench.lib import laps, peaks, smallthinker_work as work

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and "moe_experts_hit" in r
               and "window_tokens" in r]
    sizes = work.sizes_of(facts)
    if facts.get("platform") == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    seconds = sum(r["phases"].get("decode_dispatch", 0.0)
                  + r["phases"].get("decode_wait", 0.0) for r in stepped)
    if not stepped or seconds <= 0 or sizes is None:
        return None
    byts = sum(work.decode_step_bytes(
        sizes, r["moe_experts_hit"], r.get("context_tokens", 0),
        r["window_tokens"]) for r in stepped)
    bw = peaks.peak(facts["device_kind"], "hbm_bytes_per_s") * facts["chips"]
    return 100.0 * byts / seconds / bw
