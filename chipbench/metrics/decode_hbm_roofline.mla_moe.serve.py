"""Bytes a decode step of a latent-attention model has to read over the time
of the engine's ``decode_step`` call and the chip's memory bandwidth (%): the
weights outside the routed experts (attention, dense feed-forward, routers,
shared experts, head), ``moe_experts_hit`` x one expert's bytes and the live
cached rows (``context_tokens`` x 1152 B a layer), summed over the traced
laps that stepped, over their ``decode_dispatch`` + ``decode_wait`` phases x
819 GB/s.  The span holds the host's dispatch, so this is a floor on the
device's own share and cannot read over it.  Bytes from
``chipbench/lib/mla_moe_work.py``; another configuration's sizes, or laps
without the expert counts (another program), read as None."""


def read(facts):
    from chipbench.lib import laps, mla_moe_work, peaks

    stepped = [r for r in laps.traced(facts, "serve") or ()
               if r.get("n") and "moe_experts_hit" in r]
    sizes = mla_moe_work.sizes_of(facts)
    if facts.get("platform") == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    seconds = sum(r["phases"].get("decode_dispatch", 0.0)
                  + r["phases"].get("decode_wait", 0.0) for r in stepped)
    if not stepped or seconds <= 0 or sizes is None:
        return None
    byts = sum(mla_moe_work.decode_step_bytes(
        sizes, r["moe_experts_hit"], r.get("context_tokens", 0))
        for r in stepped)
    bw = peaks.peak(facts["device_kind"], "hbm_bytes_per_s") * facts["chips"]
    return 100.0 * byts / seconds / bw
