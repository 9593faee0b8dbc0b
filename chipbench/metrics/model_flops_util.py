"""The operations forward and backward need per item (the benchmark's own
count from the layer table, chipbench/lib/flops.py) x the window's rate / (chips x
the chip's bf16 peak from chipbench/lib/peaks.py), in %."""
import importlib


def read(facts):
    spec = facts["config"].get("flops")
    if facts["kind"] != "train" or not spec or facts["platform"] == "cpu":
        return None             # a rehearsal has no chip whose peak to take
    from chipbench.lib import peaks

    mod, fn = spec["function"].rsplit(".", 1)
    per_item = getattr(importlib.import_module(mod), fn)(**spec["args"])
    peak = peaks.peak(facts["device_kind"], "bf16_flops")
    return 100.0 * per_item * facts["window"]["mean_rate"] \
        / (facts["chips"] * peak)
