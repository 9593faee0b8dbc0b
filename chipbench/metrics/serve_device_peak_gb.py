"""Peak device bytes of the serving process over load, warm-up and window
(``lib/device.memory_peak_bytes``, read once the load stops), GB: the
weights, the KV pool and whatever the prefill and decode programs hold beside
them. A pool that the programs update in place reads a little over weights +
pool; one they copy reads a multiple of it."""


def read(facts):
    if facts.get("kind") != "serve" or not facts.get("memory_peak_bytes"):
        return None
    return facts["memory_peak_bytes"] / 1e9
