"""95th percentile of the same (ms)."""


def read(facts):
    lat = facts.get("latency")
    return None if not lat else lat["p95_ms"]
