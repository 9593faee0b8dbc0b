"""Median time from sending a request to its whole reply, over every request
answered inside the window (ms); closed-loop cells only."""


def read(facts):
    lat = facts.get("latency")
    return None if not lat else lat["p50_ms"]
