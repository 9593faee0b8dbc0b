"""Collective time on the first device during which no other operation runs
there / traced window, in %; cells on several chips only."""


def read(facts):
    tr = facts.get("trace")
    if not tr or facts["chips"] < 2:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
