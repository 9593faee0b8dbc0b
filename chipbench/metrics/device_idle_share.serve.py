"""1 - union of device-operation intervals / traced window, in %."""


def read(facts):
    tr = facts.get("trace")
    if facts["kind"] != "serve" or not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
