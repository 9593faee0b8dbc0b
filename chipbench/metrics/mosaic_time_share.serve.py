"""Device time of Mosaic (Pallas) custom calls / device busy time, in %."""


def read(facts):
    tr = facts.get("trace")
    if facts["kind"] != "serve" or not tr or not tr["busy_s"]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy_s"]
