"""1 - (rate over the whole window) / (median slice's rate), in %: what
excursions inside the window cost the rate.  Zero in a steady run; a PR that
removes stalls shows here."""


def read(facts):
    if facts["kind"] != "serve":
        return None
    return facts["window"]["stall_share_pct"]
