"""Share of the window the harness loop spent blocked in ``next(feed)`` (%),
host clock."""


def read(facts):
    if facts["kind"] != "train":
        return None
    return 100.0 * facts["data_wait_s"] / facts["window"]["window_s"]
