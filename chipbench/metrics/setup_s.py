"""Process start to window open, compilation included, the reference's own
time left out (s)."""


def read(facts):
    return facts["setup_s"]
