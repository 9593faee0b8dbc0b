"""Images a second through forward, backward and update with the input
pipeline running: all the steps completed in the window over all its time
(items/s)."""


def read(facts):
    if facts["kind"] != "train":
        return None
    return facts["window"]["mean_rate"]
