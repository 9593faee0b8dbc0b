"""Tokens a chip generates a second under load: all the tokens the server's
own counter saw in the window over all its time (tokens/s)."""


def read(facts):
    if facts["kind"] != "serve":
        return None
    return facts["window"]["mean_rate"]
