"""Sequences in a decode step on average: tokens generated by decode steps /
decode steps, inside the window."""


def read(facts):
    s = facts.get("serve")
    if not s or not s["decode_steps"]:
        return None
    return (s["tokens"] - s["prefills"]) / s["decode_steps"]
