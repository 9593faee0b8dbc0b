"""Time of one decode lap by the host's clock: the window's seconds less the
seconds the scheduler spent in prefill, over the decode steps it took (ms).
The scheduler's own work and idle between laps are in it: a real span needs
one in the engine (PERF.md, Open questions)."""


def read(facts):
    s = facts.get("serve")
    if not s or not s["decode_steps"]:
        return None
    return 1e3 * (facts["window"]["window_s"] - s["prefill_s"]) \
        / s["decode_steps"]
