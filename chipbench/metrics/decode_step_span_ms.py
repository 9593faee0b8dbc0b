"""Mean time of the engine's ``decode_step`` call and nothing else (ms): the
scheduler's ``decode_dispatch`` + ``decode_wait`` phases of the traced laps
that stepped.  ``decode_step_ms`` beside it is a host-clock lap."""


def read(facts):
    from chipbench.lib import laps

    stepped = [r for r in laps.traced(facts, "serve") or () if r.get("n")]
    if not stepped:
        return None
    return 1e3 * sum(r["phases"].get("decode_dispatch", 0.0)
                     + r["phases"].get("decode_wait", 0.0)
                     for r in stepped) / len(stepped)
