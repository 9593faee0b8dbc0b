"""Share of the traced laps' seconds that went to prefills (%): their
``prefill_host`` + ``prefill_wait`` phases (the host's part of a prompt's
call and the wait for its first token) over the laps' wall time.  Only a
program whose laps say how many prompt tokens they prefilled
(``prefill_tokens``) is read; another reads as None."""


def read(facts):
    from chipbench.lib import laps

    recs = [r for r in laps.traced(facts, "serve") or ()
            if "prefill_tokens" in r]
    wall = sum(laps.wall(r) for r in recs)
    if not recs or wall <= 0:
        return None
    return 100.0 * sum(r["phases"].get("prefill_host", 0.0)
                       + r["phases"].get("prefill_wait", 0.0)
                       for r in recs) / wall
