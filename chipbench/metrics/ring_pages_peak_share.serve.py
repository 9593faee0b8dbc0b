"""Peak of the window group's pages in use over the pages it has (%): the
scheduler writes ``ring_pages``, the second page group's used pages as the
lap's decode step ran, into each lap's record; the group's size is the
configuration's ``engine.window_pages``.  Read over the traced laps.  A
configuration without a window group, or a program whose laps carry no
``ring_pages``, reads as None."""


def read(facts):
    from chipbench.lib import laps

    used = [r["ring_pages"] for r in laps.traced(facts, "serve") or ()
            if "ring_pages" in r]
    total = ((facts.get("config") or {}).get("engine") or {}) \
        .get("window_pages")
    if not used or not total:
        return None
    return 100.0 * max(used) / total
