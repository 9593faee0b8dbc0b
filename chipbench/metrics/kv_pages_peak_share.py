"""Peak of ``mxtpu_serve_kv_pages_used`` inside the window / pages in the
pool, in %."""


def read(facts):
    s = facts.get("serve")
    if not s:
        return None
    return 100.0 * s["kv_pages_peak"] / s["kv_pages_total"]
