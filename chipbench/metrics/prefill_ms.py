"""Mean of ``mxtpu_serve_prefill_seconds`` inside the window (ms)."""


def read(facts):
    s = facts.get("serve")
    if not s or not s["prefills"]:
        return None
    return 1e3 * s["prefill_s"] / s["prefills"]
