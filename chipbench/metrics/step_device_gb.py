"""Device bytes of the fused step by the compiler's own count
(``memory_analysis()``: arguments + outputs + temporaries - aliased), GB."""


def read(facts):
    m = facts.get("step_memory")
    if not m:
        return None
    return (m["argument"] + m["output"] + m["temp"] - m["alias"]) / 1e9
