"""The program's own account of its hot loops, over the traced seconds.

mxnet_tpu's phase accountant (``mxnet_tpu/telemetry/goodput.py``) keeps a
ring of whole records, one per decode-scheduler lap (kind ``serve``) or
trainer step (kind ``dist``): ``t0``/``t1`` on ``time.perf_counter()``, the
seconds of each phase, ``cpu_s`` of the thread, and ``traced``, true when a
profiler session was recording at the record's start and at its end.  At
``--trace 1`` the runners open one such session over ``trace_s`` seconds (or
``trace_steps`` steps) with the load still on, so the records with ``traced``
true are the laps of the very seconds the device-trace metrics are read over.

The readers import the program's accountant here, in ``read()``, because
``facts`` cannot carry the ring yet: the runners that fill ``facts`` may not be
edited by the PR that brought this file (PERF.md, Open questions; ROADMAP.md
queues the ``benchmark`` change that moves the ring into ``facts``, delimited
by the window's own edges).  A program without the ring (the parent of that
PR), an untraced run and a run with fewer than `MIN_TRACED` traced records all
read as ``None``, and the metric is left out of the line.
"""
MIN_TRACED = 10
WAITS = ("prefill_wait", "decode_wait")     # a lap's time blocked on the device
CELL_KIND = {"serve": "serve", "dist": "train"}     # accountant's kind -> cell's


def traced(facts, kind):
    """The traced records of ``kind`` if the cell is of that kind and the
    program kept at least `MIN_TRACED` of them, else None."""
    if facts.get("kind") != CELL_KIND[kind]:
        return None
    try:
        from mxnet_tpu.telemetry import goodput

        records = goodput.window(kind)
    except (ImportError, AttributeError):
        return None
    records = [r for r in records if r.get("traced")]
    return records if len(records) >= MIN_TRACED else None


def wall(record):
    return record["t1"] - record["t0"]


def host(record):
    """A lap's seconds less those it spent blocked on the device."""
    return wall(record) - sum(record["phases"].get(p, 0.0) for p in WAITS)


def phase_ms(facts, phase):
    """Mean milliseconds a traced lap spends in ``phase``."""
    laps = traced(facts, "serve")
    if laps is None:
        return None
    return 1e3 * sum(r["phases"].get(phase, 0.0) for r in laps) / len(laps)
