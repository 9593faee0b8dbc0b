"""The program's own account of its start-up, read for the set-up metrics.

mxnet_tpu's accountant (``mxnet_tpu/telemetry/goodput.py``) keeps a ring
``startup`` of whole span records: ``name``, ``t0``/``t1`` on
``time.perf_counter()`` (the clock `setup_s` is taken on), ``id``, ``parent``
(the id of the span that held it on its thread, else None) and the owner's
fields.  The program writes ``import``, ``artifact_write``, ``artifact_read``,
``engine_build``, ``trainer_build``, ``program`` (a miss of its compile
registry), jax's own ``trace``, ``lower`` and ``backend_compile`` (inside a
``program`` or not; a ``backend_compile`` says ``cache``: ``hit``, ``miss`` or
``off``), ``first_run``, and a mark of zero length, ``ready``, when it can do
what it was started for.  It goes on writing after that mark (another model
loaded later is the same path): set-up is what ended by the first ``ready``.

A span's self time is its duration less that of its children; summed by name
it counts every second of the account once.  The readers import the ring here,
in ``read()``, as ``lib/laps.py`` does and for its reason.  A program without
the ring (the parent of the PR that brought this file) or with no ``ready``
in it reads as ``None``, and the metric is left out of the line.
"""
LOAD = ("artifact_write", "artifact_read", "engine_build", "trainer_build")


def spans():
    """The spans that ended by the first ``ready``, or None where the
    program keeps no such ring or has not written the mark."""
    try:
        from mxnet_tpu.telemetry import goodput

        records = goodput.window("startup")
    except (ImportError, AttributeError):
        return None
    marks = [r["t1"] for r in records if r["name"] == "ready"]
    if not marks:
        return None
    ready = min(marks)
    return [r for r in records if r["t1"] <= ready and r["name"] != "ready"]


def duration(record):
    return record["t1"] - record["t0"]


def self_seconds(records):
    """``{name: seconds}``: each span's duration less its children's."""
    held = {}
    for r in records:
        if r["parent"] is not None:
            held[r["parent"]] = held.get(r["parent"], 0.0) + duration(r)
    out = {}
    for r in records:
        own = max(0.0, duration(r) - held.get(r["id"], 0.0))
        out[r["name"]] = out.get(r["name"], 0.0) + own
    return out


def phase_s(names):
    """Self seconds of the spans called one of ``names``; None where none
    was written."""
    records = spans()
    if records is None:
        return None
    own = self_seconds(records)
    if not any(n in own for n in names):
        return None
    return sum(own.get(n, 0.0) for n in names)


def cache_misses():
    """Backend compiles that jax's persistent cache did not serve; None
    where the cache took part in none (not armed, as in a rehearsal)."""
    records = spans()
    if records is None:
        return None
    said = [r.get("cache") for r in records if r["name"] == "backend_compile"]
    if not any(c in ("hit", "miss") for c in said):
        return None
    return said.count("miss")


def covered_s(records):
    """Seconds the top-level spans cover: the union of their intervals
    (threads overlap; a child lies inside its parent)."""
    ids = {r["id"] for r in records}
    top = sorted((r["t0"], r["t1"]) for r in records
                 if r["parent"] not in ids)
    total, end = 0.0, None
    for t0, t1 in top:
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def outside_program_s(facts):
    """`setup_s` less what the account covers: backend start, the harness's
    reference weights, request plan and warm-up."""
    records = spans()
    if records is None:
        return None
    return facts["setup_s"] - covered_s(records)
