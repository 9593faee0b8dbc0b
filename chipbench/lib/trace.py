"""From the profiler's trace to numbers.

`start()` opens a jax.profiler trace in a directory under TMPDIR; `stop()`
closes it; `reduce()` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain events and reduces those.  The reduction works on plain events
``{"plane", "line", "name", "start", "dur"}`` (seconds), so it can be checked
against a small recorded trace kept beside the tests
(``chipbench/tests/data/recorded_trace.json``).

What is read:
  device planes   ``/device:TPU:<n>``; the line ``XLA Ops`` holds one event
                  per executed HLO operation, named by the operation's whole
                  HLO text, from which `parse_op` takes the short name, the
                  opcode and the result shape; a ``custom-call`` on the TPU
                  is a Mosaic (Pallas) kernel.  Asynchronous copies have a
                  line of their own (``Async XLA Ops``), which is not read:
                  busy time is the time an operation occupies the core.
  host planes     ``/host:CPU``; the harness's own ``TraceAnnotation`` spans
                  (``chipbench.*``) and the program's (``mxtpu.*``: the phases
                  of the decode scheduler's lap and of a trainer's step) are
                  events on their Python threads' lines
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
ANNOTATION_PREFIX = ("chipbench.", "mxtpu.")
BETWEEN = "host_between_annotations"

# An "XLA Ops" event is named by the operation's whole HLO text:
#   %fusion.10 = bf16[256,112,112,64]{0,3,2,1:T(8,128)(2,1)} fusion(pred[...
_NAME = re.compile(r"^%?([^ ]+) = ")
_OPCODE = re.compile(r"[\})\]] ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r" = \(*([a-z0-9]+\[[0-9,]*\])")

# classes of device operations by HLO opcode
CLASSES = {
    "fusion": "fusion", "convolution": "convolution", "sort": "sort",
    "copy": "copy", "copy-start": "copy", "copy-done": "copy",
    "pad": "pad", "reshape": "reshape", "bitcast": "reshape",
    "transpose": "reshape",
}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


class Session:
    def __init__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.stopped = False
        # annotations and device operations only: the profiler's Python
        # tracer hooks every call of every thread, which nothing here
        # reads, which taxes the host thread that feeds the device, and
        # which a process with thousands of waiting request threads does
        # not survive (PERF.md, PR 29)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self):
        import jax

        if not self.stopped:
            jax.profiler.stop_trace()
            self.stopped = True

    def events(self):
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb under %s"
                               % self.dir)
        return load_events(paths[0])

    def reduce(self, chips):
        try:
            return reduce(self.events(), chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def start():
    return Session()


def load_events(path):
    """Plain events of an ``.xplane.pb``: device planes' op lines and the
    host planes' ``chipbench.*`` and ``mxtpu.*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if is_dev and line.name not in OP_LINES:
                continue
            for ev in line.events:
                name = ev.name
                if not is_dev and not name.startswith(ANNOTATION_PREFIX):
                    continue
                e = {"plane": plane.name, "line": line.name, "name": name,
                     "start": ev.start_ns * 1e-9, "dur": ev.duration_ns * 1e-9}
                if is_dev:
                    e.update(parse_op(name))
                out.append(e)
    return out


def parse_op(text):
    """Short name, opcode and result shape of an operation from its HLO
    text; a TPU custom call is a Mosaic (Pallas) kernel."""
    m = _NAME.match(text)
    name = m.group(1) if m else text[:64]
    m = _OPCODE.search(text)
    op = m.group(1) if m else "unknown"
    m = _SHAPE.search(text)
    return {"name": name, "op": op, "shape": m.group(1) if m else ""}


def classify(event):
    op = event.get("op", "unknown")
    if op == "custom-call":
        return "mosaic"
    if op.startswith(COLLECTIVES):
        return "collective"
    return CLASSES.get(op, "other")


def _union(intervals):
    """Total length and merged list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def composition(events, top=10):
    """The ``top`` longest idle gaps of the first device: (seconds, {span:
    seconds of the gap during which it was the innermost open span}), where
    a span is an annotation of the host and ``BETWEEN`` stands for none."""
    dev = [e for e in events if DEVICE_PLANE.match(e["plane"])]
    first = min((e["plane"] for e in dev), default=None,
                key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    _, busy = _union([(e["start"], e["start"] + e["dur"])
                      for e in dev if e["plane"] == first])
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:top]
    host = [(e["start"], e["start"] + e["dur"], e["name"]) for e in events
            if not DEVICE_PLANE.match(e["plane"])]
    out = []
    for length, lo, hi in gaps:
        over = [h for h in host if h[0] < hi and h[1] > lo]
        cuts = sorted({lo, hi} | {t for h in over for t in h[:2]
                                  if lo < t < hi})
        spans = {}
        for a, b in zip(cuts, cuts[1:]):
            open_ = [h for h in over if h[0] <= a and h[1] >= b]
            # innermost: the latest to start and, of those, the first to end
            name = max(open_, key=lambda h: (h[0], -h[1]))[2] if open_ \
                else BETWEEN
            spans[name] = spans.get(name, 0.0) + (b - a)
        out.append((length, spans))
    return out


def reduce(events, chips):
    """Busy and idle time, operation classes, Mosaic share, exposed
    collectives, the ten largest operations and the ten longest idle gaps,
    each named by the innermost span that covers most of it."""
    by_dev = {}
    for e in events:
        m = DEVICE_PLANE.match(e["plane"])
        if m:
            by_dev.setdefault(int(m.group(1)), []).append(e)
    devs = sorted(by_dev)[:chips]
    if not devs:
        return None
    t_lo = min(e["start"] for d in devs for e in by_dev[d])
    t_hi = max(e["start"] + e["dur"] for d in devs for e in by_dev[d])
    busy, class_s, op_s = [], {}, {}
    for d in devs:
        total, _ = _union([(e["start"], e["start"] + e["dur"])
                           for e in by_dev[d]])
        busy.append(total)
        for e in by_dev[d]:
            cls = classify(e)
            class_s[cls] = class_s.get(cls, 0.0) + e["dur"] / len(devs)
            key = "%s:%s %s" % (cls, e["name"], e.get("shape", ""))
            op_s[key] = op_s.get(key, 0.0) + e["dur"] / len(devs)
    busy_s = sum(busy) / len(busy)
    window_s = t_hi - t_lo

    # exposed collectives on the first device: collective time during which
    # no other operation runs there
    first = by_dev[devs[0]]
    coll = [(e["start"], e["start"] + e["dur"]) for e in first
            if classify(e) == "collective"]
    other = [(e["start"], e["start"] + e["dur"]) for e in first
             if classify(e) != "collective"]
    coll_total, coll_merged = _union(coll)
    _, other_merged = _union(other)
    covered = 0.0
    j = 0
    for s, e in coll_merged:
        while j < len(other_merged) and other_merged[j][1] <= s:
            j += 1
        k = j
        while k < len(other_merged) and other_merged[k][0] < e:
            covered += min(e, other_merged[k][1]) - max(s, other_merged[k][0])
            k += 1
    exposed_s = coll_total - covered

    gap_spans = composition(events)
    top = [[max(spans, key=spans.get), g] for g, spans in gap_spans]
    ops = sorted(([k, v] for k, v in op_s.items()), key=lambda kv: -kv[1])
    classes = sorted((["all_%s_ops" % k, v] for k, v in class_s.items()),
                     key=lambda kv: -kv[1])
    return {
        "busy_s": busy_s, "window_s": window_s, "devices": len(devs),
        "class_s": class_s, "mosaic_s": class_s.get("mosaic", 0.0),
        "collective_s": coll_total, "collective_exposed_s": exposed_s,
        "idle_gap_spans": gap_spans,
        "breakdown": {"device_ops": (classes[:5] + ops[:5])[:10],
                      "idle_gaps": top},
    }
