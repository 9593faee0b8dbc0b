"""Runner of the serving cells.

Set-up makes the weights from the seed, saves the artifact, loads it through
``ModelRepository.load(generate=True)`` (which compiles this cell's buckets
and no others) behind a ``ServingServer`` on 127.0.0.1, and builds every
request of the run.  Load starts before the window; the window opens
``warm_s`` seconds later, at a decode-step completion, when the decode batch
is at its working size.

Replies are not streamed, so tokens per slice are read from the server's own
counter ``mxtpu_serve_generated_tokens_total``.  A poller thread watches the
decode-step counter; a slice edge is the first step completion at or after
the nominal edge (``slice_s`` apart), so every slice holds whole decode laps,
and the counters are read once the step's burst of increments has settled.
"""
from __future__ import annotations

import importlib
import shutil
import sys
import tempfile
import threading
import time

from ..lib import device as devlib
from ..lib import loadgen, slices, trace as tracelib

MODEL = "lm/1"
PATH = "/v1/models/lm:generate"
POLL_S = 0.001          # the poller's look at the decode-step counter
SETTLE_S = 0.004        # a step's burst of counter increments has settled
DRAIN_S = 60.0          # the longest wait for the client threads to end


class _Poller(threading.Thread):
    """Reads the scheduler's counters at slice edges snapped to decode-step
    completions, and the peak of the KV pages gauge between them."""

    def __init__(self, nominal_edges):
        super().__init__(name="chipbench-poller", daemon=True)
        from mxnet_tpu import telemetry

        labels = {"model": MODEL}
        self.steps = telemetry.counter("mxtpu_serve_decode_steps_total",
                                       labels)
        self.tokens = telemetry.counter("mxtpu_serve_generated_tokens_total",
                                        labels)
        self.prefill = telemetry.histogram("mxtpu_serve_prefill_seconds",
                                           labels)
        self.pages = telemetry.gauge("mxtpu_serve_kv_pages_used", labels)
        self.active = telemetry.gauge("mxtpu_serve_active_sequences", labels)
        self.nominal = list(nominal_edges)
        self.edges = []                 # dicts, one per edge reached
        self.pages_peak = 0
        self.done = threading.Event()
        self.halt = threading.Event()

    def read(self, t):
        return {"t": t, "steps": self.steps.value, "tokens": self.tokens.value,
                "prefill_s": self.prefill.sum, "prefills": self.prefill.count,
                "active": self.active.value}

    def run(self):
        last_steps = self.steps.value
        k = 0
        while k < len(self.nominal) and not self.halt.is_set():
            time.sleep(POLL_S)
            now = time.perf_counter()
            steps = self.steps.value
            if self.edges:
                self.pages_peak = max(self.pages_peak, self.pages.value)
            if steps == last_steps:
                continue
            last_steps = steps
            if now < self.nominal[k]:
                continue
            # a decode step has just completed: let its burst of counter
            # increments settle, then read
            tokens, quiet_since = self.tokens.value, now
            while True:
                time.sleep(POLL_S)
                t = time.perf_counter()
                v = self.tokens.value
                if v != tokens:
                    tokens, quiet_since = v, t
                elif t - quiet_since >= SETTLE_S or t - now > 0.05:
                    break
            self.edges.append(self.read(now))
            self.pages_peak = max(self.pages_peak, self.pages.value)
            k += 1
        self.done.set()


def horizon_s(traffic, seconds, trace):
    """The seconds of arrivals a run offers: the warm-up, the window, two
    slices of slack for edges that snap late, and a traced run's seconds
    after the window."""
    return float(traffic["warm_s"]) + seconds + 2 * float(traffic["slice_s"]) \
        + (float(traffic["trace_s"]) + 10.0 if trace else 0.0)


def _process_threads():
    """Threads of this process as the kernel counts them (the chip machine
    kills a process at 4096); None where /proc says nothing."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("Threads:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def _latency(records, t_open, t_close):
    lat = [1e3 * (r["t_done"] - r["t_from"]) for r in records
           if r["status"] == 200 and t_open <= r["t_done"] <= t_close]
    if not lat:
        return None
    p50, _ = slices.percentile(lat, 50)
    p95, beyond = slices.percentile(lat, 95)
    return {"p50_ms": p50, "p95_ms": p95, "beyond_p95": beyond,
            "p90_ms": slices.percentile(lat, 90)[0],
            "p99_ms": slices.percentile(lat, 99)[0],
            "requests": len(lat), "max_ms": max(lat)}


def _sample(finished, requests, n, seed):
    """The shortest, the longest and seeded others of the requests the
    window finished."""
    import numpy as np

    def total(r):
        return len(requests[r["index"]]["prompt"]) + len(r["tokens"])

    order = sorted(finished, key=total)
    picked = [order[0], order[-1]] if len(order) > 1 else list(order)
    rest = [r for r in order[1:-1]]
    rng = np.random.RandomState((seed + 17) % (2 ** 32))
    for i in rng.permutation(len(rest))[:max(0, n - len(picked))]:
        picked.append(rest[i])
    return picked


def run(cell, config, traffic, opts, t_process):
    from mxnet_tpu.telemetry import goodput

    chips = cell["chips"]
    devs = devlib.devices_or_exit(chips, allow_cpu=opts.rehearse)
    ref = importlib.import_module(config["reference"])
    factory = importlib.import_module(config["model"]["factory"])
    sizes = config["sizes"]
    slice_s = float(traffic["slice_s"])
    warm_s = float(traffic["warm_s"])
    n_slices = int(opts.seconds // slice_s)

    # ---- set-up ----------------------------------------------------------
    stamps, last = [], [t_process]

    def stamp(what):
        now = time.perf_counter()
        stamps.append("%s %.1f s" % (what, now - last[0]))
        last[0] = now

    stamp("imports and backend")
    workdir = tempfile.mkdtemp(prefix="chipbench_lm_")
    try:
        weights = ref.make_weights(opts.seed, sizes)
        stamp("weights")
        prefix = factory.save(config, weights, workdir)
        del weights
        stamp("zoo model and artifact")
        repo, server, model = factory.serve(config, traffic, prefix)
        stamp("load and warm (compile included)")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if opts.break_step:
        opts.break_step(model)
    requests = loadgen.plan(traffic, opts.seed, sizes["vocab_size"],
                            horizon_s(traffic, opts.seconds, opts.trace))
    load = loadgen.Load(traffic, requests, server.port, PATH)
    stamp("requests")
    compile_s = goodput.totals()["phases"].get("compile", 0.0)
    misses0 = _jit_misses()

    # ---- load, then the window -------------------------------------------
    tracing = None
    try:
        poller = _Poller([])
        # read before the load starts: while a closed loop's later clients
        # start, its first are already being answered
        at_load = poller.read(time.perf_counter())
        load.start()
        t_nominal = load.t0 + warm_s
        poller.nominal = [t_nominal + k * slice_s for k in range(n_slices + 1)]
        poller.start()
        limit = t_nominal + opts.seconds + 60.0
        while not poller.done.wait(0.05):
            if time.perf_counter() > limit:
                poller.halt.set()
                raise RuntimeError(
                    "the window did not close: %d of %d slice edges seen "
                    "(no decode step completes?)"
                    % (len(poller.edges), n_slices + 1))
        edges = poller.edges
        t_open, t_close = edges[0]["t"], edges[-1]["t"]
        setup_s = t_open - t_process
        threads = [_process_threads()]      # at the window's close
        if opts.trace:
            # the profiler's own start and stop stall the host: it runs over
            # seconds of its own, once the window has closed and with the
            # load still on, so the slices stay the program's
            tracing = tracelib.start()
            time.sleep(float(traffic["trace_s"]))
            tracing.stop()
        threads.append(_process_threads())  # as the load stops
        load.stop()
        peak = devlib.memory_peak_bytes(devs)
        if traffic["loop"] == "open":
            # what is still queued or decoding is cancelled: neither
            # attempted nor failed
            model.abort_pending()
        joined = load.join(DRAIN_S)
        at_end = poller.read(time.perf_counter())
    finally:
        if tracing is not None and not tracing.stopped:
            tracing.stop()
        server.shutdown()
        repo.unload("lm", timeout=5.0)
    compiles_in_window = _jit_misses() - misses0

    # ---- the window, read ------------------------------------------------
    ts = [e["t"] for e in edges]
    work = [edges[i + 1]["tokens"] - edges[i]["tokens"]
            for i in range(len(edges) - 1)]
    window = slices.summary(ts, work)
    records = list(load.records)
    in_run = [r for r in records if r["t_done"] <= t_close or
              traffic["loop"] == "closed"]
    good = [r for r in in_run if r["status"] == 200 and r["tokens"] is not None
            and len(r["tokens"]) == requests[r["index"]]["new"]]
    failed = len(in_run) - len(good)
    info = _latency(good, t_open, t_close)
    latency = info if traffic["loop"] == "closed" else None
    reply_tokens = sum(len(r["tokens"]) for r in records
                       if r["status"] == 200 and r["tokens"])
    counted = at_end["tokens"] - at_load["tokens"]
    facts = {
        "kind": "serve", "cell": cell, "config": config, "traffic": traffic,
        "chips": chips, "device_kind": devs[0].device_kind,
        "platform": devs[0].platform,
        "window": window, "setup_s": setup_s, "compile_s": compile_s,
        "latency": latency,
        "serve": {
            "tokens": edges[-1]["tokens"] - edges[0]["tokens"],
            "decode_steps": edges[-1]["steps"] - edges[0]["steps"],
            "prefills": edges[-1]["prefills"] - edges[0]["prefills"],
            "prefill_s": edges[-1]["prefill_s"] - edges[0]["prefill_s"],
            "kv_pages_peak": poller.pages_peak,
            "kv_pages_total": config["engine"]["num_pages"],
            "active_at_open": edges[0]["active"]},
        "compiles_in_window": compiles_in_window,
        "trace": None if tracing is None else tracing.reduce(chips),
    }
    served = facts["serve"]
    print("setup: " + "; ".join(stamps) + "; load before the window %.1f s"
          % (t_open - load.t0), flush=True)
    print("window: %.3f s, %.2f tokens/s; %d slices of about %.1f s: median "
          "%.2f, min %.2f, max %.2f; stall share %.3f%%; %d sequences "
          "active at open, %d decode steps of %.2f on average; compiles "
          "inside the window %d"
          % (window["window_s"], window["mean_rate"], window["slices"],
             slice_s, window["median_rate"], window["min_rate"],
             window["max_rate"], window["stall_share_pct"],
             edges[0]["active"], served["decode_steps"],
             (served["tokens"] - served["prefills"])
             / max(1, served["decode_steps"]), compiles_in_window),
          flush=True)
    print("requests: sent %d, answered by the end of the run %d, good %d, "
          "failed %d, all client threads joined %s; generator late by max "
          "%.1f ms; counter saw %d tokens over the load, replies hold %d, "
          "difference %d (tokens of requests cancelled or cut); %d requests "
          "planned; process threads %s at the window's close, %s as the load "
          "stops"
          % (len(load.late_s) if traffic["loop"] == "open"
             else min(load._next, len(requests)),
             len(in_run), len(good), failed, joined,
             1e3 * max(load.late_s or [0.0]), counted, reply_tokens,
             counted - reply_tokens, len(requests), threads[0], threads[1]),
          flush=True)
    if traffic["loop"] == "closed" and load._next >= len(requests):
        # for the builder's eye: clients that found no request left went home
        print("chipbench: %s: the plan of %d requests ran out: raise "
              "max_requests" % (cell["name"], len(requests)),
              file=sys.stderr, flush=True)
    sent_by_close = sum(1 for r in requests[:len(load.late_s)]
                        if load.t0 + r["due"] <= t_close) \
        if traffic["loop"] == "open" else None
    if sent_by_close is not None:
        print("open loop: %d requests due by the window's close, %d answered "
              "by then, backlog %d"
              % (sent_by_close, len(in_run), sent_by_close - len(in_run)),
              flush=True)
    if info:
        print("latency of the %d requests answered inside the window: p50 "
              "%.1f ms, p90 %.1f ms, p95 %.1f ms (%d beyond it), p99 %.1f ms, "
              "max %.1f ms"
              % (info["requests"], info["p50_ms"], info["p90_ms"],
                 info["p95_ms"], info["beyond_p95"], info["p99_ms"],
                 info["max_ms"]), flush=True)
    if opts.series:
        opts.series({"edges": edges, "records": [
            {k: v for k, v in r.items() if k != "tokens"} for r in records],
            "setup_s": setup_s, "t0": load.t0, "trace": facts["trace"]})

    # ---- correctness, once the window has closed and the model is freed ---
    t0 = time.perf_counter()
    del model, repo, server
    finished = [r for r in good if t_open <= r["t_done"] <= t_close]
    ok = bool(finished) and failed == 0 and compiles_in_window == 0
    facts["checks"] = checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0}}
    if finished:
        picked = _sample(finished, requests, int(traffic["check_requests"]),
                         opts.seed)
        pairs = [(requests[r["index"]]["prompt"], r["tokens"]) for r in picked]
        control = (opts.control or [None])[0]
        got = ref.served_gaps(opts.seed, sizes, pairs,
                              int(traffic["check_pad_to"]), control)
        # the limit is the mix's: long contexts read wider gaps
        limit = traffic["limits"]["served_gap_per_1k"]
        good_gap = got["served_gap_per_1k"] <= limit
        ok = ok and good_gap
        checks["served_gap_per_1k"] = {"value": got["served_gap_per_1k"],
                                       "limit": limit}
        print("check served_gap_per_1k %.6g  limit %.6g  %s  (%d served tokens "
              "of %d requests, %d of them not the reference's best, widest "
              "gap %.6g; reference took %.1f s)"
              % (got["served_gap_per_1k"], limit,
                 "ok" if good_gap else "FAIL", got["tokens"], got["requests"],
                 got["not_best"], got["served_gap"],
                 time.perf_counter() - t0), flush=True)
        if control:
            print("control %s served_gap_per_1k %.6g (%d tokens not the "
                  "reference's best, widest gap %.6g)"
                  % (control, got["control_gap_per_1k"],
                     got["control_not_best"], got["control_gap"]), flush=True)
    else:
        print("check served_gap_per_1k: the window finished no request  FAIL",
              flush=True)
    facts["correct"] = ok
    facts["attempted"] = len(in_run)
    facts["failed"] = failed
    facts["memory_peak_bytes"] = peak
    facts["devices"] = devs
    return facts


def _jit_misses():
    from mxnet_tpu import telemetry

    return telemetry.counter("mxtpu_jit_cache_miss_total").value
