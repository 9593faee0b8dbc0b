#!/usr/bin/env python
"""serve_bench: open/closed-loop load generator for the serving subsystem
(docs/serving.md load-test playbook).

Builds (or loads) a model, serves it in-process through the real HTTP
stack (`ServingServer` on 127.0.0.1), and measures four phases:

  1. ``sequential`` — one closed-loop client, single-example requests:
     the predict-API baseline the batcher must beat.
  2. ``batched`` — N closed-loop clients, single-example requests: the
     dynamic-batching payoff at the SAME per-request deadline budget.
  3. ``mixed`` — N clients with varying per-request example counts:
     exercises every padding bucket; the executable-cache proof is that
     ZERO ``jit_compile`` events fire in this phase (warmup covered all
     buckets).
  4. ``open`` (optional, ``--open-rate``) — Poisson arrivals at a fixed
     rate: latency under a load the server does not control.

``--generate`` runs the decode row instead (docs/serving.md
§Generation): a tiny decoder-only LM is exported and served through the
continuous-batching scheduler + paged KV cache, N closed-loop clients
fire ``:generate`` requests with RANDOM prompt lengths and UNEQUAL
``max_new_tokens`` (the workload shape batch-synchronous serving cannot
batch), and the row reports tokens/sec, inter-token p50/p99 from the
``mxtpu_serve_intertoken_seconds`` histogram, KV-page peak occupancy,
and the post-warm jit-compile count (must be 0).

``--autoscale`` runs the elasticity row instead (docs/serving.md
§Autoscaling surge playbook): the model is served through a 1-replica
pool with the `Autoscaler` armed, an open-loop surge overdrives it, and
the row reports the measured scale-up latency (surge start -> the grown
pool fully serving), the p99-verdict recovery time, the idle
scale-down, the decision counters, and that no request answered 500.
Closed-loop clients in every row honor ``Retry-After`` on 429/503
(the honored count rides the JSON) — hammering a shedding server both
skews the loss-window rps and fights the recovery window.

``--failover`` runs the resilience row instead (docs/serving.md
chaos-testing playbook): the model is served through a supervised
``--replicas N`` pool, a closed-loop workload runs for
``--failover-duration`` seconds, and ``--kill-after`` seconds in one
replica is SIGKILLed mid-run. The row reports the error-rate and
status-code breakdown (every request must resolve to 200/429/503/504 —
nothing silently dropped), throughput overall and DURING the
single-replica loss window (must stay > 0), and the
recovery-time-to-healthy measured from the kill to the respawned
replica's ready heartbeat.

Emits one JSON document on stdout: p50/p99 latency, throughput,
speedup over sequential, batch occupancy, error counts by status, and
the jit-compile-after-warmup count. Run under a fresh
``MXTPU_TELEMETRY_DIR`` to archive the full metrics JSONL next to the
result (tools/bench_capture.sh `serve_resnet18` / `serve_failover`
rows do).

Offline evidence (CPU):

  JAX_PLATFORMS=cpu python tools/serve_bench.py > BENCH_serve.json
  JAX_PLATFORMS=cpu python tools/serve_bench.py --failover \
      > BENCH_failover.json
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------

def _build_mlp(tmpdir):
    """A BLAS-bound MLP: per-call overhead dominates single-request serving,
    so batching headroom is visible even on CPU."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential(prefix="bench_")
    with net.name_scope():
        net.add(gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(1024, activation="relu"),
                gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()
    net(mx.nd.array(np.zeros((1, 64), np.float32)))
    prefix = os.path.join(tmpdir, "mlp")
    net.export(prefix, epoch=0)
    return prefix, {"data": (64,)}


def _build_resnet18(tmpdir, image_size):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet18_v1()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()
    shape = (3, image_size, image_size)
    net(mx.nd.array(np.zeros((1,) + shape, np.float32)))
    prefix = os.path.join(tmpdir, "resnet18")
    net.export(prefix, epoch=0)
    return prefix, {"data": shape}


def _build_lm(tmpdir, vocab=512):
    """A small decoder-only LM (2 layers, d=64) exported as a generation
    artifact — big enough that a decode step does real matmuls, small
    enough that the CPU row stays fast."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.serving import save_lm

    lm = TransformerLM(vocab_size=vocab, units=64, hidden_size=128,
                       num_layers=2, num_heads=4, max_length=128)
    lm.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return save_lm(lm, os.path.join(tmpdir, "lm")), vocab


def _hist_quantile(snap_entry, q):
    """Approximate a quantile from a cumulative-bucket histogram
    snapshot (upper-bound of the bucket where the quantile falls)."""
    if not snap_entry or not snap_entry.get("count"):
        return None
    total = snap_entry["count"]
    items = []
    for bound, cum in snap_entry.get("buckets", {}).items():
        items.append((float("inf") if bound == "+Inf" else float(bound),
                      cum))
    items.sort()
    target = q * total
    for bound, cum in items:
        if cum >= target:
            return None if bound == float("inf") else bound
    return None


# ---------------------------------------------------------------------------
# the decode row (docs/serving.md §Generation)
# ---------------------------------------------------------------------------

def _run_generate(args, log):
    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ModelRepository, ServingServer

    tmpdir = tempfile.mkdtemp(prefix="serve_bench_lm_")
    log("building + exporting LM (vocab %d) ..." % args.gen_vocab)
    prefix, vocab = _build_lm(tmpdir, vocab=args.gen_vocab)
    repo = ModelRepository()
    t0 = time.perf_counter()
    model = repo.load(
        "bench", prefix, generate=True,
        generate_opts=dict(num_pages=args.kv_pages,
                           page_size=args.kv_page_size,
                           max_prompt=args.max_prompt,
                           max_new_tokens=args.max_new_tokens,
                           max_batch=args.gen_max_batch),
        queue_depth=max(256, args.clients * 4))
    load_s = time.perf_counter() - t0
    gi = model.generate_info
    log("loaded: decode buckets %s, prefill buckets %s, kv %d pages x %d "
        "tokens, warm %.1fs"
        % (gi["decode_buckets"], gi["prefill_buckets"], gi["num_pages"],
           gi["page_size"], model.warm_seconds or 0.0))

    misses = telemetry.get_registry().counter("mxtpu_jit_cache_miss_total")
    base_miss = misses.value

    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    endpoint = ("127.0.0.1", server.port, "/v1/models/bench:generate")
    timeout_s = args.timeout_ms / 1e3 + 10.0

    # random prompts + UNEQUAL budgets: the continuous-batching workload
    rng = random.Random(0)
    nprng = np.random.RandomState(0)
    payloads = []
    for _ in range(64):
        plen = rng.randint(2, args.max_prompt)
        payloads.append(json.dumps({
            "tokens": [int(t) for t in nprng.randint(1, vocab, plen)],
            "max_new_tokens": rng.randint(max(2, args.max_new_tokens // 4),
                                          args.max_new_tokens),
            "timeout_ms": args.timeout_ms,
        }).encode())

    # KV occupancy watcher (scheduler-side gauge, sampled)
    alloc = model.scheduler.allocator
    peak = {"used": 0}
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            peak["used"] = max(peak["used"], alloc.used_pages)
            time.sleep(0.002)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()

    log("closed loop: %d clients x %d generations ..."
        % (args.clients, args.requests))
    t0 = time.perf_counter()
    phase = _closed_loop(endpoint, payloads, clients=args.clients,
                         requests_each=args.requests, timeout_s=timeout_s)
    wall = time.perf_counter() - t0
    stop.set()
    watcher.join(timeout=1.0)

    snap = telemetry.snapshot()
    label = '{model="%s/%d"}' % (model.name, model.version)
    tokens = snap.get("mxtpu_serve_generated_tokens_total" + label,
                      {}).get("value", 0)
    steps = snap.get("mxtpu_serve_decode_steps_total" + label,
                     {}).get("value", 0)
    inter = snap.get("mxtpu_serve_intertoken_seconds" + label, {})
    prefill = snap.get("mxtpu_serve_prefill_seconds" + label, {})
    # first tokens are sampled by PREFILL, not decode steps — exclude
    # them so the mean decode batch is honest occupancy, not inflated
    # by one request's worth per admission
    decode_tokens = tokens - (prefill.get("count") or 0)
    jit_after_warm = misses.value - base_miss
    p50 = _hist_quantile(inter, 0.50)
    p99 = _hist_quantile(inter, 0.99)
    result = {
        "mode": "serve_decode",
        "net": "transformer_lm",
        "device": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
                  else "default",
        "generate": gi,
        "clients": args.clients,
        "requests": phase["requests"],
        "codes": phase["codes"],
        "wall_s": round(wall, 3),
        "load_s": round(load_s, 2),
        "warm_s": round(model.warm_seconds or 0.0, 2),
        "generated_tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 2) if wall else None,
        "decode_steps": steps,
        "mean_decode_batch": round(decode_tokens / steps, 2)
                             if steps else None,
        "request_p50_ms": phase["p50_ms"],
        "request_p99_ms": phase["p99_ms"],
        "intertoken_p50_ms": round(p50 * 1e3, 3) if p50 else None,
        "intertoken_p99_ms": round(p99 * 1e3, 3) if p99 else None,
        "prefill_mean_ms": round(prefill["sum"] / prefill["count"] * 1e3, 3)
                           if prefill.get("count") else None,
        "kv": {
            "pages_total": alloc.num_pages,
            "page_size": alloc.page_size,
            "peak_pages_used": peak["used"],
            "peak_occupancy": round(peak["used"] / alloc.num_pages, 3),
            "pages_used_at_drain": alloc.used_pages,
        },
        "jit_compiles_after_warmup": jit_after_warm,
        # decode rows carry health verdicts too (inter-token p99 + KV
        # occupancy objectives register at scheduler load)
        "slo": _slo_block([_slo_sample("decode")], args.slo_spec),
    }
    log("decode: %.1f tok/s, inter-token p99 %sms, kv peak %d/%d pages, "
        "jit after warm %d, pages at drain %d"
        % (result["tokens_per_sec"] or 0.0, result["intertoken_p99_ms"],
           peak["used"], alloc.num_pages, jit_after_warm,
           alloc.used_pages))
    server.drain(shutdown=True)
    telemetry.flush(reason="serve_bench_decode")
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0


# ---------------------------------------------------------------------------
# load phases
# ---------------------------------------------------------------------------

def _slo_sample(phase):
    """Condensed SLO verdicts (one row per objective) sampled at a phase
    boundary — the health trail a committed bench row carries."""
    from mxnet_tpu.telemetry import slo as _slo

    return {"phase": phase, "verdicts": [
        {"slo": v["slo"], "healthy": v["healthy"], "page": v["page"],
         "ticket": v["ticket"], "no_data": v["no_data"],
         "burn_rate": v["burn_rate"], "value": v["value"],
         "budget_remaining": v["budget_remaining"]}
        for v in _slo.verdicts()]}


def _slo_block(samples, spec_path):
    """The output `slo` block: per-phase samples + the final full
    verdicts (the machine-readable health stamp next to the latency
    points)."""
    from mxnet_tpu.telemetry import slo as _slo

    return {"spec": spec_path,
            "evaluator_running": _slo.running(),
            "samples": samples,
            "final": _slo.verdicts()}


def _phase_breakdown(spans):
    """Aggregate collected span records into the per-phase latency table
    (queue / assembly / wire / compute / unpad — plus the request total)
    and find the slowest request's trace id, the one to feed
    `tools/trace_merge.py --trace <id>`."""
    by_phase = {}
    slowest = None
    for s in spans:
        name = s.get("name", "")
        if not name.startswith("serve."):
            continue
        dur_ms = (s.get("dur_us") or 0) / 1e3
        phase = name.split(".", 1)[1]
        by_phase.setdefault(phase, []).append(dur_ms)
        attrs = s.get("attrs") or {}
        if phase == "dispatch" and "wire_s" in attrs:
            # router-side split of the dispatch window: serialization +
            # hop cost vs the replica's own compute
            by_phase.setdefault("wire", []).append(attrs["wire_s"] * 1e3)
        if phase == "request" and (slowest is None
                                   or dur_ms > slowest["total_ms"]):
            slowest = {"trace_id": s.get("trace"),
                       "total_ms": round(dur_ms, 3)}
    phases = {}
    for phase, vals in sorted(by_phase.items()):
        vals.sort()
        phases[phase] = {
            "count": len(vals),
            "mean_ms": round(sum(vals) / len(vals), 3),
            "p50_ms": round(_percentile(vals, 50), 3),
            "p99_ms": round(_percentile(vals, 99), 3),
        }
    return phases, slowest


def _percentile(sorted_ms, q):
    if not sorted_ms:
        return None
    i = min(len(sorted_ms) - 1, int(round(q * (len(sorted_ms) - 1))))
    return sorted_ms[i]


class _Client:
    """One persistent keep-alive connection (the realistic steady-client
    shape: no TCP setup or server thread spawn per request)."""

    # well-behaved clients honor Retry-After, but a bench must stay
    # bounded: a server-suggested backoff is capped here
    RETRY_AFTER_CAP_S = 5.0

    def __init__(self, host, port, path, timeout_s):
        self.host, self.port, self.path = host, port, path
        self.timeout_s = timeout_s
        self.conn = None
        self.retry_after_honored = 0

    def post(self, body):
        t0 = time.perf_counter()
        retry_after = None
        try:
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout_s)
            self.conn.request("POST", self.path, body=body,
                              headers={"Content-Type": "application/json"})
            r = self.conn.getresponse()
            r.read()
            code = r.status
            retry_after = r.getheader("Retry-After")
            if r.will_close:
                self.conn.close()
                self.conn = None
        except Exception:
            code = -1
            if self.conn is not None:
                self.conn.close()
                self.conn = None
        return (time.perf_counter() - t0) * 1e3, code, retry_after

    def backoff(self, code, retry_after):
        """Honor a 429/503's Retry-After before the next closed-loop
        request. Hammering a shedding server immediately both skews the
        measured loss-window rps and FIGHTS the recovery the autoscaler
        (or a respawning replica) is buying — the exact anti-pattern the
        header exists to prevent. Returns True when a backoff was
        served."""
        if code not in (429, 503) or not retry_after:
            return False
        try:
            delay = float(retry_after)
        except ValueError:
            return False
        if delay <= 0:
            return False
        time.sleep(min(delay, self.RETRY_AFTER_CAP_S))
        self.retry_after_honored += 1
        return True

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def _closed_loop(endpoint, payloads, clients, requests_each, timeout_s):
    """`clients` threads, each firing `requests_each` back-to-back posts
    over its own persistent connection — honoring ``Retry-After`` on
    429/503 sheds like a well-behaved client (the honored count rides
    the phase result)."""
    lats, codes, lock = [], {}, threading.Lock()
    honored = [0]

    def worker(wid):
        cli = _Client(*endpoint, timeout_s=timeout_s)
        mine = []
        my_codes = {}
        for i in range(requests_each):
            ms, code, retry_after = cli.post(
                payloads[(wid + i) % len(payloads)])
            mine.append(ms)
            my_codes[code] = my_codes.get(code, 0) + 1
            cli.backoff(code, retry_after)
        cli.close()
        with lock:
            lats.extend(mine)
            honored[0] += cli.retry_after_honored
            for c, n in my_codes.items():
                codes[c] = codes.get(c, 0) + n

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lats.sort()
    total = clients * requests_each
    return {
        "requests": total,
        "wall_s": round(wall, 3),
        "rps": round(total / wall, 2),
        "p50_ms": round(_percentile(lats, 0.50), 3),
        "p99_ms": round(_percentile(lats, 0.99), 3),
        "mean_ms": round(sum(lats) / len(lats), 3),
        "codes": {str(k): v for k, v in sorted(codes.items())},
        "retry_after_honored": honored[0],
    }


def _open_loop(endpoint, payloads, rate, duration, timeout_s):
    """Poisson arrivals at `rate`/s for `duration`s (bounded concurrency)."""
    lats, codes, lock = [], {}, threading.Lock()
    sem = threading.Semaphore(256)
    threads = []
    rng = random.Random(0)

    def one(body):
        try:
            cli = _Client(*endpoint, timeout_s=timeout_s)
            ms, code, _ = cli.post(body)  # open loop: arrivals are not
            cli.close()                   # paced by the server's hints
            with lock:
                lats.append(ms)
                codes[code] = codes.get(code, 0) + 1
        finally:
            sem.release()

    t0 = time.perf_counter()
    next_t = t0
    i = 0
    while True:
        next_t += rng.expovariate(rate)
        if next_t - t0 > duration:
            break
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        sem.acquire()
        t = threading.Thread(target=one, args=(payloads[i % len(payloads)],),
                             daemon=True)
        t.start()
        threads.append(t)
        i += 1
    for t in threads:
        t.join(timeout=timeout_s + 5)
    wall = time.perf_counter() - t0
    lats.sort()
    return {
        "target_rate": rate,
        "duration_s": duration,
        "requests": len(lats),
        "achieved_rps": round(len(lats) / wall, 2) if lats else 0.0,
        "p50_ms": round(_percentile(lats, 0.50), 3) if lats else None,
        "p99_ms": round(_percentile(lats, 0.99), 3) if lats else None,
        "codes": {str(k): v for k, v in sorted(codes.items())},
    }


def _closed_loop_timed(endpoint, payloads, clients, duration_s, timeout_s):
    """`clients` threads firing back-to-back posts until `duration_s`
    elapses, honoring ``Retry-After`` on sheds (a closed-loop client
    that hammers a degraded pool skews the loss-window rps AND fights
    the recovery window). Returns per-request (t_done, ms, code) records
    (t_done on the shared perf_counter clock) plus the honored-backoff
    count, so callers can window the timeline around an injected
    failure."""
    recs, lock = [], threading.Lock()
    honored = [0]
    t0 = time.perf_counter()

    def worker(wid):
        cli = _Client(*endpoint, timeout_s=timeout_s)
        mine = []
        i = 0
        while time.perf_counter() - t0 < duration_s:
            ms, code, retry_after = cli.post(
                payloads[(wid + i) % len(payloads)])
            mine.append((time.perf_counter() - t0, ms, code))
            i += 1
            cli.backoff(code, retry_after)
        cli.close()
        with lock:
            recs.extend(mine)
            honored[0] += cli.retry_after_honored

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0, recs, honored[0]


def _watch_pool(pool, timeline, stop, interval_s=0.005):
    """Sample the pool's healthy-replica count into `timeline` as
    (t_perf_counter, healthy) transition records."""
    last = None
    while not stop.is_set():
        h = pool.healthy_count
        if h != last:
            timeline.append((time.perf_counter(), h))
            last = h
        time.sleep(interval_s)
    # one closing sample: the caller stops the watch the instant the pool
    # reports full health, which can land between two samples
    h = pool.healthy_count
    if h != last:
        timeline.append((time.perf_counter(), h))


def _payload(arr, timeout_ms):
    return json.dumps({"inputs": {"data": arr.tolist()},
                       "timeout_ms": timeout_ms}).encode()


# ---------------------------------------------------------------------------
# the failover row (docs/serving.md chaos-testing playbook)
# ---------------------------------------------------------------------------

def _run_failover(args, prefix, input_shapes, log):
    """Closed-loop load over a supervised replica pool with one replica
    SIGKILLed mid-run. The evidence this row commits: throughput during
    the single-replica loss stays > 0, every request resolves to a
    deterministic status (200/429/503/504 — nothing silently dropped, no
    500s), and the pool recovers to full health."""
    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ModelRepository, ServingServer

    repo = ModelRepository()
    t0 = time.perf_counter()
    model = repo.load("bench", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch, max_delay_ms=args.delay_ms,
                      queue_depth=max(1024, args.clients * 4),
                      replicas=args.replicas)
    load_s = time.perf_counter() - t0
    pool = model.pool
    log("pooled load: %d replicas, buckets=%s, %.1fs (per-replica load + "
        "warm)" % (args.replicas, model.buckets, load_s))

    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    endpoint = ("127.0.0.1", server.port, "/v1/models/bench:predict")
    timeout_s = args.timeout_ms / 1e3 + 10.0
    shape = next(iter(input_shapes.values()))
    rng = np.random.RandomState(0)
    payloads = [_payload(rng.uniform(-1, 1, (1,) + shape).astype(np.float32),
                         args.timeout_ms) for _ in range(8)]

    timeline, stop = [], threading.Event()
    watcher = threading.Thread(target=_watch_pool,
                               args=(pool, timeline, stop), daemon=True)
    watcher.start()
    kill_rec = {}

    def killer():
        time.sleep(args.kill_after)
        pid = pool.replica_pid(0)
        kill_rec["t"] = time.perf_counter()
        kill_rec["pid"] = pid
        log("SIGKILL replica 0 (pid %s) at t=%.1fs" % (pid, args.kill_after))
        try:
            os.kill(pid, 9)
        except OSError as e:
            kill_rec["error"] = str(e)

    threading.Thread(target=killer, daemon=True).start()
    log("closed loop: %d clients for %.0fs, kill at %.0fs ..."
        % (args.clients, args.failover_duration, args.kill_after))
    t_run, recs, honored = _closed_loop_timed(
        endpoint, payloads, args.clients, args.failover_duration,
        timeout_s)
    # let the respawn land even when the kill came late in the window
    recovery_deadline = time.perf_counter() + 60.0
    while pool.healthy_count < args.replicas and \
            time.perf_counter() < recovery_deadline:
        time.sleep(0.02)
    stop.set()
    watcher.join(timeout=2.0)

    t_kill = kill_rec.get("t")
    recovery_s = None
    if t_kill is not None:
        recovered = [t for (t, h) in timeline
                     if t > t_kill and h >= args.replicas]
        if recovered:
            recovery_s = recovered[0] - t_kill
    loss_end = t_kill + recovery_s if (t_kill is not None
                                       and recovery_s is not None) \
        else t_run + args.failover_duration
    loss = [r for r in recs if t_kill is not None
            and t_kill <= t_run + r[0] <= loss_end]
    codes = {}
    for _, _, code in recs:
        codes[code] = codes.get(code, 0) + 1
    lats = sorted(ms for _, ms, _ in recs)
    ok = codes.get(200, 0)
    resolved = all(c in (200, 429, 503, 504) for c in codes)
    snap = telemetry.snapshot()
    label = '{model="%s/%d"}' % (model.name, model.version)

    def counter(name):
        return snap.get(name + label, {}).get("value", 0)

    wall = max(r[0] for r in recs) if recs else args.failover_duration
    result = {
        "mode": "serve_failover",
        "net": os.path.basename(args.model) if args.model else args.net,
        "device": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
                  else "default",
        "replicas": args.replicas,
        "buckets": model.buckets,
        "duration_s": args.failover_duration,
        "kill_after_s": args.kill_after,
        "load_s": round(load_s, 2),
        "requests": len(recs),
        "codes": {str(k): v for k, v in sorted(codes.items())},
        "error_rate": round(1.0 - ok / len(recs), 4) if recs else None,
        "unresolved": codes.get(-1, 0),
        "all_resolved_deterministically": resolved,
        "rps_overall": round(len(recs) / wall, 2) if recs else 0.0,
        "retry_after_honored": honored,
        "p50_ms": round(_percentile(lats, 0.50), 3) if lats else None,
        "p99_ms": round(_percentile(lats, 0.99), 3) if lats else None,
        "recovery_s": round(recovery_s, 3) if recovery_s is not None
                      else None,
        "loss_window": {
            "requests": len(loss),
            "rps": round(len(loss) / recovery_s, 2)
                   if recovery_s else None,
            "codes": {str(c): sum(1 for r in loss if r[2] == c)
                      for c in sorted({r[2] for r in loss})},
        },
        "healthy_timeline": [
            [round(t - (t_kill or t_run), 3), h] for t, h in timeline],
        "pool": {
            "failovers": counter("mxtpu_serve_failover_total"),
            "requeued": counter("mxtpu_serve_failover_requeued_total"),
            "restarts": counter("mxtpu_serve_replica_restart_total"),
            "final_healthy": pool.healthy_count,
        },
    }
    log("failover: %d reqs, codes=%s, recovery=%.2fs, loss-window rps=%s"
        % (len(recs), result["codes"], recovery_s or -1.0,
           result["loss_window"]["rps"]))
    server.drain(shutdown=True)
    telemetry.flush(reason="serve_bench_failover")
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0


# ---------------------------------------------------------------------------
# the autoscale row (docs/serving.md §Autoscaling surge playbook)
# ---------------------------------------------------------------------------

def _run_autoscale(args, prefix, input_shapes, log):
    """Open-loop surge over a 1-replica pool with the autoscaler armed.
    The evidence this row commits: the surge breaches the serving SLOs,
    the pool scales up IN PLACE (measured scale-up latency = surge start
    to the new replica serving), the p99 verdict recovers (measured
    recovery time), and sustained idle drains the pool back down — with
    every request resolving deterministically (no 500s)."""
    import numpy as np

    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import (Autoscaler, ModelRepository,
                                   ServingServer)
    from mxnet_tpu.telemetry import slo as _slo

    # bench-scale SLO windows: breach + recovery must fit in a ~60s row
    # (the tier-1 chaos e2e uses the same shape at a smaller scale)
    for k, v in (("MXTPU_SLO_WINDOW_MS", "500"),
                 ("MXTPU_SLO_FAST_WINDOWS", "5"),
                 ("MXTPU_SLO_SLOW_WINDOW_S", "60"),
                 ("MXTPU_SLO_SERVE_P99_MS", "500")):
        os.environ.setdefault(k, v)
    _slo.stop()  # a fresh evaluator picks up the bench cadence

    repo = ModelRepository()
    t0 = time.perf_counter()
    model = repo.load("bench", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch, max_delay_ms=args.delay_ms,
                      queue_depth=max(256, args.clients * 4),
                      replicas=1, max_replicas=args.max_replicas)
    load_s = time.perf_counter() - t0
    model.min_replicas = 1
    pool = model.pool
    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    asc = server.attach_autoscaler(Autoscaler(
        repo, interval_ms=500, up_windows=2, idle_s=args.idle_s,
        cooldown_s=2.0))
    endpoint = ("127.0.0.1", server.port, "/v1/models/bench:predict")
    timeout_s = args.timeout_ms / 1e3 + 10.0
    shape = next(iter(input_shapes.values()))
    rng = np.random.RandomState(0)
    payloads = [_payload(rng.uniform(-1, 1, (1,) + shape).astype(np.float32),
                         args.timeout_ms) for _ in range(8)]

    # pool size/health timeline (the scale-up latency evidence)
    timeline, stop = [], threading.Event()

    def watch():
        last = None
        while not stop.is_set():
            cur = (pool.size, pool.healthy_count)
            if cur != last:
                timeline.append((time.perf_counter(), cur[0], cur[1]))
                last = cur
            time.sleep(0.01)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()

    log("phase 1/4: baseline closed loop (%d clients x 10) ..."
        % args.clients)
    baseline = _closed_loop(endpoint, payloads, clients=args.clients,
                            requests_each=10, timeout_s=timeout_s)
    log("  baseline: %.1f rps p99=%.1fms" % (baseline["rps"],
                                             baseline["p99_ms"]))

    # the surge ships HEAVY requests (up to 8 examples each): the
    # overload is measured in examples/sec, so a batching-efficient pool
    # is still genuinely overdriven and the p99/queue objectives breach
    surge_n = min(8, model.max_batch)
    surge_payloads = [
        _payload(rng.uniform(-1, 1, (surge_n,) + shape).astype(np.float32),
                 args.timeout_ms) for _ in range(8)]
    surge_rate = args.surge_rate or max(150.0, 1.5 * baseline["rps"])
    log("phase 2/4: open-loop surge @ %.0f req/s x %d examples for "
        "%.0fs ..." % (surge_rate, surge_n, args.surge_duration))
    t_surge = time.perf_counter()
    surge = _open_loop(endpoint, surge_payloads, surge_rate,
                       args.surge_duration, timeout_s)
    t_surge_end = time.perf_counter()
    # scale-up latency: surge start -> the grown pool fully serving
    scale_up_s = None
    scaled_to = max((s for _, s, _ in timeline), default=1)
    if scaled_to > 1:
        serving = [t for t, s, h in timeline if s > 1 and h >= s]
        if serving:
            scale_up_s = serving[0] - t_surge
    log("  surge: %d reqs, codes=%s; scaled to %d (scale-up %.1fs)"
        % (surge["requests"], surge["codes"], scaled_to,
           scale_up_s or -1.0))

    log("phase 3/4: p99 recovery ...")
    objective = "serve-p99:%s/%d" % (model.name, model.version)
    recovery_s = None
    deadline = time.perf_counter() + 60.0
    while recovery_s is None and time.perf_counter() < deadline:
        v = next((v for v in _slo.verdicts() if v["slo"] == objective),
                 None)
        if v is not None and v["healthy"] and not v["no_data"]:
            recovery_s = time.perf_counter() - t_surge_end
            break
        time.sleep(0.25)
    log("  p99 verdict recovered in %s s" % (round(recovery_s, 2)
                                             if recovery_s else "NEVER"))

    log("phase 4/4: idle scale-down ...")
    scale_down_s = None
    deadline = time.perf_counter() + 60.0
    while pool.size > 1 and time.perf_counter() < deadline:
        time.sleep(0.25)
    if pool.size == 1 and scaled_to > 1:
        scale_down_s = time.perf_counter() - t_surge_end
    time.sleep(1.5)  # let the last remove's drain/decision records land
    stop.set()
    watcher.join(timeout=2.0)

    codes = dict(baseline["codes"])
    for c, n in surge["codes"].items():
        codes[str(c)] = codes.get(str(c), 0) + n
    snap = telemetry.snapshot()

    def decisions(action):
        return snap.get('mxtpu_autoscale_decisions_total{action="%s"}'
                        % action, {}).get("value", 0)

    result = {
        "mode": "serve_autoscale",
        "net": os.path.basename(args.model) if args.model else args.net,
        "device": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
                  else "default",
        "buckets": model.buckets,
        "load_s": round(load_s, 2),
        "baseline": dict(baseline, clients=args.clients),
        "surge": dict(surge, rate=surge_rate),
        "min_replicas": 1,
        "max_replicas": args.max_replicas,
        "scaled_to": scaled_to,
        "scale_up_latency_s": round(scale_up_s, 3)
                              if scale_up_s is not None else None,
        "p99_recovery_s": round(recovery_s, 3)
                          if recovery_s is not None else None,
        "scale_down_s": round(scale_down_s, 3)
                        if scale_down_s is not None else None,
        "final_replicas": pool.size,
        "codes": codes,
        "zero_500s": all(int(c) in (200, 429, 503, 504)
                         for c in codes),
        "retry_after_honored": baseline["retry_after_honored"],
        "decisions": {a: decisions(a)
                      for a in ("up", "down", "evict", "blocked")},
        "decision_trail": asc.describe()["decisions"],
        "size_timeline": [[round(t - t_surge, 3), s, h]
                          for t, s, h in timeline],
        "slo": _slo_block([_slo_sample("surge")], args.slo_spec),
    }
    log("autoscale: scaled 1->%d in %ss, p99 recovered %ss, down in %ss, "
        "codes=%s" % (scaled_to, result["scale_up_latency_s"],
                      result["p99_recovery_s"], result["scale_down_s"],
                      codes))
    server.drain(shutdown=True)
    telemetry.flush(reason="serve_bench_autoscale")
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--net", choices=("mlp", "resnet18"), default="mlp")
    p.add_argument("--model", default=None,
                   help="serve an existing artifact instead of building one "
                        "(export prefix or .mxc; needs --input for a prefix)")
    p.add_argument("--input", default=None, metavar="NAME=DIMS",
                   help="per-example input signature for --model prefixes, "
                        "e.g. data=3x224x224")
    p.add_argument("--image-size", type=int, default=32,
                   help="resnet18 spatial size (32 keeps CPU runs fast)")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--delay-ms", type=float, default=5.0)
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--requests", type=int, default=25,
                   help="closed-loop requests PER CLIENT per phase")
    p.add_argument("--seq-requests", type=int, default=None,
                   help="sequential-phase request count "
                        "(default: clients*requests capped at 200)")
    p.add_argument("--timeout-ms", type=float, default=30000.0,
                   help="per-request deadline used by EVERY phase (equal "
                        "latency budget across sequential and batched)")
    p.add_argument("--open-rate", type=float, default=0.0,
                   help="open-loop phase arrival rate per second (0 = skip)")
    p.add_argument("--open-duration", type=float, default=5.0)
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="distributed-tracing sample rate for the bench "
                        "(1.0 = every request contributes to the "
                        "per-phase breakdown; 0 disables spans)")
    p.add_argument("--generate", action="store_true",
                   help="run the decode row instead: a tiny decoder-only "
                        "LM served through the continuous-batching "
                        "scheduler + paged KV cache (tokens/sec, "
                        "inter-token p99, KV occupancy, jit-after-warm)")
    p.add_argument("--gen-vocab", type=int, default=512)
    p.add_argument("--gen-max-batch", type=int, default=8,
                   help="decode batch buckets = powers of two up to this")
    p.add_argument("--kv-pages", type=int, default=128)
    p.add_argument("--kv-page-size", type=int, default=8)
    p.add_argument("--max-prompt", type=int, default=16)
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--slo-spec", default=None, metavar="PATH",
                   help="JSON SLO spec (MXTPU_SLO_SPEC format) loaded "
                        "before serving starts; the run's verdicts and "
                        "burn rates land in the output's `slo` block "
                        "either way (built-in objectives evaluate "
                        "without a spec)")
    p.add_argument("--failover", action="store_true",
                   help="run the resilience row instead of the throughput "
                        "phases: closed-loop load over a --replicas pool "
                        "with a SIGKILLed replica at --kill-after")
    p.add_argument("--autoscale", action="store_true",
                   help="run the elasticity row instead: open-loop surge "
                        "over a 1-replica pool with the autoscaler armed "
                        "(surge -> measured scale-up latency -> p99 "
                        "recovery -> idle scale-down)")
    p.add_argument("--surge-rate", type=float, default=0.0,
                   help="--autoscale surge arrival rate per second "
                        "(0 = 1.5x the measured baseline, min 150; each "
                        "surge request carries up to 8 examples)")
    p.add_argument("--surge-duration", type=float, default=8.0,
                   help="--autoscale surge length in seconds")
    p.add_argument("--max-replicas", type=int, default=3,
                   help="--autoscale ceiling")
    p.add_argument("--idle-s", dest="idle_s", type=float, default=4.0,
                   help="--autoscale idle window before scale-down")
    p.add_argument("--replicas", type=int, default=2,
                   help="pool size for --failover (>= 2 so the endpoint "
                        "survives a single-replica loss)")
    p.add_argument("--failover-duration", type=float, default=12.0,
                   help="closed-loop seconds for the --failover row")
    p.add_argument("--kill-after", type=float, default=3.0,
                   help="seconds into the --failover run to SIGKILL "
                        "replica 0")
    args = p.parse_args(argv)

    import numpy as np

    import mxnet_tpu  # noqa: F401  (package init pins platform handling)
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ModelRepository, ServingServer

    log = lambda msg: print("[serve_bench] " + msg, file=sys.stderr)  # noqa: E731

    # committed BENCH rows carry machine-readable health verdicts, not
    # just latency points: load any spec objectives up front and sample
    # verdicts/burn rates per phase (docs/observability.md §SLOs)
    if args.slo_spec:
        telemetry.slo.load_spec(args.slo_spec)
        telemetry.slo.start()

    if args.generate:
        return _run_generate(args, log)

    tmpdir = tempfile.mkdtemp(prefix="serve_bench_")
    input_shapes = None
    if args.model:
        prefix = args.model
        if args.input:
            iname, dims = args.input.split("=", 1)
            input_shapes = {iname: tuple(int(d) for d in dims.split("x"))}
    elif args.net == "resnet18":
        log("building resnet18_v1 (%dx%d) ..." % (args.image_size,
                                                  args.image_size))
        prefix, input_shapes = _build_resnet18(tmpdir, args.image_size)
    else:
        log("building mlp ...")
        prefix, input_shapes = _build_mlp(tmpdir)

    if args.failover:
        return _run_failover(args, prefix, input_shapes, log)

    if args.autoscale:
        return _run_autoscale(args, prefix, input_shapes, log)

    # per-phase peak-RSS bookkeeping (telemetry.memory): the serving
    # memory budget's committed CPU evidence needs real residency numbers
    # next to each phase's throughput
    def phase_mem():
        return telemetry.memory.read_process_memory() or {}

    mem_phases = {"start": phase_mem()}

    repo = ModelRepository()
    t0 = time.perf_counter()
    model = repo.load("bench", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch, max_delay_ms=args.delay_ms,
                      queue_depth=max(1024, args.clients * 4))
    load_s = time.perf_counter() - t0
    mem_phases["loaded"] = phase_mem()
    log("loaded buckets=%s warm=%.2fs" % (model.buckets,
                                          model.warm_seconds or 0.0))

    # executable-cache evidence: executor builds BEFORE traffic (warmup
    # compiles one forward per bucket; steady state must add zero)
    builds = telemetry.get_registry().counter(
        "mxtpu_executor_build_total", {"what": "forward"})
    builds_after_warm = builds.value

    # distributed tracing: sample bench traffic; the per-phase breakdown
    # reads the emitted spans back in-process (those a telemetry directory's
    # flusher has already written out are in its JSONL instead)
    tracing = telemetry.tracing
    if args.trace_sample > 0:
        tracing.configure(sample=min(1.0, args.trace_sample))

    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    endpoint = ("127.0.0.1", server.port, "/v1/models/bench:predict")
    timeout_s = args.timeout_ms / 1e3 + 10.0
    shape = next(iter(input_shapes.values()))
    rng = np.random.RandomState(0)

    one = [_payload(rng.uniform(-1, 1, (1,) + shape).astype(np.float32),
                    args.timeout_ms) for _ in range(8)]

    seq_n = args.seq_requests or min(200, args.clients * args.requests)
    log("phase 1/3: sequential x%d ..." % seq_n)
    seq = _closed_loop(endpoint, one, clients=1, requests_each=seq_n,
                       timeout_s=timeout_s)
    log("  sequential: %.1f req/s p50=%.1fms p99=%.1fms"
        % (seq["rps"], seq["p50_ms"], seq["p99_ms"]))
    mem_phases["sequential"] = phase_mem()
    slo_samples = [_slo_sample("sequential")]

    log("phase 2/3: batched closed-loop %d clients x%d ..."
        % (args.clients, args.requests))
    batched = _closed_loop(endpoint, one, clients=args.clients,
                           requests_each=args.requests, timeout_s=timeout_s)
    log("  batched: %.1f req/s p50=%.1fms p99=%.1fms"
        % (batched["rps"], batched["p50_ms"], batched["p99_ms"]))
    mem_phases["batched"] = phase_mem()
    slo_samples.append(_slo_sample("batched"))

    # mixed per-request example counts: every bucket gets traffic, and the
    # executable cache must already hold them all
    sizes = [s for s in (1, 2, 3, 4, 5, 7, 8) if s <= model.max_batch]
    mix_rng = random.Random(0)
    mixed_payloads = [
        _payload(rng.uniform(-1, 1, (mix_rng.choice(sizes),) + shape)
                 .astype(np.float32), args.timeout_ms)
        for _ in range(32)]
    builds_before_mixed = builds.value
    log("phase 3/3: mixed sizes %s ..." % sizes)
    mixed = _closed_loop(endpoint, mixed_payloads, clients=args.clients,
                         requests_each=max(4, args.requests // 2),
                         timeout_s=timeout_s)
    jit_after_warm = builds.value - builds_after_warm
    jit_in_mixed = builds.value - builds_before_mixed
    log("  mixed: %.1f req/s; jit compiles during traffic: %d"
        % (mixed["rps"], jit_after_warm))
    mem_phases["mixed"] = phase_mem()
    slo_samples.append(_slo_sample("mixed"))

    open_phase = None
    if args.open_rate > 0:
        log("open loop @ %.0f req/s for %.0fs ..." % (args.open_rate,
                                                      args.open_duration))
        open_phase = _open_loop(endpoint, one, args.open_rate, args.open_duration,
                                timeout_s)

    # occupancy evidence from the serving metrics themselves
    snap = telemetry.snapshot()
    label = '{model="%s/%d"}' % (model.name, model.version)
    occ = snap.get("mxtpu_serve_batch_occupancy" + label, {})
    bsz = snap.get("mxtpu_serve_batch_size" + label, {})
    batches = snap.get("mxtpu_serve_batches_total" + label, {}).get("value", 0)
    examples = snap.get("mxtpu_serve_examples_total" + label,
                        {}).get("value", 0)

    phases, slowest = _phase_breakdown(list(tracing._PENDING))
    if phases:
        log("  phase breakdown (p50 ms): %s" % {
            k: v["p50_ms"] for k, v in phases.items()})
    if slowest:
        log("  slowest request: %.1fms trace %s (render: python tools/"
            "trace_merge.py --trace %s -o slow.json <telemetry jsonl>)"
            % (slowest["total_ms"], slowest["trace_id"],
               slowest["trace_id"]))
    tracing.configure()

    speedup = round(batched["rps"] / seq["rps"], 2) if seq["rps"] else None
    result = {
        "mode": "serve_bench",
        "net": os.path.basename(args.model) if args.model else args.net,
        "device": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
                  else "default",
        "buckets": model.buckets,
        "max_batch": model.max_batch,
        "delay_ms": args.delay_ms,
        "timeout_ms": args.timeout_ms,
        "load_s": round(load_s, 2),
        "warm_s": round(model.warm_seconds or 0.0, 2),
        "sequential": seq,
        "batched": dict(batched, clients=args.clients),
        "mixed": dict(mixed, sizes=sizes),
        "open": open_phase,
        "speedup_batched_vs_sequential": speedup,
        "jit_compiles_after_warmup": jit_after_warm,
        "jit_compiles_in_mixed_phase": jit_in_mixed,
        # span-derived per-phase latency split + the trace id to render
        # for the worst request (tools/trace_merge.py --trace <id>)
        "phases": phases or None,
        "slowest_request": slowest,
        "trace_sample": args.trace_sample,
        "bucket_flops": model.bucket_flops or None,
        # per-executable memory attribution of the served model (what the
        # MXTPU_SERVE_MEMORY_BUDGET admission check prices) + peak RSS at
        # each phase boundary (docs/observability.md §Memory)
        "model_memory": {"total_bytes": model.memory_bytes,
                         "per_bucket": {str(b): f for b, f in
                                        sorted(model.bucket_memory.items())}},
        "memory_phases": mem_phases,
        # machine-readable health verdicts sampled during the run
        # (docs/observability.md §SLOs): committed BENCH rows say whether
        # the run was healthy, not just how fast it went
        "slo": _slo_block(slo_samples, args.slo_spec),
        "occupancy": {
            "batches": batches,
            "examples": examples,
            "mean_batch": round(examples / batches, 2) if batches else None,
            "mean_fill": round(occ["sum"] / occ["count"], 3)
                         if occ.get("count") else None,
            "batch_size_hist": bsz.get("buckets"),
        },
    }
    server.drain(shutdown=True)
    telemetry.flush(reason="serve_bench")  # archive JSONL when dir is set
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
