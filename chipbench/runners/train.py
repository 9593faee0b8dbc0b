"""Runner of the training cells.

One object, the promoted trainer with its state, is built from the seed,
driven through ``warm_steps`` steps by the same loop, call and feed that the
window uses, and handed to the window.  The first three of those steps are
the ones the plain reference follows once the window has closed and the
trainer is freed.  The first half of the warm steps ends in the only drain of
the dispatch queue (the checked losses and norms are fetched there); the
second half refills it, so the window opens on a full queue.

Inside the window the harness does no batch-sized host work: host batches are
a pool made during set-up and cycled; no loss is fetched; step completions
are seen by a watcher thread that blocks on the losses in order, and the loop
keeps ``INFLIGHT_STEPS`` steps dispatched ahead of the newest completion, so
the dispatch queue is never drained.
"""
from __future__ import annotations

import importlib
import itertools
import queue
import threading
import time

from ..lib import device as devlib
from ..lib import slices, trace as tracelib

CHECK_STEPS = 3
INFLIGHT_STEPS = 2


class _Watcher(threading.Thread):
    """Blocks on each step's loss in order and notes when it became ready."""

    def __init__(self, inflight):
        super().__init__(name="chipbench-watcher", daemon=True)
        self.q = queue.Queue()
        self.done = []                  # completion time of step i
        self.room = threading.Semaphore(inflight)
        self.open_index = None          # the step whose completion opens
        self.seconds = None             # the window, and its length
        self.close_at = None
        self.closed = threading.Event()
        self.error = None

    def run(self):
        import jax

        while True:
            item = self.q.get()
            if item is None:
                return
            try:
                jax.block_until_ready(item)
            except Exception as e:  # noqa: BLE001 — reported by the main loop
                self.error = e
                self.closed.set()
                self.room.release()
                return
            now = time.perf_counter()
            self.done.append(now)
            if len(self.done) - 1 == self.open_index:
                self.close_at = now + self.seconds
            if self.close_at is not None and now >= self.close_at:
                self.closed.set()
            self.room.release()


def _program_norm_fns(lr, wd):
    import jax
    import jax.numpy as jnp

    def norm(a):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

    @jax.jit
    def grad_norms(moms, w0):
        # SGD with momentum, first step from zero momentum:
        # mom1 = -lr * (g + wd * w0)  =>  g = -mom1 / lr - wd * w0
        return jnp.stack([norm(-m / lr - wd * w) for m, w in zip(moms, w0)])

    @jax.jit
    def dw_norms(ws, w0):
        return jnp.stack([norm(a - b) for a, b in zip(ws, w0)])

    return grad_norms, dw_norms


def compare(program, reference):
    """The numbers `correct` is decided on, each beside nothing yet: the
    caller holds them against the configuration's limits.  Norm gaps are the
    gap between the program's norm and the reference's (not the norm of a
    difference), against the reference's norm of that leaf or of the median
    leaf, whichever is larger, worst leaf."""
    import numpy as np

    out = {"loss_rel_gap": max(
        abs(p - r) / abs(r)
        for p, r in zip(program["losses"], reference["losses"]))}
    for key, name in (("grad_norms", "grad_norm_gap"),
                      ("dw_norms", "dw_norm_gap")):
        p = np.asarray(program[key], np.float64)
        r = np.asarray(reference[key], np.float64)
        scale = np.maximum(r, np.median(r))
        gaps = np.abs(p - r) / scale
        worst = int(np.argmax(gaps))
        out[name] = float(gaps[worst])
        out[name + "_leaf"] = reference["names"][worst]
        order = np.argsort(-gaps)[:4]
        out[name + "_top"] = " ".join("%s=%.3g" % (reference["names"][i],
                                                   gaps[i]) for i in order) \
            + " median=%.3g" % float(np.median(gaps))
    return out


def judge(numbers, limits):
    """[(name, value, limit, ok)] and the verdict."""
    rows = []
    for name, limit in sorted(limits.items()):
        value = numbers[name]
        rows.append((name, value, limit, bool(value == value and value <= limit)))
    return rows, all(r[3] for r in rows)


def run(cell, config, traffic, opts, t_process):
    import jax
    import numpy as np

    from mxnet_tpu import compile as mxc
    from mxnet_tpu.telemetry import goodput

    chips = cell["chips"]
    devs = devlib.devices_or_exit(chips, allow_cpu=opts.rehearse)
    ref = importlib.import_module(config["reference"])
    factory = importlib.import_module(config["model"]["factory"])
    sizes = dict(config["sizes"])
    if "batch" in traffic:          # a mix may state its own global batch
        sizes["batch"] = int(traffic["batch"])
    opt = config["optimizer"]
    warm_steps = int(traffic["warm_steps"])
    settle_steps = warm_steps // 2
    slice_steps = int(traffic["slice_steps"])
    if warm_steps - settle_steps < CHECK_STEPS + 1:
        raise ValueError("the first half of warm_steps must cover the %d "
                         "checked steps and one more" % CHECK_STEPS)
    stamp = _Stamps(t_process)
    stamp("imports and backend")

    # ---- set-up: weights and the pool of host batches, from the seed ------
    weights = ref.make_weights(opts.seed, sizes)
    stamp("weights")
    batches = ref.make_batches(opts.seed, sizes, int(traffic["pool_batches"]))
    stamp("host batches")
    trainer, names = factory.build(config, weights, chips)
    stamp("model and trainer")
    if opts.break_step:
        opts.break_step(trainer)
    sharded = trainer.sharded
    state_sharding = sharded._shardings
    w0 = [jax.device_put(weights[n], state_sharding[i])
          for n, i in zip(names, sharded._trainable)]
    del weights
    grad_norms, dw_norms = _program_norm_fns(
        float(opt["learning_rate"]), float(opt["wd"]))

    feed = trainer.prefetch(itertools.cycle(batches))
    watcher = _Watcher(INFLIGHT_STEPS)
    watcher.start()
    waits, dispatches = [], []
    first_losses, prog_g, prog_dw = [], None, None
    annotate = jax.profiler.TraceAnnotation

    def one_step():
        with annotate("chipbench.wait_inflight"):
            watcher.room.acquire()
        t0 = time.perf_counter()
        with annotate("chipbench.next_feed"):
            xb, yb = next(feed)
        t1 = time.perf_counter()
        with annotate("chipbench.step_batch"):
            loss = trainer.step_batch(xb, yb)
        t2 = time.perf_counter()
        waits.append(t1 - t0)
        dispatches.append(t2 - t1)
        watcher.q.put(loss._data)
        return loss

    tracing = None
    try:
        # the checked steps and the rest of the warm-up, then the only
        # drain of the dispatch queue before the window's end: the first
        # losses and norms are fetched here, `settle_steps` before the
        # window opens, so that it opens on a full queue
        for i in range(warm_steps - settle_steps):
            loss = one_step()
            if i < CHECK_STEPS:
                first_losses.append(loss._data)
            if i == 0:
                prog_g = grad_norms(
                    [jax.tree_util.tree_leaves(s)[0] for s in sharded._states],
                    w0)
            if i == CHECK_STEPS - 1:
                prog_dw = dw_norms(
                    [sharded._arrays[k] for k in sharded._trainable], w0)
                del w0
        while len(watcher.done) < warm_steps - settle_steps:
            if watcher.error is not None:
                raise watcher.error
            time.sleep(0.001)
        stamp("first %d steps (compile included)"
              % (warm_steps - settle_steps))
        program = {"losses": [float(np.asarray(v)) for v in first_losses],
                   "grad_norms": np.asarray(prog_g, np.float64),
                   "dw_norms": np.asarray(prog_dw, np.float64)}
        sig = tuple((tuple(b.shape), str(b.dtype)) for b in batches[0])
        exe = mxc.compiled(sharded._step_key(sig))
        mem = exe.memory_analysis() if exe is not None else None
        compile_s = goodput.totals()["phases"].get("compile", 0.0)
        misses0 = _jit_misses()
        # the window opens at the completion of the last warm step, seen by
        # the watcher while this loop keeps dispatching
        watcher.seconds = opts.seconds
        watcher.open_index = warm_steps - 1
        step = warm_steps - settle_steps
        while not watcher.closed.is_set():
            one_step()
            step += 1
        if opts.trace and watcher.error is None:
            # the profiler's own start and stop stall the host for seconds:
            # it runs over steps of its own, once the window has closed and
            # with the loop still going, so the slices stay the program's
            tracing = tracelib.start()
            for _ in range(int(traffic["trace_steps"])):
                one_step()
                step += 1
            while len(watcher.done) < step and watcher.error is None:
                time.sleep(0.001)
            tracing.stop()
        if watcher.error is not None:
            raise watcher.error
        watcher.q.put(None)
        watcher.join()
    finally:
        feed.close()
        if tracing is not None and not tracing.stopped:
            tracing.stop()
    dispatched = step
    done = list(watcher.done)
    setup_s = done[warm_steps - 1] - t_process
    stamp.at("window open", done[warm_steps - 1])
    print("setup: " + "; ".join("%s %.1f s" % kv for kv in stamp.rows),
          flush=True)
    compiles_in_window = _jit_misses() - misses0
    peak = devlib.memory_peak_bytes(devs)

    # ---- the window, read ------------------------------------------------
    edges, work = slices.step_slices(done[warm_steps - 1:], slice_steps,
                                     sizes["batch"])
    window = slices.summary(edges, work)
    n_in = (len(edges) - 1) * slice_steps
    lo, hi = warm_steps, warm_steps + n_in
    facts = {
        "kind": "train", "cell": cell, "config": config, "traffic": traffic,
        "chips": chips, "device_kind": devs[0].device_kind,
        "platform": devs[0].platform,
        "window": window, "setup_s": setup_s, "compile_s": compile_s,
        "data_wait_s": sum(waits[lo:hi]), "dispatch_s": sum(dispatches[lo:hi]),
        "step_memory": None if mem is None else {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes},
        "compiles_in_window": compiles_in_window,
        "trace": None if tracing is None else tracing.reduce(chips),
    }
    print("window: %d steps in %.3f s, %.2f items/s; %d slices of %d: median "
          "%.2f, min %.2f, max %.2f; stall share %.3f%%; data wait "
          "%.3f s; steps dispatched %d, compiles inside the window %d"
          % (n_in, window["window_s"], window["mean_rate"], window["slices"],
             slice_steps, window["median_rate"], window["min_rate"],
             window["max_rate"], window["stall_share_pct"],
             facts["data_wait_s"], dispatched, compiles_in_window),
          flush=True)
    if opts.series:
        opts.series({"done": done, "wait": waits, "dispatch": dispatches,
                     "warm_steps": warm_steps, "t_process": t_process,
                     "setup_s": setup_s, "trace": facts["trace"]})

    # ---- correctness, outside the window and outside set-up --------------
    del trainer, sharded, feed
    t0 = time.perf_counter()
    reference = ref.follow(opts.seed, sizes, batches, opt, CHECK_STEPS,
                           "float32")
    numbers = compare(program, reference)
    rows, ok = judge(numbers, config["limits"])
    ok = ok and compiles_in_window == 0
    for name, value, limit, good in rows:
        print("check %-16s %.6g  limit %.6g  %s%s"
              % (name, value, limit, "ok" if good else "FAIL",
                 "  (worst leaves: %s)" % numbers[name + "_top"]
                 if name + "_top" in numbers else ""), flush=True)
    print("check losses program %s reference %s; reference took %.1f s"
          % (["%.5f" % v for v in program["losses"]],
             ["%.5f" % v for v in reference["losses"]],
             time.perf_counter() - t0), flush=True)
    if opts.control:
        for precision in opts.control:
            c = ref.follow(opts.seed, sizes, batches, opt, CHECK_STEPS,
                           precision)
            print("control %s %s" % (precision, {
                k: v for k, v in compare(c, reference).items()}), flush=True)
    facts["checks"] = dict(
        {name: {"value": value, "limit": limit}
         for name, value, limit, _ in rows},
        compiles_in_window={"value": compiles_in_window, "limit": 0})
    facts["correct"] = ok
    facts["attempted"] = n_in
    facts["failed"] = 0
    facts["memory_peak_bytes"] = peak
    facts["devices"] = devs
    return facts


class _Stamps:
    """Where set-up's seconds go, printed on an earlier line."""

    def __init__(self, t0):
        self.last, self.rows = t0, []

    def at(self, what, now):
        self.rows.append((what, now - self.last))
        self.last = now

    def __call__(self, what):
        self.at(what, time.perf_counter())


def _jit_misses():
    from mxnet_tpu import telemetry

    return telemetry.counter("mxtpu_jit_cache_miss_total").value
