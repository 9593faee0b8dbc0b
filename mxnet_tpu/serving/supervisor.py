"""Replica process supervision + the replica worker entry point.

The serving resilience layer (docs/serving.md §resilience) runs each
served model as N *replica worker processes* so a wedged executor, a
poisoned request, or an OOM kills one process, not the endpoint. This
module is the process half of that design; the routing half is
`replica_pool.ReplicaPool`.

It deliberately reuses the `tools/launch.py` supervision machinery's
shape (docs/fault_tolerance.md): workers are spawned as session leaders
so teardown can signal the whole process GROUP (grandchildren die too),
teardown escalates SIGTERM → SIGKILL over `MXTPU_TEARDOWN_GRACE`, every
respawn bumps a per-replica restart *generation* exported as
`MXTPU_RESTART_GENERATION` (the same variable the elastic launcher uses,
so `MXTPU_FAULT_INJECT`'s ``gen=`` condition gates replica faults exactly
like trainer faults — a respawned replica does NOT re-fire its fault),
and respawns back off exponentially (`MXTPU_SERVE_RESTART_BACKOFF_MS`,
doubling, capped at 60s).

Worker side (``python -m mxnet_tpu.serving.supervisor``): loads an
artifact (or a test stub), warms every padding bucket, CONNECTS to the
pool's localhost listener, and serves length-prefixed pickled messages:

    router -> replica   {kind: predict, id, arrays, bucket, n, remaining}
                        {kind: generate, id, tokens, max_new_tokens,
                         temperature, top_k, top_p, remaining, trace}
                        {kind: ping, id} | {kind: stats, id}
                        {kind: shutdown}
    replica -> router   {kind: hello, replica, generation, pid}
                        {kind: ready, warm_seconds, bucket_flops,
                         bucket_memory, compile_digests, generate, ...}
                        {kind: result, id, outputs, seconds}
                        {kind: gen_result, id, tokens, finish_reason}
                        {kind: gen_error, id, status, error}
                        {kind: expired, id} | {kind: error, id, error}
                        {kind: pong, id} | {kind: stats_result, id, stats}

Generation workers (``--generate PREFIX``, docs/serving.md §Generation)
run their own continuous-batching scheduler: ``generate`` frames enqueue
into it and the receive loop keeps answering pings while the scheduler
thread decodes, so liveness stays on the heartbeat clock under long
generations; ``gen_result`` replies are pushed OUT OF ORDER as sequences
finish (the completion hook owns a send lock).

``remaining`` is the batch deadline budget in seconds (per-request
deadlines are process-local monotonic times, so the ROUTER converts to a
remaining budget before the wire): a replica that wakes up past it —
e.g. after a ``slow_reply`` injection — answers ``expired`` and never
runs the forward, so a slow replica cancels work instead of computing
answers nobody is waiting for.

SIGTERM asks the worker to finish its current batch and exit 0; the
handler (`_on_term`) only flips a flag — it is walked by the mxlint
signal-safety checker, so it must stay free of locks/logging/allocation
beyond a list-slot store.
"""
from __future__ import annotations

import logging
import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

from .. import chip_binding as _chips
from .. import env as _env
from ..base import MXNetError

_LOG = logging.getLogger("mxnet_tpu.serving.supervisor")

_HDR = struct.Struct("!I")
_MAX_MSG = 1 << 30  # 1 GiB framing sanity bound
TOKEN_LEN = 32      # hex chars of the per-pool handshake secret


# ---------------------------------------------------------------------------
# wire protocol (shared by router and worker)
# ---------------------------------------------------------------------------

def send_msg(sock, obj):
    """One length-prefixed pickle frame. Pickle over a TCP socket is only
    safe because the router refuses to unpickle ANYTHING from a connection
    that has not first presented the pool's per-process handshake secret
    (`MXTPU_SERVE_POOL_TOKEN`, random per pool, handed to workers via
    their environment — the moral equivalent of multiprocessing's
    authkey); without it, any local user who found the 127.0.0.1 port
    could run code in the serving process via a crafted frame."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_HDR.pack(len(payload)) + payload)


def recv_msg(sock, first_timeout=None, rest_timeout=30.0):
    """Receive one frame. ``first_timeout`` bounds the wait for the FIRST
    byte (None = block); once a message has started, ``rest_timeout``
    bounds each subsequent chunk so a half-written frame from a dying peer
    cannot park us forever. Returns None on clean EOF before a frame
    starts. socket.timeout is raised ONLY before a frame starts (the
    stream is intact and a retry is safe); once bytes of a frame were
    consumed, a stall raises plain OSError — the framing can no longer be
    trusted, so callers that retry socket.timeout (the router's poll loop)
    must never resume reading mid-frame garbage."""
    sock.settimeout(first_timeout)
    try:
        first = sock.recv(_HDR.size)
    except socket.timeout:
        raise
    if not first:
        return None
    sock.settimeout(rest_timeout)
    buf = bytearray(first)
    try:
        while len(buf) < _HDR.size:
            chunk = sock.recv(_HDR.size - len(buf))
            if not chunk:
                raise OSError("peer closed mid-header")
            buf.extend(chunk)
        (length,) = _HDR.unpack(bytes(buf))
        if length > _MAX_MSG:
            raise OSError("oversized frame (%d bytes)" % length)
        data = bytearray()
        while len(data) < length:
            chunk = sock.recv(min(1 << 20, length - len(data)))
            if not chunk:
                raise OSError("peer closed mid-message")
            data.extend(chunk)
    except socket.timeout:
        raise OSError("peer stalled mid-frame (rest_timeout %.1fs)"
                      % rest_timeout) from None
    return pickle.loads(bytes(data))


# ---------------------------------------------------------------------------
# router side: one supervised replica process
# ---------------------------------------------------------------------------

def _signal_pg(proc, sig):
    """Signal the worker's whole process group (it was spawned a session
    leader), falling back to the single pid."""
    if proc.poll() is not None:
        return
    try:
        os.killpg(proc.pid, sig)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            proc.send_signal(sig)
        except OSError:
            pass


def teardown(proc, grace=None):
    """Escalating SIGTERM → SIGKILL process-group teardown (the
    tools/launch.py `_teardown` contract for a single worker): give the
    group `grace` seconds (`MXTPU_TEARDOWN_GRACE`) to exit cleanly, then
    SIGKILL the survivors — a replica wedged in a forward ignores nothing
    after SIGKILL, so ejection can never hang the router."""
    if proc.poll() is not None:
        return
    if grace is None:
        grace = _env.get("MXTPU_TEARDOWN_GRACE")
    _signal_pg(proc, signal.SIGTERM)
    deadline = time.monotonic() + max(0.0, grace)
    while time.monotonic() < deadline and proc.poll() is None:
        time.sleep(0.02)
    if proc.poll() is None:
        _signal_pg(proc, signal.SIGKILL)
    try:
        proc.wait(timeout=10)
    except (subprocess.TimeoutExpired, OSError):
        pass


def _pump(stream, label):
    """Prefix a replica's merged stdout/stderr per line (the launch.py
    rank-prefix pattern) so a multi-replica post-mortem stays readable."""
    prefix = ("[%s] " % label).encode()
    out = getattr(sys.stderr, "buffer", None)
    for line in iter(stream.readline, b""):
        if out is not None:
            out.write(prefix + line)
            out.flush()
        else:
            sys.stderr.write((prefix + line).decode("utf-8", "replace"))
            sys.stderr.flush()
    stream.close()


class _ChipSlots:
    """Which of this host's TPU chips the process has handed to replica
    workers (`chip_binding`: a chip belongs to one process). Process-wide:
    every pool of the router draws from the same chips."""

    def __init__(self):
        self._lock = threading.Lock()
        self._owners = {}   # chip -> label of the replica bound to it

    def acquire(self, label):
        if _chips.parent_holds_tpu():
            raise MXNetError(
                "replica %s needs a TPU chip of its own, but this process "
                "already holds the host's TPU (it ran a jax computation "
                "there). A chip belongs to one process: keep the router "
                "off the chips (JAX_PLATFORMS=cpu) when it serves through "
                "replica workers" % label)
        n = _chips.host_chip_count()
        with self._lock:
            for chip in range(n):
                if chip not in self._owners:
                    self._owners[chip] = label
                    return chip
            raise MXNetError(
                "replica %s needs a TPU chip of its own, but all %d of "
                "this host are bound (%s). A chip belongs to one process: "
                "ask for fewer replicas, or pin the workers to the CPU "
                "(extra_env={'JAX_PLATFORMS': 'cpu'})"
                % (label, n, ", ".join(
                    "%d: %s" % kv for kv in sorted(self._owners.items()))))

    def release(self, chip):
        with self._lock:
            self._owners.pop(chip, None)


_CHIP_SLOTS = _ChipSlots()


class ReplicaProcess:
    """Spawn/teardown state for one replica slot.

    ``worker_args`` is the argv tail describing WHAT to serve (artifact or
    stub flags); this class owns generation counting, the env protocol and
    the process-group lifecycle. A fresh `spawn()` after `teardown()`
    starts the next generation.
    """

    def __init__(self, model, replica_id, connect_addr, worker_args,
                 extra_env=None, teardown_grace=None, token=None):
        self.model = str(model)
        self.replica_id = int(replica_id)
        self.connect_addr = connect_addr
        self.worker_args = list(worker_args)
        self.extra_env = dict(extra_env or {})
        self.teardown_grace = teardown_grace
        self.token = token
        self.generation = -1  # no spawn yet
        self.proc = None
        self._pump_thread = None
        # a worker that would claim a TPU is told which chip is its own,
        # for the life of the slot (close() gives it back); a slot the
        # host has no free chip for fails HERE, in the caller's thread
        self.chip = None
        if "TPU_VISIBLE_CHIPS" not in self.extra_env \
                and _chips.owns_chip({**os.environ, **self.extra_env}):
            self.chip = _CHIP_SLOTS.acquire(
                "%s/r%d" % (self.model, self.replica_id))

    @property
    def pid(self):
        return self.proc.pid if self.proc is not None else None

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def spawn(self):
        """Start the next generation of this replica (session leader, own
        process group, line-prefixed output). Returns the generation."""
        self.generation += 1
        env = dict(os.environ)
        env.update(self.extra_env)
        # the launcher env protocol: generation gates fault injection and
        # labels flight-recorder events in the worker
        env["MXTPU_RESTART_GENERATION"] = str(self.generation)
        if self.token:
            # handshake secret via the environment (same-UID readable
            # only — argv would leak it to every user via /proc)
            env["MXTPU_SERVE_POOL_TOKEN"] = self.token
        # a replica must never inherit the parent's serving port/telemetry
        # HTTP endpoint (port collisions across respawns)
        env.pop("MXTPU_TELEMETRY_PORT", None)
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        if self.chip is not None:
            env.update(_chips.replica_env(self.chip))
        argv = [sys.executable, "-m", "mxnet_tpu.serving.replica_worker",
                "--connect", "%s:%d" % self.connect_addr,
                "--replica", str(self.replica_id),
                "--generation", str(self.generation)] + self.worker_args
        self.proc = subprocess.Popen(
            argv, env=env, start_new_session=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        self._pump_thread = threading.Thread(
            target=_pump, args=(self.proc.stdout,
                                "%s/r%d.g%d" % (self.model, self.replica_id,
                                                self.generation)),
            daemon=True,
            name="mxtpu-replica-pump-r%d" % self.replica_id)
        self._pump_thread.start()
        return self.generation

    def teardown(self):
        if self.proc is not None:
            teardown(self.proc, self.teardown_grace)

    def close(self):
        """Final teardown of the slot: the worker dies and its chip goes
        back to the host's free chips."""
        self.teardown()
        if self.chip is not None:
            _CHIP_SLOTS.release(self.chip)
            self.chip = None

    def exit_code(self):
        return self.proc.poll() if self.proc is not None else None


def backoff_s(consecutive_restarts, initial_ms=None):
    """Exponential respawn backoff: initial * 2^(n-1), capped at 60s."""
    if initial_ms is None:
        initial_ms = _env.get("MXTPU_SERVE_RESTART_BACKOFF_MS")
    if consecutive_restarts <= 0:
        return 0.0
    return min(60.0, (initial_ms / 1e3) * (2 ** (consecutive_restarts - 1)))


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

# SIGTERM flag: a one-slot list the handler stores into. The handler is an
# mxlint signal-safety entry point — no locks, no logging, no Event.set().
_STOP = [False]


def _on_term(signum, frame):
    _STOP[0] = True


def _build_stub_runner(args):
    """Test stubs (numpy-only, no artifact): `echo` answers x*2; a
    positive --stub-delay-ms sleeps per batch (holds batches in flight so
    tests can land faults deterministically)."""
    import numpy as np

    delay = max(0.0, args.stub_delay_ms) / 1e3

    def runner(arrays, bucket, n):
        if delay:
            time.sleep(delay)
        name = sorted(arrays)[0]
        return [np.asarray(arrays[name]) * 2.0]

    return runner


def _parse_inputs(specs):
    shapes, dtypes = {}, {}
    for spec in specs or ():
        name, _, dims = spec.partition("=")
        if ":" in dims:
            dims, dtype = dims.split(":", 1)
            dtypes[name] = dtype
        shapes[name] = tuple(int(d) for d in dims.split("x") if d)
    return shapes, (dtypes or None)


def _connect_and_hello(args):
    """Dial the pool's listener, present the handshake secret and the
    hello frame; returns the connected socket (shared by the predict and
    generate worker paths)."""
    host, _, port = args.connect.rpartition(":")
    sock = socket.create_connection((host, int(port)), timeout=30)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    # authenticate BEFORE the first pickled frame: the router unpickles
    # nothing from a connection that has not presented the pool secret
    token = (_env.raw("MXTPU_SERVE_POOL_TOKEN") or "").encode("ascii")
    sock.sendall(token.ljust(TOKEN_LEN, b"\0")[:TOKEN_LEN])
    send_msg(sock, {"kind": "hello", "replica": args.replica,
                    "generation": args.generation, "pid": os.getpid()})
    return sock


def _generate_worker_main(args):
    """Generation replica (docs/serving.md §Generation): build the LM
    decode engine, warm every prefill/decode bucket, report ready with
    the KV geometry, then serve ``generate`` frames by feeding the local
    continuous-batching scheduler — replies are pushed as sequences
    finish, out of order, while this receive loop keeps answering
    pings/stats."""
    from .. import compile as _compile
    from .. import telemetry
    from ..telemetry import tracing
    from .batcher import ServingError
    from .generate import GenerateScheduler, TransformerLMEngine, load_lm

    compile_cursor = _compile.mark()
    engine = TransformerLMEngine(
        lm=load_lm(args.generate), num_pages=args.kv_pages,
        page_size=args.kv_page_size, max_prompt=args.max_prompt,
        max_new_tokens=args.max_new_tokens, max_batch=args.max_batch,
        window_pages=args.kv_window_pages)
    sched = GenerateScheduler(engine, name="replica%d" % args.replica,
                              warm=not args.no_warm)
    compile_entries = _compile.keys_since(compile_cursor)

    sock = _connect_and_hello(args)
    send_lock = threading.Lock()

    def _send(obj):
        with send_lock:     # scheduler completion hook + this loop share
            send_msg(sock, obj)

    misses = telemetry.get_registry().counter("mxtpu_jit_cache_miss_total")
    base_miss = misses.value

    def stats():
        # the acceptance evidence: zero-compile steady state + KV pages
        # reclaimed, observable from the router (pool.replica_stats)
        return {"kv_pages_total": sched.allocator.num_pages,
                "kv_pages_used": sched.allocator.used_pages,
                "jit_after_warm": misses.value - base_miss,
                "pending": sched.pending()}

    _send({"kind": "ready", "replica": args.replica,
           "generation": args.generation,
           "warm_seconds": sched.warm_seconds,
           "buckets": list(engine.buckets),
           "example_shapes": {}, "input_dtypes": None,
           "bucket_flops": None, "bucket_memory": None,
           "generate": engine.geometry(),
           "compile_digests":
               sorted({d for _, d in compile_entries}) or None,
           "compile_prefetched": 0})
    _LOG.info("generate replica %d gen %d ready (warm %.2fs, buckets %s)",
              args.replica, args.generation, sched.warm_seconds or 0.0,
              list(engine.buckets))

    def on_complete(req):
        if req.tag is None:
            return
        if req.error is not None:
            _send({"kind": "gen_error", "id": req.tag,
                   "status": getattr(req.error, "status", 500),
                   "error": str(req.error)})
        else:
            _send({"kind": "gen_result", "id": req.tag,
                   "tokens": list(req.outputs or []),
                   "finish_reason": req.finish_reason})

    served = 0
    try:
        while not _STOP[0]:
            try:
                msg = recv_msg(sock, first_timeout=0.25)
            except socket.timeout:
                continue
            except OSError:
                break
            if msg is None or msg.get("kind") == "shutdown":
                break
            kind = msg.get("kind")
            if kind == "ping":
                _send({"kind": "pong", "id": msg.get("id")})
                continue
            if kind == "stats":
                _send({"kind": "stats_result", "id": msg.get("id"),
                       "stats": stats()})
                continue
            if kind != "generate":
                _LOG.warning("generate replica %d: unknown message "
                             "kind %r", args.replica, kind)
                continue
            served += 1
            deadline = None if msg.get("remaining") is None \
                else time.monotonic() + float(msg["remaining"])
            ref = tracing.from_wire(msg["trace"]) \
                if msg.get("trace") else None
            try:
                req = sched.submit(
                    msg["tokens"],
                    max_new_tokens=msg.get("max_new_tokens"),
                    temperature=msg.get("temperature") or 0.0,
                    top_k=msg.get("top_k") or 0,
                    top_p=msg.get("top_p") if msg.get("top_p") is not None
                    else 1.0,
                    deadline=deadline, trace=ref, on_complete=on_complete)
                req.tag = msg["id"]
                if req.done():   # resolved before the tag landed
                    on_complete(req)
            except ServingError as e:
                _send({"kind": "gen_error", "id": msg["id"],
                       "status": e.status, "error": str(e)})
            except Exception as e:   # malformed request: 400, never die
                _send({"kind": "gen_error", "id": msg["id"],
                       "status": 400,
                       "error": "%s: %s" % (type(e).__name__, e)})
    finally:
        sched.close(drain=False, timeout=0)
        try:
            sock.close()
        except OSError:
            pass
    _LOG.info("generate replica %d gen %d exiting after %d requests",
              args.replica, args.generation, served)
    return 0


def worker_main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="serving replica worker (spawned by ReplicaPool)")
    p.add_argument("--connect", required=True, metavar="HOST:PORT")
    p.add_argument("--replica", type=int, required=True)
    p.add_argument("--generation", type=int, default=0)
    p.add_argument("--artifact", default=None,
                   help="export prefix or .mxc path (tools/serve.py spec)")
    p.add_argument("--input", action="append", default=[],
                   metavar="NAME=DIMS[:DTYPE]")
    p.add_argument("--max-batch", type=int, default=None)
    p.add_argument("--stub", choices=("echo",), default=None,
                   help="serve a numpy stub instead of an artifact (tests)")
    p.add_argument("--stub-delay-ms", type=float, default=0.0)
    p.add_argument("--no-warm", action="store_true")
    p.add_argument("--generate", default=None, metavar="PREFIX",
                   help="serve a generation LM artifact (save_lm prefix) "
                        "through the continuous-batching scheduler")
    p.add_argument("--kv-pages", type=int, default=None)
    p.add_argument("--kv-page-size", type=int, default=None)
    p.add_argument("--kv-window-pages", type=int, default=None)
    p.add_argument("--max-prompt", type=int, default=None)
    p.add_argument("--max-new-tokens", type=int, default=None)
    args = p.parse_args(argv)

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(message)s", stream=sys.stderr)
    signal.signal(signal.SIGTERM, _on_term)

    if args.generate:
        return _generate_worker_main(args)

    from .. import compile as _compile
    from ..parallel.resilience import maybe_inject_serving_fault
    from ..telemetry import tracing
    from .batcher import power_of_two_buckets

    max_batch = args.max_batch
    if max_batch is None:
        max_batch = _env.get("MXTPU_SERVE_MAX_BATCH")
    manifest_id = None
    prefetched = 0
    compile_cursor = _compile.mark()
    if args.stub:
        runner = _build_stub_runner(args)
        example_shapes, input_dtypes = _parse_inputs(args.input)
        buckets = power_of_two_buckets(max_batch)
    elif args.artifact:
        from .model_repository import build_runner

        example_shapes, input_dtypes = _parse_inputs(args.input)
        # warmup-manifest prefetch BEFORE the artifact binds: with the
        # persistent tier armed and a manifest from a previous publish of
        # this artifact+geometry, every executable the warm needs
        # deserializes up front — ready with zero jit_compile events
        # (docs/compile_cache.md cold-start playbook). The id keys on the
        # RESOLVED max_batch (the same resolution the bucket set uses and
        # the repository applies), so an MXTPU_SERVE_MAX_BATCH change
        # cleanly partitions manifests instead of reusing a stale one.
        manifest_id = _compile.model_manifest_id(
            args.artifact, max_batch, example_shapes or None)
        prefetched = _compile.prefetch(manifest_id)
        if prefetched:
            _LOG.info("replica %d: prefetched %d cached executable(s) "
                      "from warmup manifest %s", args.replica, prefetched,
                      manifest_id)
        runner, buckets, example_shapes, input_dtypes, _meta = build_runner(
            args.artifact, input_shapes=example_shapes or None,
            input_dtypes=input_dtypes, max_batch=max_batch)
    else:
        p.error("need --artifact or --stub")

    sock = _connect_and_hello(args)

    # warm every bucket BEFORE ready: a replica never joins the pool with a
    # cold executable cache (the same publish-after-warm rule as in-process
    # models, docs/serving.md)
    warm_s = 0.0
    bucket_flops = {}
    bucket_memory = {}
    if not args.no_warm:
        import numpy as np

        from ..telemetry import flops as _tm_flops
        from ..telemetry import memory as _tm_memory

        t0 = time.monotonic()
        for b in buckets:
            zeros = {k: np.zeros((b,) + tuple(s),
                                 dtype=(input_dtypes or {}).get(k, "float32"))
                     for k, s in example_shapes.items()}
            f0 = _tm_flops.total()
            m0 = _tm_memory.recorded_mark()
            _compile.begin_touch_log()
            try:
                runner(zeros, b, b)
            finally:
                touched = _compile.end_touch_log()
            f = _tm_flops.total() - f0
            if f:
                bucket_flops[int(b)] = f
            # memory figures the bucket's warm filled/deserialized/touched
            # — the router prices the pool's footprint from the ready frame
            mem = _tm_memory.bucket_figures(touched,
                                            _tm_memory.recorded_since(m0))
            if mem:
                bucket_memory[int(b)] = mem
        warm_s = time.monotonic() - t0
    # record this replica's executable key-set and (re)write the warmup
    # manifest so the NEXT cold start — a respawned generation or a fresh
    # deployment — prefetches these executables instead of compiling
    compile_entries = _compile.keys_since(compile_cursor)
    cache_dir = _compile.cache_dir()
    if cache_dir and manifest_id and compile_entries:
        _compile.write_manifest(cache_dir, manifest_id, compile_entries,
                                model="replica", version=args.generation)
    # staged prefetch entries the warm never claimed (stale manifest rows)
    # must not stay pinned for the worker's lifetime
    unclaimed = _compile.clear_staged()
    if unclaimed:
        _LOG.info("replica %d: dropped %d unclaimed prefetched "
                  "executable(s) (stale manifest rows)", args.replica,
                  unclaimed)
    send_msg(sock, {"kind": "ready", "replica": args.replica,
                    "generation": args.generation, "warm_seconds": warm_s,
                    "bucket_flops": bucket_flops or None,
                    "bucket_memory": bucket_memory or None,
                    "buckets": list(buckets),
                    "example_shapes": {k: tuple(v)
                                       for k, v in example_shapes.items()},
                    "input_dtypes": {k: str(v) for k, v in
                                     (input_dtypes or {}).items()} or None,
                    "compile_digests":
                        sorted({d for _, d in compile_entries}) or None,
                    "compile_prefetched": prefetched})
    _LOG.info("replica %d gen %d ready (warm %.2fs, buckets %s)",
              args.replica, args.generation, warm_s, list(buckets))

    seq = 0
    while not _STOP[0]:
        try:
            msg = recv_msg(sock, first_timeout=0.25)
        except socket.timeout:
            continue
        except OSError:
            break  # router went away: nothing to serve into
        if msg is None:
            break  # clean EOF
        kind = msg.get("kind")
        if kind == "shutdown":
            break
        if kind == "ping":
            send_msg(sock, {"kind": "pong", "id": msg.get("id")})
            continue
        if kind != "predict":
            _LOG.warning("replica %d: unknown message kind %r",
                         args.replica, kind)
            continue
        seq += 1
        t_batch = time.monotonic()
        deadline = None if msg.get("remaining") is None \
            else t_batch + float(msg["remaining"])
        # fault hook at the batch boundary (kill_replica / wedge_replica /
        # slow_reply — docs/fault_tolerance.md §5)
        maybe_inject_serving_fault(seq, args.replica)
        # deadline propagation: a replica that wakes up past the batch
        # budget (slow_reply, GC pause, CPU contention) cancels instead of
        # computing an answer nobody is waiting for
        if deadline is not None and time.monotonic() >= deadline:
            send_msg(sock, {"kind": "expired", "id": msg["id"]})
            continue
        t_run_wall = time.time()
        try:
            outs = runner(msg["arrays"], msg["bucket"], msg["n"])
        except Exception as e:  # model failure (incl. OSError from the
            try:                # runner itself): answer, never die
                send_msg(sock, {"kind": "error", "id": msg["id"],
                                "error": "%s: %s" % (type(e).__name__, e)})
            except OSError:
                break  # router went away mid-reply
            continue
        compute_s = time.monotonic() - t_batch
        # cross-process trace: one compute span per traced request in the
        # batch, parented under the router's dispatch span shipped on the
        # frame — this process's JSONL carries the worker lane of the
        # merged timeline (tools/trace_merge.py)
        for wire_ctx in msg.get("traces") or ():
            ref = tracing.from_wire(wire_ctx)
            if ref is not None:
                tracing.emit_span(
                    "serve.compute", t_run_wall, compute_s, ref,
                    component="worker",
                    attrs={"replica": args.replica,
                           "generation": args.generation,
                           "bucket": msg["bucket"], "n": msg["n"]})
        try:
            send_msg(sock, {"kind": "result", "id": msg["id"],
                            "outputs": outs, "seconds": compute_s})
        except OSError:
            break  # router went away: nothing to serve into
    try:
        sock.close()
    except OSError:
        pass
    _LOG.info("replica %d gen %d exiting after %d batches",
              args.replica, args.generation, seq)
    return 0


if __name__ == "__main__":
    sys.exit(worker_main())
