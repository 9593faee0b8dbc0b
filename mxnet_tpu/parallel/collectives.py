"""Collective communication layer — XLA collectives over the mesh.

Replaces the reference's four transports (SURVEY §5.8): ps-lite/ZMQ
parameter server (kvstore_dist.h:44), NCCL (kvstore_nccl.h:285-482),
CommDevice P2P reduce (comm.h:451-728) and CommCPU (comm.h:272-407).
Inside a compiled step these are `lax.psum`/`all_gather`/`ppermute` which
XLA lowers onto ICI rings (and DCN across pod slices); at the host level
`jax.distributed` replaces the ps-lite scheduler rendezvous.

Two call modes:
  * inside `shard_map`/`pmap` — the `axis_name` forms are used directly;
  * outside jit — `all_reduce_arrays` provides an eager, engine-style
    reduce across per-device NDArray copies (what kvstore('device') uses).
"""
from __future__ import annotations

import time as _time_mod

from .. import env as _env
from ..telemetry import core as _telemetry
from ..telemetry import recorder as _recorder

__all__ = [
    "psum", "pmean", "pmax", "pmin", "all_gather", "reduce_scatter",
    "ppermute", "axis_index", "axis_size", "all_to_all",
    "all_reduce_arrays", "broadcast_arrays", "init_process_group", "barrier",
    "rank", "num_workers",
]


# ---- in-graph collectives (use inside shard_map-ped / pmapped fns) --------

def psum(x, axis_name):
    import jax

    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    import jax

    return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name):
    import jax

    return jax.lax.pmax(x, axis_name)


def pmin(x, axis_name):
    import jax

    return jax.lax.pmin(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    import jax

    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    import jax

    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def ppermute(x, axis_name, perm):
    import jax

    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    import jax

    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis,
                              tiled=tiled)


def axis_index(axis_name):
    import jax

    return jax.lax.axis_index(axis_name)


def axis_size(axis_name):
    import jax

    return jax.lax.psum(1, axis_name)


# ---- eager cross-device reduce (kvstore('device') backend) ----------------

def _payload_bytes(arrays):
    """Total bytes across a list of jax/np arrays (best-effort)."""
    total = 0
    for a in arrays:
        try:
            total += int(a.size) * int(a.dtype.itemsize)
        except (AttributeError, TypeError):
            pass
    return total


def _observe_collective(op, arrays, seconds):
    """Telemetry for one eager collective: call count, payload bytes, and
    dispatch latency (async enqueue time — profile_sync-style device timing
    belongs to the profiler, not the always-on layer)."""
    if not _telemetry._STATE.enabled:
        return  # the kill switch must also skip the payload-byte scan
    from ..telemetry import tracing as _tracing

    nbytes = _payload_bytes(arrays)
    labels = {"op": op}
    _telemetry.counter("mxtpu_collective_calls_total", labels).inc()
    _telemetry.counter("mxtpu_collective_bytes_total", labels).inc(nbytes)
    _telemetry.histogram("mxtpu_collective_seconds", labels).observe(
        seconds, exemplar=_tracing.current_trace_id())
    # inside a traced step, the collective becomes a child span (emitted
    # retroactively from the measured window; no-op otherwise)
    _tracing.emit_span("train.collective", _time_mod.time() - seconds,
                       seconds, _tracing.current(), component="train",
                       attrs={"op": op, "bytes": nbytes})


def all_reduce_arrays(arrays):
    """Sum a list of same-shaped jax arrays living on different devices and
    return the sum materialized on each array's device — the eager
    equivalent of CommDevice::Reduce+Broadcast (comm.h:451-728). XLA runs
    the adds on-device; transfers ride ICI when available."""
    import jax

    if not arrays:
        return []
    t0 = _time_mod.perf_counter()
    if len(arrays) == 1:
        out = [jax.device_put(arrays[0], list(arrays[0].devices())[0])]
        _observe_collective("all_reduce", arrays,
                            _time_mod.perf_counter() - t0)
        return out
    # pairwise tree reduce: log2(n) rounds of concurrent adds instead of a
    # serial hub chain (the comm.h:451-728 CommDevice analogue)
    level = list(arrays)
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            a, b = level[i], level[i + 1]
            nxt.append(a + jax.device_put(b, list(a.devices())[0]))
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    total = level[0]
    out = [jax.device_put(total, list(a.devices())[0]) for a in arrays]
    _observe_collective("all_reduce", arrays, _time_mod.perf_counter() - t0)
    return out


def _barrier_sum(v):
    # module-level jitted reduction: jax.jit caches by function identity, so
    # a per-call lambda would retrace + recompile on every barrier()
    import jax

    global _BARRIER_JIT
    if _BARRIER_JIT is None:
        _BARRIER_JIT = jax.jit(lambda v: v.sum())
    return _BARRIER_JIT(v)


_BARRIER_JIT = None


def broadcast_arrays(src, devices):
    import jax

    t0 = _time_mod.perf_counter()
    out = [jax.device_put(src, d) for d in devices]
    _observe_collective("broadcast", [src] * len(out),
                        _time_mod.perf_counter() - t0)
    return out


# ---- multi-host bootstrap (ps-lite scheduler replacement) -----------------

def _enable_cpu_collectives(jax):
    """Multi-process groups on the CPU backend need an explicit cross-host
    collectives implementation — without one, every cross-process psum dies
    with XLA's 'Multiprocess computations aren't implemented on the CPU
    backend'. Select gloo when the platform is explicitly CPU (tests,
    localhost launches; MXTPU_CPU_COLLECTIVES overrides, 'none' disables).
    Must run before backend init, i.e. alongside the rendezvous."""
    import os

    impl = _env.get("MXTPU_CPU_COLLECTIVES")
    if impl == "none":
        return
    plats = (jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS")
             or "")
    if "cpu" not in [p.strip() for p in plats.split(",")]:
        return
    jax.config.update("jax_cpu_collectives_implementation", impl)


def init_process_group(coordinator_address=None, num_processes=None,
                       process_id=None, timeout=None, retries=None):
    """Multi-host rendezvous via jax.distributed — replaces the DMLC_PS_ROOT
    scheduler env protocol (SURVEY §3.4). No-op when single-process or when
    the envs are absent.

    Bounded (docs/fault_tolerance.md): the rendezvous waits at most
    `timeout` seconds (default ``MXTPU_RENDEZVOUS_TIMEOUT``, 300) for the
    group to assemble, redialing transient errors `retries` times (default
    ``MXTPU_RENDEZVOUS_RETRIES``, 0) with exponential backoff before
    raising a diagnosable MXNetError — a worker group whose peer died or
    never launched fails fast instead of parking every rank forever (the
    ps-lite scheduler's van timeout analogue, restored for the
    jax.distributed coordinator)."""
    import os
    import time as _time

    import jax

    from ..base import MXNetError

    def _env_int(*names):
        """Protocol-fallback read: first set name wins, MXTPU leg routed
        through the typed registry. A malformed value falls through to the
        next source (registry contract: never crash rendezvous on a typo)."""
        for n in names:
            v = _env.raw(n) if n.startswith("MXTPU_") else os.environ.get(n)
            if v is not None:
                try:
                    return int(v)
                except ValueError:
                    continue
        return None

    # Size/rank resolution order: our protocol, the reference's DMLC
    # protocol, then whatever process manager actually spawned us — OpenMPI
    # (tools/launch.py --launcher mpi), generic PMI, slurm (srun on a TPU
    # pod plays dmlc-tracker's role). The scheduler vars are chosen to only
    # exist on processes the manager really fanned out: OMPI_*/PMI_* appear
    # only under mpirun/mpiexec, and SLURM_STEP_NUM_TASKS is per-srun-step
    # (an sbatch batch script sees SLURM_NTASKS for the *allocation* but its
    # own step is a single task — sniffing SLURM_NTASKS would deadlock a
    # lone `python train.py` inside `sbatch --ntasks=4`).
    if num_processes is None:
        num_processes = _env_int("MXTPU_NUM_WORKERS", "MXNET_TPU_NUM_WORKERS",
                                 "DMLC_NUM_WORKER", "OMPI_COMM_WORLD_SIZE",
                                 "PMI_SIZE", "SLURM_STEP_NUM_TASKS") or 1
    if num_processes <= 1:
        return
    if coordinator_address is None:
        coordinator_address = _env.raw("MXTPU_COORDINATOR")
    if process_id is None:
        process_id = _env_int("MXTPU_PROCESS_ID", "DMLC_WORKER_ID",
                              "OMPI_COMM_WORLD_RANK", "PMI_RANK",
                              "SLURM_PROCID")
    if jax.distributed.is_initialized():
        return  # idempotent re-entry
    if timeout is None:
        # registry default 300; explicit 0 means "fail immediately"
        timeout = _env.get("MXTPU_RENDEZVOUS_TIMEOUT")
    if retries is None:
        # default 0: total time to a clear failure stays within ONE timeout
        # (+ margin) — the acceptance bar for a never-arriving peer. Set
        # MXTPU_RENDEZVOUS_RETRIES>0 for flaky fabrics where a second dial
        # (with backoff) is worth paying the extra timeout windows.
        retries = _env.get("MXTPU_RENDEZVOUS_RETRIES")
    # NOTE: must run before the first jax computation — the backend snapshots
    # the process group at creation (call this before importing anything
    # that touches jax arrays, or at worker start; tools/launch.py pattern)
    _enable_cpu_collectives(jax)

    def _diagnosis(cause):
        return (
            "distributed rendezvous failed (timeout %ds): rank %s of %s "
            "dialing coordinator %s — %s. A peer likely died before "
            "rendezvous or never launched; check the other ranks' logs "
            "(tools/launch.py prefixes them per rank), raise "
            "MXTPU_RENDEZVOUS_TIMEOUT for slow fleets, or use "
            "tools/launch.py --max-restarts for automatic group restart."
            % (timeout, "?" if process_id is None else process_id,
               num_processes, coordinator_address or "<auto-detect>", cause))

    backoff = 1.0
    _recorder.record_event(
        "rendezvous_start", coordinator=coordinator_address or "<auto>",
        num_processes=num_processes, process_id=process_id,
        generation=_telemetry.restart_generation(), timeout_s=timeout)
    t_dial = _time.perf_counter()
    for attempt in range(retries + 1):
        try:
            _dial_with_deadline(jax, coordinator_address, num_processes,
                                process_id, timeout)
            _recorder.record_event(
                "rendezvous_ok",
                seconds=round(_time.perf_counter() - t_dial, 3),
                attempts=attempt + 1)
            _telemetry.counter("mxtpu_rendezvous_total",
                               {"outcome": "ok"}).inc()
            return
        except _RendezvousTimeout:
            # the deadline expired with every side still waiting: the
            # missing peer won't materialize on a redial, so retries are
            # pointless — surface the bounded failure immediately
            _recorder.record_event(
                "rendezvous_failed", cause="deadline",
                seconds=round(_time.perf_counter() - t_dial, 3))
            _telemetry.counter("mxtpu_rendezvous_total",
                               {"outcome": "timeout"}).inc()
            raise MXNetError(_diagnosis(
                "group did not assemble within the deadline")) from None
        except Exception as e:  # bind failure / RuntimeError / grpc error
            # tear down any half-initialized client so a retry starts clean
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            if attempt >= retries:
                _recorder.record_event(
                    "rendezvous_failed", cause=type(e).__name__,
                    seconds=round(_time.perf_counter() - t_dial, 3),
                    attempts=attempt + 1)
                _telemetry.counter("mxtpu_rendezvous_total",
                                   {"outcome": "error"}).inc()
                raise MXNetError(_diagnosis(
                    "%s: %s (after %d attempt(s))"
                    % (type(e).__name__, e, retries + 1))) from e
            _time.sleep(backoff)
            backoff = min(backoff * 2, 30.0)


class _RendezvousTimeout(Exception):
    """Internal: the dial thread outlived the configured deadline."""


def _dial_with_deadline(jax, coordinator_address, num_processes, process_id,
                        timeout):
    """Run jax.distributed.initialize under OUR deadline instead of XLA's.

    XLA's own initialization_timeout is useless as a failure bound: on
    expiry the coordination-service client LOG(FATAL)s — the whole process
    aborts with a C++ stack instead of an exception anything can catch
    (observed: 'Terminating process because the JAX distributed service
    detected fatal errors ... DEADLINE_EXCEEDED ... RegisterTask'). So the
    dial runs on a daemon thread with XLA's deadline pushed far past ours,
    and the calling thread enforces `timeout` with a join: expiry raises a
    catchable _RendezvousTimeout → MXNetError, and the parked dial thread
    dies with the process (the worker exits on the error; even if the
    caller lingers, XLA's far deadline eventually reclaims the thread)."""
    import threading

    box = {}
    lock = threading.Lock()

    def dial():
        try:
            if coordinator_address is None:
                # no launcher-provided coordinator: hand jax the whole
                # rendezvous — its cluster auto-detection covers slurm (srun
                # nodelist), OpenMPI, and Cloud TPU pod metadata, and fails
                # with its own clear error when nothing can resolve. Do NOT
                # pass size/rank: auto-detection derives them from the same
                # source as the coordinator.
                jax.distributed.initialize(
                    initialization_timeout=timeout + 86400)
            else:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                    initialization_timeout=timeout + 86400)
            with lock:
                if box.get("abandoned"):
                    # the caller already reported failure and may have
                    # fallen back to single-process work: a group that
                    # assembles late must NOT silently come alive under it
                    try:
                        jax.distributed.shutdown()
                    except Exception:
                        pass
                else:
                    box["ok"] = True
        except BaseException as e:  # surfaced to the caller below
            box["err"] = e

    t = threading.Thread(target=dial, name="mxtpu-rendezvous-dial",
                         daemon=True)
    t.start()
    t.join(timeout)
    with lock:
        if "ok" in box:
            return
        box["abandoned"] = True
    if "err" in box:
        raise box["err"]
    raise _RendezvousTimeout()


def rank():
    import jax

    return jax.process_index()


def num_workers():
    import jax

    return jax.process_count()


def barrier():
    """Host-level barrier (reference: KVStore::Barrier kvstore.h:364).
    Implemented as a tiny all-device reduction that every participant must
    reach before any can proceed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from .mesh import default_mesh

    mesh = default_mesh()
    x = jnp.zeros((jax.device_count(),))
    y = jax.device_put(x, NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])))
    jax.block_until_ready(_barrier_sum(y))
