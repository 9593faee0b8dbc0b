"""Distributed job launcher (reference: tools/launch.py — the dmlc-tracker
front-end that spawned scheduler/server/worker processes over
ssh/mpi/yarn/sge).

TPU-native: there are no parameter servers; every process is a worker in a
synchronous `jax.distributed` group (the coordinator service replaces the
ps-lite scheduler rendezvous — SURVEY §5.8). Launch modes:

  --launcher local   N processes on this host (the reference's nightly dist
                     tests pattern, tests/nightly/test_all.sh:55)
  --launcher ssh     one process per hostfile slot over ssh (reference
                     dmlc-tracker/ssh.py); requires -H/--hostfile with
                     `host` or `host:slots` lines; rank 0's host serves the
                     coordinator, so its address must be reachable from all
                     hosts
  --launcher mpi     delegates process placement to mpirun/mpiexec
                     (reference dmlc-tracker/mpi.py); ranks resolve via
                     OMPI_COMM_WORLD_RANK/PMI_RANK inside
                     `init_process_group`, so the command needs no wrapper

yarn/sge submission is a documented divergence: on TPU fleets the cluster
scheduler (k8s/slurm) owns placement, and `init_process_group` reads
SLURM_PROCID/SLURM_STEP_NUM_TASKS directly — `srun python train.py` on a
pod is the whole launch story (parallel/collectives.py:init_process_group).

Every mode emits the standard env protocol so
`mxnet_tpu.kv.create('dist_sync')` works unmodified:

  MXTPU_COORDINATOR          host:port of process 0's coordinator service
  MXTPU_NUM_WORKERS          group size        (alias: DMLC_NUM_WORKER)
  MXTPU_PROCESS_ID           this process rank (alias: DMLC_WORKER_ID)
  MXTPU_RESTART_GENERATION   supervised respawn count (0 = first launch)

Elastic supervision (--max-restarts N, docs/fault_tolerance.md): the
launcher supervises the group; the FIRST rank failure triggers an
escalating SIGTERM→SIGKILL teardown of every worker's process group (no
rank is ever left parked in a rendezvous waiting for a dead peer), then —
restarts permitting — the whole group respawns after an exponential
backoff on a FRESH rendezvous port. Workers resume from the last complete
checkpoint via parallel.resilience. Local/ssh worker output is prefixed
per rank so multi-rank post-mortems stay readable. This restores, in
TPU-native form, the node-failure semantics ps-lite's scheduler provided
the reference (PAPER §1 layer map).

Preemption (MXTPU_PREEMPT_EXIT_CODE, default 83): a worker that exits
with the graceful-preemption rc checkpointed on its way out (SIGTERM +
grace window, parallel.resilience.maybe_preempt_exit), so the launcher
restarts the group WITHOUT consuming the --max-restarts budget and with
the backoff reset to its initial value — preemptions are scheduler
events, not crash loops. A `preempt` launcher event records each one.

Usage:
  python tools/launch.py -n 4 python train.py ...
  python tools/launch.py -n 4 --max-restarts 3 python train.py ...
  python tools/launch.py -n 8 --launcher ssh -H hosts.txt python train.py ...
  python tools/launch.py -n 16 --launcher mpi --hostfile hosts.txt -- \
      python train.py ...
"""
from __future__ import annotations

import argparse
import os
import random
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _remote_port():
    """Coordinator port for a REMOTE rank-0 host. Nothing can be verified
    from here, so pick from a band below Linux's default ephemeral range
    (32768+) to minimise collision odds; pass --port to pin one that is
    known-free on the rank-0 host."""
    return random.randint(10000, 29999)


def _protocol_env(n, coord, extra, rank=None, generation=0):
    """The env-var protocol workers see. rank=None yields only the
    rank-independent half (mpi mode: the process manager assigns ranks).
    `generation` counts supervised group restarts (0 = first launch) so
    workers — and the MXTPU_FAULT_INJECT harness — can tell a respawned
    life from the original (parallel/resilience.py:restart_generation)."""
    env = {
        "MXTPU_COORDINATOR": coord,
        "MXTPU_NUM_WORKERS": str(n),
        "MXTPU_RESTART_GENERATION": str(generation),
        # distributed-tracing context: worker step spans join the launch
        # trace under this generation's span (telemetry/tracing.py; the
        # flags bit carries whether the launcher env samples the run)
        "MXTPU_TRACE_CONTEXT": _generation_trace_context(generation),
        # reference-compatible aliases (DMLC_* protocol, launch.py:29)
        "DMLC_NUM_WORKER": str(n),
        "DMLC_ROLE": "worker",
    }
    if rank is not None:
        env["MXTPU_PROCESS_ID"] = str(rank)
        env["DMLC_WORKER_ID"] = str(rank)
    for kv in extra:
        k, _, v = kv.partition("=")
        env[k] = v
    return env


def _parse_hostfile(path):
    """`host` or `host:slots` per line (dmlc hostfile format); '#' comments.
    Returns one host entry per slot: ["a", "a", "b", ...]."""
    slots = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            host, _, n = line.partition(":")
            slots.extend([host.strip()] * (int(n) if n else 1))
    return slots


def _log(msg):
    sys.stderr.write("[launcher] %s\n" % msg)
    sys.stderr.flush()


def _emit_event(kind, **fields):
    """Launcher-side telemetry: append one JSON event line to
    $MXTPU_TELEMETRY_DIR/launcher-events.jsonl (the same directory workers
    flush their telemetry into — docs/observability.md). Deliberately
    stdlib-only and import-free: the launcher must never pay (or depend on)
    a framework/jax import just to supervise processes."""
    directory = os.environ.get("MXTPU_TELEMETRY_DIR")
    if not directory:
        return
    try:
        import json

        os.makedirs(directory, exist_ok=True)
        with open(os.path.join(directory, "launcher-events.jsonl"), "a") as f:
            f.write(json.dumps({
                "kind": "event", "ts": time.time(), "event": kind,
                "pid": os.getpid(), "fields": fields}) + "\n")
    except OSError:
        pass  # telemetry must never break supervision


# -- launch trace (distributed tracing, docs/observability.md §Tracing) ----
# one trace id per launcher invocation; each supervised generation is a
# span under it, exported to workers via MXTPU_TRACE_CONTEXT so their
# training-step spans share the trace. Import-free like _emit_event: the
# launcher hand-rolls the same `{"kind": "event", "event": "span"}` record
# shape tools/trace_merge.py normalizes.
_LAUNCH_TRACE = "%032x" % random.getrandbits(128)
_GEN_SPANS = {}  # generation -> (span_id, start_wall)


def _launch_sampled():
    """Whether the launcher environment samples the run (workers inherit
    the flag and force-record their step spans when it is set)."""
    try:
        return float(os.environ.get("MXTPU_TRACE_SAMPLE") or 0) >= 1.0
    except ValueError:
        return False


def _generation_trace_context(generation):
    span_id, _ = _GEN_SPANS.get(generation) or (None, None)
    if span_id is None:
        span_id = "%016x" % random.getrandbits(64)
        _GEN_SPANS[generation] = (span_id, time.time())
    return "%s-%s-%02d" % (_LAUNCH_TRACE, span_id,
                           1 if _launch_sampled() else 0)


def _emit_generation_span(generation, rc):
    """Close generation `generation`'s span (emitted at exit, when its
    duration is known) into launcher-events.jsonl."""
    span_id, start = _GEN_SPANS.get(generation) or (None, None)
    if span_id is None:
        return
    _emit_event("span", name="launch.generation", trace=_LAUNCH_TRACE,
                span=span_id, parent=None, component="launcher",
                ts=start, dur_us=(time.time() - start) * 1e6,
                attrs={"generation": generation, "rc": rc})


_PUMP_LOCK = threading.Lock()


def _pump(stream, label):
    """Copy one worker's merged stdout/stderr to our stdout, prefixing every
    line with its rank — post-mortems of a multi-rank failure stay readable
    (the reference dmlc-tracker interleaved raw streams)."""
    out = sys.stdout.buffer if hasattr(sys.stdout, "buffer") else None
    prefix = ("[%s] " % label).encode()
    for line in iter(stream.readline, b""):
        with _PUMP_LOCK:
            if out is not None:
                out.write(prefix + line)
                out.flush()
            else:  # stdout replaced by a text-only object (capture shims)
                sys.stdout.write((prefix + line).decode("utf-8", "replace"))
                sys.stdout.flush()
    stream.close()


def _signal_group(procs, sig):
    """Deliver `sig` to each worker's whole process GROUP (workers are
    spawned session leaders), so grandchildren — dataloader workers, shells
    the command spawned — die with it instead of leaking."""
    for p in procs:
        if p.poll() is None:
            try:
                os.killpg(p.pid, sig)
            except (ProcessLookupError, PermissionError, OSError):
                try:
                    p.send_signal(sig)
                except OSError:
                    pass


def _teardown(procs, grace=None, generation=None):
    """Escalating group teardown: when MXTPU_TELEMETRY_DIR is configured,
    SIGUSR1 first (flight-recorder dump — every survivor writes thread
    stacks + recent telemetry events before dying, so a hung worker's
    teardown always leaves a diagnosis behind, telemetry/recorder.py);
    then SIGTERM, give the group `grace` seconds (MXTPU_TEARDOWN_GRACE,
    default 10) to exit cleanly — flushing logs, closing checkpoints in
    progress — then SIGKILL the survivors. A rank wedged in a collective
    waiting for the dead peer ignores nothing after SIGKILL, so the
    restart loop is never blocked by a hung group."""
    if all(p.poll() is not None for p in procs):
        return
    if grace is None:
        grace = float(os.environ.get("MXTPU_TEARDOWN_GRACE", "10"))
    survivors = [p for p in procs if p.poll() is None]
    # SIGUSR1 only when telemetry output is configured: mxnet_tpu installs
    # the dump handler at import under MXTPU_TELEMETRY_DIR, so every
    # framework worker dumps-and-survives. Without the dir (or for
    # non-framework commands) SIGUSR1's DEFAULT action would terminate the
    # worker instantly, robbing it of its SIGTERM cleanup grace — so the
    # launcher skips the broadcast rather than break teardown semantics.
    dump_first = hasattr(signal, "SIGUSR1") and \
        bool(os.environ.get("MXTPU_TELEMETRY_DIR"))
    _log("tearing down %d live worker(s): %sSIGTERM, SIGKILL after %.0fs"
         % (len(survivors),
            "SIGUSR1 (flight-recorder dump), then " if dump_first else "",
            grace))
    _emit_event("launcher_teardown", live=len(survivors), grace_s=grace,
                dump_first=dump_first, generation=generation)
    if dump_first:
        _signal_group(procs, signal.SIGUSR1)
        # let handlers write their dump files before SIGTERM lands
        dump_grace = float(os.environ.get("MXTPU_DUMP_GRACE", "1.0"))
        deadline = time.time() + dump_grace
        while time.time() < deadline and any(p.poll() is None for p in procs):
            time.sleep(0.05)
    _signal_group(procs, signal.SIGTERM)
    deadline = time.time() + grace
    while time.time() < deadline and any(p.poll() is None for p in procs):
        time.sleep(0.05)
    survivors = [p for p in procs if p.poll() is None]
    if survivors:
        _log("%d worker(s) survived SIGTERM for %.0fs; sending SIGKILL"
             % (len(survivors), grace))
        _signal_group(survivors, signal.SIGKILL)
    for p in procs:
        try:
            p.wait(timeout=10)
        except (subprocess.TimeoutExpired, OSError):
            pass


def _preempt_exit_code():
    """The graceful-preemption rc contract (parallel/resilience.py
    maybe_preempt_exit), read import-free from the env like the rest of
    the launcher."""
    try:
        return int(os.environ.get("MXTPU_PREEMPT_EXIT_CODE", "83"))
    except ValueError:
        return 83


def _run_generation(cmds, preempt_rc=None, generation=None):
    """Spawn every (argv, env, label) and supervise by polling: the FIRST
    failure — a spawn error partway through the list, or any worker exiting
    nonzero — tears the survivors down (escalating SIGTERM→SIGKILL on the
    process groups), so one crashed rank never leaves the rest parked in
    the rendezvous waiting for it. Workers that exit 0 simply leave the
    others to finish. (ssh mode: the teardown hits the local ssh client;
    sshd tears the remote command down with the connection.) Labeled
    workers get their output line-prefixed via a pump thread.

    Returns (rc, preempted). `preempted` is True when ANY worker's final
    rc equals `preempt_rc` — checked after teardown, because the
    first-OBSERVED exit may be a peer's collective error while the
    actually-preempted rank (which DID land an emergency checkpoint
    before exiting) finished an instant earlier. Also counts a worker
    that preempt-exits gracefully under the teardown SIGTERM itself:
    either way a fresh checkpoint exists, so the restart makes progress."""
    procs, pumps = [], []
    rc = 0
    try:
        for argv, env, label in cmds:
            p = subprocess.Popen(
                argv, env=env, start_new_session=True,
                stdout=subprocess.PIPE if label else None,
                stderr=subprocess.STDOUT if label else None)
            procs.append(p)
            if label:
                t = threading.Thread(target=_pump, args=(p.stdout, label),
                                     daemon=True)
                t.start()
                pumps.append(t)
        pending = list(procs)
        while pending and not rc:
            for p in list(pending):
                r = p.poll()
                if r is not None:
                    pending.remove(p)
                    rc = rc or r
            if pending and not rc:
                time.sleep(0.1)
    finally:
        _teardown(procs, generation=generation)  # nonzero rc -> stragglers
        for t in pumps:
            t.join(timeout=5)
    preempted = preempt_rc is not None and any(
        p.returncode == preempt_rc for p in procs)
    return rc, preempted


def _spawn_and_wait(make_cmds, max_restarts=0, backoff=1.0):
    """Supervising restart loop (the elastic-training front half; the back
    half is checkpoint auto-resume, parallel/resilience.py). `make_cmds`
    maps a generation number to the (argv, env, label) list for that
    generation — called FRESH each time so every generation gets a new
    rendezvous port (the dead coordinator's port may sit in TIME_WAIT) and
    workers see MXTPU_RESTART_GENERATION. On group failure: escalating
    teardown, exponential-backoff wait, respawn — up to `max_restarts`
    times, after which the last exit code propagates.

    Two exits are NOT ordinary failures: a generation where some worker
    exited with the graceful-preemption rc (MXTPU_PREEMPT_EXIT_CODE,
    default 83) restarts for FREE — the preempted rank checkpointed on
    its way out, so the retry makes forward progress and should not
    burn the crash budget — and the backoff ramp resets to its initial
    value, since exponential backoff exists to damp crash loops, not to
    punish schedulers for reclaiming capacity."""
    generation = 0
    restarts_used = 0
    initial_delay = max(backoff, 0.0)
    delay = initial_delay
    prev_exit = None  # (ts, rc, preempted) of the previous generation
    while True:
        if generation:
            _log("spawning generation %d" % generation)
        if prev_exit is not None:
            # goodput job ledger (docs/observability.md §Goodput): the gap
            # between the previous generation's teardown and this spawn is
            # categorized downtime — labeled preempt vs crash from the
            # rc-83 contract. tools/goodput_report.py joins it (plus each
            # rank's goodput_first_step event for the restore→first-step
            # tail) against per-rank phase totals.
            _emit_event("launcher_downtime", generation=generation,
                        cause="preempt" if prev_exit[2] else "crash",
                        rc=prev_exit[1],
                        down_s=round(time.time() - prev_exit[0], 3))
        _emit_event("launcher_generation_start", generation=generation,
                    max_restarts=max_restarts)
        rc, preempted = _run_generation(make_cmds(generation),
                                        _preempt_exit_code(),
                                        generation=generation)
        prev_exit = (time.time(), rc, preempted)
        _emit_event("launcher_generation_exit", generation=generation, rc=rc,
                    preempted=preempted)
        _emit_generation_span(generation, rc)
        if rc == 0:
            return 0
        if preempted and max_restarts > 0:
            # free restart: the preempted rank landed an emergency
            # checkpoint before exiting, so the next generation resumes
            # with fresh progress — budget untouched, backoff reset
            generation += 1
            delay = initial_delay
            _log("group preempted (rc=%d); free restart as generation %d in "
                 "%.1fs (restart budget untouched: %d/%d used)"
                 % (rc, generation, delay, restarts_used, max_restarts))
            _emit_event("preempt", generation=generation, rc=rc,
                        restarts_used=restarts_used, backoff_s=delay)
            if delay:
                time.sleep(delay)
            continue
        if restarts_used >= max_restarts:
            if max_restarts:
                _log("group failed (rc=%d); %d restart(s) exhausted, giving "
                     "up" % (rc, max_restarts))
            _emit_event("launcher_restarts_exhausted", generation=generation,
                        rc=rc)
            return rc
        generation += 1
        restarts_used += 1
        _log("group failed (rc=%d); restarting (%d/%d) in %.1fs on a fresh "
             "rendezvous port" % (rc, restarts_used, max_restarts, delay))
        _emit_event("launcher_restart", generation=generation, rc=rc,
                    backoff_s=delay)
        if delay:
            time.sleep(delay)
        delay = min(max(delay, 0.5) * 2, 60.0)


def _chip_binding():
    """mxnet_tpu/chip_binding.py, loaded by file path: importing it through
    the package would pull in the framework and jax, which the launcher
    must never pay for (or depend on) just to supervise processes."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu", "chip_binding.py")
    spec = importlib.util.spec_from_file_location("_chip_binding", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _launch_local(args):
    n = args.num_workers
    chips = _chip_binding()
    # A TPU chip belongs to one process. One local rank keeps the whole
    # host (it drives every chip through a mesh); several ranks that would
    # each claim the TPU get one chip each, as ONE TPU system, and more of
    # them than the host has chips is refused here instead of dying in
    # libtpu's lockfile one by one.
    rank_env = dict(os.environ)
    rank_env.update(kv.partition("=")[::2] for kv in args.env)
    bind = n > 1 and chips.owns_chip(rank_env)
    if bind:
        try:
            chips.group_env(0, n, [0] * n)
        except ValueError as e:   # more ranks than chips, or no such box
            _log("%s. Use a rank count that fits (one rank drives every "
                 "chip through a mesh), or pin the ranks to the CPU with "
                 "--env JAX_PLATFORMS=cpu" % e)
            return 2

    def make_cmds(generation):
        # fresh port per generation: --port pins one (the old coordinator is
        # dead by restart time, so rebinding it is safe), else probe anew
        port = args.port or _free_port()
        coord = "127.0.0.1:%d" % port
        tpu_ports = [_free_port() for _ in range(n)] if bind else None
        cmds = []
        for rank in range(n):
            env = dict(os.environ)
            if bind:
                env.update(chips.group_env(rank, n, tpu_ports))
            env.update(_protocol_env(n, coord, args.env, rank, generation))
            cmds.append((args.command, env, "rank %d" % rank))
        return cmds

    return _spawn_and_wait(make_cmds, args.max_restarts, args.restart_backoff)


def _launch_ssh(args):
    """One ssh session per rank (reference dmlc-tracker/ssh.py): env rides
    inline `env K=V` prefixes because sshd filters most SendEnv vars, and
    the remote cwd mirrors the local one (the dmlc assumption: a shared
    filesystem or identical checkouts)."""
    if not args.hostfile:
        raise SystemExit("--launcher ssh requires -H/--hostfile")
    slots = _parse_hostfile(args.hostfile)
    if len(slots) < args.num_workers:
        raise SystemExit("hostfile provides %d slots < -n %d"
                         % (len(slots), args.num_workers))
    cwd = os.getcwd()
    ssh = shlex.split(args.ssh_cmd)

    def make_cmds(generation):
        port = args.port or _remote_port()
        coord = "%s:%d" % (slots[0], port)
        cmds = []
        for rank in range(args.num_workers):
            host = slots[rank]
            env = _protocol_env(args.num_workers, coord, args.env, rank,
                                generation)
            # PYTHONPATH travels so `python tools/launch.py` from a checkout
            # works without install on the remote side
            if os.environ.get("PYTHONPATH"):
                env.setdefault("PYTHONPATH", os.environ["PYTHONPATH"])
            envs = " ".join("%s=%s" % (k, shlex.quote(v))
                            for k, v in sorted(env.items()))
            remote = "cd %s && env %s %s" % (
                shlex.quote(cwd), envs,
                " ".join(shlex.quote(c) for c in args.command))
            cmds.append((ssh + [host, remote], dict(os.environ),
                         "rank %d" % rank))
        return cmds

    return _spawn_and_wait(make_cmds, args.max_restarts, args.restart_backoff)


# per-flavor syntax for exporting one env var through the mpi launcher
_MPI_ENV_FLAG = {
    "openmpi": lambda k, v: ["-x", k],          # value from mpirun's env
    "mpich": lambda k, v: ["-genv", k, v],      # mpiexec/hydra, Intel MPI
    "none": lambda k, v: [],                    # cluster forwards env itself
}


def _launch_mpi(args):
    """Delegate placement to mpirun (reference dmlc-tracker/mpi.py). Rank
    and size are NOT passed per-process — `init_process_group` reads
    OMPI_COMM_WORLD_RANK/PMI_RANK in each worker, so one mpirun command
    covers every rank. The coordinator is bound by worker rank 0, so its
    default address follows the placement: the hostfile's first host when
    one is given (mpirun fills hosts in order), else this host (purely
    local mpirun). --coordinator-host/--port override both."""
    def make_cmds(generation):
        if args.coordinator_host:
            host = args.coordinator_host
            port = args.port or _remote_port()
        elif args.hostfile:
            host = _parse_hostfile(args.hostfile)[0]
            # rank 0 is remote: no local probe can verify its ports
            port = args.port or _remote_port()
        else:
            host = "127.0.0.1"
            port = args.port or _free_port()
        coord = "%s:%d" % (host, port)
        proto = _protocol_env(args.num_workers, coord, args.env,
                              generation=generation)
        env = dict(os.environ)
        env.update(proto)
        cmd = shlex.split(args.mpi_cmd) + ["-np", str(args.num_workers)]
        if args.hostfile:
            cmd += ["--hostfile", args.hostfile]
        flag = _MPI_ENV_FLAG[args.mpi_flavor]
        export = set(proto)
        if "PYTHONPATH" in env:
            export.add("PYTHONPATH")
        for var in sorted(export):
            cmd += flag(var, env[var])
        # label=None: mpirun already multiplexes rank output; piping it
        # through a prefix pump would only obscure mpirun's own framing
        return [(cmd + args.command, env, None)]

    return _spawn_and_wait(make_cmds, args.max_restarts, args.restart_backoff)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed job (local/ssh/mpi)")
    parser.add_argument("-n", "--num-workers", required=True, type=int)
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh", "mpi"],
                        help="process placement: local spawns on this host; "
                             "ssh uses -H/--hostfile; mpi delegates to "
                             "mpirun (yarn/sge: use your cluster scheduler "
                             "— see module doc)")
    parser.add_argument("-H", "--hostfile",
                        help="hosts, one `host` or `host:slots` per line "
                             "(ssh: required; mpi: forwarded to mpirun)")
    parser.add_argument("--port", type=int, default=0,
                        help="coordinator port (default: a free local port "
                             "for local/mpi; a random 10000-29999 port for "
                             "ssh, where rank 0 is remote and can't be "
                             "probed — pin this if it might collide)")
    parser.add_argument("--coordinator-host", default=None,
                        help="mpi: address workers dial for rank-0 "
                             "rendezvous (default: this host's fqdn)")
    parser.add_argument("--ssh-cmd", default="ssh -o StrictHostKeyChecking=no",
                        help="ssh client command (tests substitute a local "
                             "shim)")
    parser.add_argument("--mpi-cmd", default="mpirun",
                        help="mpi launcher command (tests substitute a "
                             "local shim)")
    parser.add_argument("--mpi-flavor", default="openmpi",
                        choices=sorted(_MPI_ENV_FLAG),
                        help="env-export syntax: openmpi uses `-x VAR`, "
                             "mpich/Intel uses `-genv VAR VAL`, none skips "
                             "env flags (scheduler forwards the env)")
    parser.add_argument("--env", action="append", default=[],
                        help="extra KEY=VAL for every worker")
    parser.add_argument("--compile-cache", nargs="?", const="1",
                        default=None, metavar="DIR",
                        help="arm the persistent executable-artifact tier "
                             "(MXTPU_COMPILE_CACHE, docs/compile_cache.md) "
                             "for every worker in every generation: a "
                             "restarted generation reloads its compiled "
                             "steps from DIR (default: the repo-local "
                             "cache) and reaches step 1 with zero "
                             "jit_compile events")
    parser.add_argument("--sharded-step", action="store_true",
                        help="export MXTPU_SHARDED_STEP=1 fleet-wide: "
                             "gluon.Trainer(block=)/module.fit() promote "
                             "to the fused whole-step executable "
                             "(docs/sharded_training.md); pair with "
                             "--compile-cache so restarts skip compiles")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="elastic supervision: after a group failure "
                             "(escalating SIGTERM→SIGKILL teardown) respawn "
                             "the whole group up to N times with exponential "
                             "backoff and a fresh rendezvous port; workers "
                             "see MXTPU_RESTART_GENERATION and auto-resume "
                             "from the last complete checkpoint "
                             "(parallel/resilience.py). Graceful preemptions "
                             "(exit rc MXTPU_PREEMPT_EXIT_CODE, default 83) "
                             "restart for free — they do not consume this "
                             "budget. Default 0 = fail fast, the pre-elastic "
                             "behavior")
    parser.add_argument("--restart-backoff", type=float, default=1.0,
                        help="initial seconds between generations (doubles "
                             "each restart, capped at 60; resets to the "
                             "initial value after a graceful preemption)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    # restart-path arming: fold the cache/promotion flags into the --env
    # list so every launcher AND every elastic restart generation
    # (_protocol_env) exports them — explicit --env KEY=VAL still wins
    # because later entries overwrite earlier ones
    armed = []
    if args.compile_cache is not None:
        armed.append("MXTPU_COMPILE_CACHE=%s" % args.compile_cache)
    if args.sharded_step:
        armed.append("MXTPU_SHARDED_STEP=1")
    if armed:
        args.env = armed + args.env

    return {"local": _launch_local,
            "ssh": _launch_ssh,
            "mpi": _launch_mpi}[args.launcher](args)


if __name__ == "__main__":
    sys.exit(main())
