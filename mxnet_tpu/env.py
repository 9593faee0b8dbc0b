"""Typed registry of every ``MXTPU_*`` environment variable.

The reference framework read its ~71 ``MXNET_*`` knobs through one choke
point (``dmlc::GetEnv`` — typed, defaulted, greppable). Three generations of
runtime machinery here (Pallas fusion, elastic fault tolerance, telemetry)
had instead accumulated ad-hoc ``os.environ`` reads scattered across the
library, and the docs table drifted from the code. This module is the single
authority: every MXTPU variable is declared once — name, type, default,
documentation — and library code reads it through the typed accessors below.

Static enforcement: ``ci/mxlint``'s ``env-registry`` checker fails the tree
when library code reads an ``MXTPU_*`` name through raw ``os.environ`` /
``os.getenv``, when a read name is missing from this registry, or when the
registry and the ``docs/env_vars.md`` table disagree (the table's Framework
section is GENERATED from this registry: ``python -m mxnet_tpu.env
--markdown``).

Accessors (registered names only — an unregistered name raises ``KeyError``
eagerly, the runtime arm of the lint guarantee):

  * ``raw(name)``    -> exactly ``os.environ.get(name)`` (``None`` if unset)
    — for call sites with bespoke parsing (tri-state gates, on/off synonym
    sets) that must keep their historical semantics bit-for-bit.
  * ``is_set(name)`` -> set to a non-empty string.
  * ``get(name, default=...)`` -> value parsed per the registered type, with
    the registered default (or the per-call override) when unset or
    malformed. Malformed-falls-back matches the library's defensive reads
    (a typo'd ``MXTPU_FLIGHTREC_EVENTS`` must not take training down).

Types: ``str`` (returned verbatim), ``int`` / ``float`` (parsed, fallback on
``ValueError``), ``bool`` (unset/empty/``0``/``false``/``off``/``no`` are
False, anything else True — the superset of the ``not in ("", "0")`` idiom
the scattered reads used).

Pure stdlib, imports nothing from the package — ``telemetry.core`` (which
must stay jax/numpy-free) imports it during early package init.
"""
from __future__ import annotations

import os

__all__ = ["EnvVar", "registry", "names", "raw", "is_set", "get",
           "markdown_table"]

_FALSY = ("", "0", "false", "off", "no")


class EnvVar:
    """One registered variable: name, type, default, documentation."""

    __slots__ = ("name", "vtype", "default", "doc")

    def __init__(self, name, vtype, default, doc):
        self.name = name
        self.vtype = vtype
        self.default = default
        self.doc = doc

    def parse(self, value):
        """Parse a raw env string per this var's type; ValueError on a
        value the type can't hold (``get`` turns that into the default)."""
        if self.vtype == "bool":
            return value.strip().lower() not in _FALSY
        if self.vtype == "int":
            return int(value)
        if self.vtype == "float":
            return float(value)
        return value

    def default_str(self):
        """Rendering of the default for the generated docs table."""
        if self.default is None:
            return "unset"
        if self.vtype == "bool":
            return "`1`" if self.default else "`0`"
        return "`%s`" % (self.default,)


_REGISTRY: dict = {}  # name -> EnvVar, insertion-ordered (= docs-table order)


def _var(name, vtype, default, doc):
    assert name.startswith("MXTPU_") and name not in _REGISTRY, name
    _REGISTRY[name] = EnvVar(name, vtype, default, doc)


def registry():
    """The full name -> EnvVar mapping (insertion-ordered copy)."""
    return dict(_REGISTRY)


def names():
    """Registered names, in declaration (= documentation) order."""
    return list(_REGISTRY)


def _check(name):
    var = _REGISTRY.get(name)
    if var is None:
        raise KeyError(
            "environment variable %r is not in the mxnet_tpu.env registry; "
            "declare it there (with type/default/doc) before reading it"
            % (name,))
    return var


def raw(name):
    """``os.environ.get(name)`` for a registered name (None when unset)."""
    _check(name)
    return os.environ.get(name)


def is_set(name):
    """Registered name is set to a non-empty string."""
    _check(name)
    return bool(os.environ.get(name))


_UNSET = object()


def get(name, default=_UNSET):
    """Typed read: parse per the registered type; the registered default
    (or the per-call ``default`` override) when unset or malformed."""
    var = _check(name)
    fallback = var.default if default is _UNSET else default
    value = os.environ.get(name)
    if value is None:
        return fallback
    try:
        return var.parse(value)
    except ValueError:
        return fallback


# ---------------------------------------------------------------------------
# the registry — declaration order is the docs/env_vars.md table order
# ---------------------------------------------------------------------------

# -- runtime / compile ------------------------------------------------------
_var("MXTPU_NO_NATIVE", "bool", False,
     "Disable the native C++ runtime (recordio/prefetch/buffer pool); "
     "pure-Python fallbacks are used.")
_var("MXTPU_COMPILE_CACHE", "str", None,
     "Persistent tier of the unified executable cache "
     "(`mxnet_tpu.compile`, docs/compile_cache.md): a directory path, or "
     "`1` for the repo-local `.mxtpu_compile_cache` default; "
     "`0`/`off`/`none` (or unset) disables. Compiled executables are "
     "serialized per (key x shapes x dtypes x jax version x backend) with "
     "crc-verified atomic-rename artifacts, so a restarted serving "
     "replica / elastic-restart generation / repeat bench run reaches "
     "steady state with zero recompiles. Not default-on: artifacts are "
     "machine-scoped (XLA:CPU AOT reloads across machine-feature "
     "mismatches risk SIGILL) and the directory must be trusted "
     "(artifacts unpickle on load). Manage with "
     "`python -m mxnet_tpu.compile`.")
_var("MXTPU_COMPILE_CACHE_ENTRIES", "int", 4096,
     "Capacity of the unified executable cache's in-memory LRU table "
     "(`mxnet_tpu.compile.registry`): oldest-touched executables are "
     "evicted past this many entries "
     "(`mxtpu_compile_cache_evict_total`).")
_var("MXTPU_SHARDED_STEP", "bool", False,
     "Promote user-facing training loops onto the fused whole-step "
     "executable (forward + loss + backward + optimizer update as ONE "
     "jit with donated param/state buffers, docs/sharded_training.md): "
     "`gluon.Trainer(..., block=)` internally becomes a "
     "`parallel.ShardedTrainer`, and `module.fit()` routes each step "
     "through `Module.fused_step` — no model-code changes. Fused keys "
     "carry a device-topology fingerprint, so with "
     "`MXTPU_COMPILE_CACHE` armed their executables persist and a "
     "restarted run reaches step 1 with zero `jit_compile` events. "
     "Exported fleet-wide by `tools/launch.py --sharded-step`.")
_var("MXTPU_SHARDED_PREFETCH", "bool", True,
     "On the first fused-step cache miss, batch-stage every artifact "
     "listed in the trainer's warmup manifest from the persistent tier "
     "before building (`compile.prefetch`): a restarted generation "
     "loads its whole executable set in one pass instead of "
     "one-disk-probe-per-shape. `0` falls back to per-key probing.")
_var("MXTPU_PY_RECORDIO", "bool", False,
     "Force the Python recordio reader/writer even when the native library "
     "is built (used by rec2idx for `tell()` positions).")

# -- fused kernels ----------------------------------------------------------
_var("MXTPU_PALLAS_LSTM", "str", "auto",
     "Fused Pallas LSTM layer (`ops/pallas_kernels.lstm_layer`): `auto` = "
     "on for TPU, `1` forces it everywhere (interpret mode on CPU — "
     "tests), `0` disables (lax.scan fallback).")
_var("MXTPU_PALLAS_DECODE", "str", "auto",
     "Paged decode-attention kernel (`ops/pallas_kernels.paged_attention` "
     "— flash-decode, q_len=1 against the block-allocated KV cache, the "
     "live pages copied by the kernel itself): `auto` = kernel on TPU, "
     "dense-gather jnp fallback elsewhere; `1` forces the kernel "
     "everywhere (interpret mode on CPU — parity tests); `0` forces the "
     "jnp path; shapes the "
     "kernel cannot take go to the jnp path under any value. Read at trace "
     "time of each decode executable — flip it between processes, not "
     "mid-process.")
_var("MXTPU_S2D_STEM", "bool", False,
     "`1` builds model-zoo ResNets with the space-to-depth stem (7×7/s2 "
     "over 3ch → 4×4/s1 over 12ch; weight-space transform `resnet."
     "stem_weight_to_s2d`, checkpoint converter `resnet."
     "convert_stem_params`).")

# -- profiler ---------------------------------------------------------------
_var("MXTPU_PROFILE_SYNC", "bool", False,
     "Profiler records true device time by blocking per op, instead of "
     "(async) dispatch time. Equivalent of the reference engine's "
     "profiling stamps.")

# -- data loading -----------------------------------------------------------
_var("MXTPU_DATALOADER_CTX", "str", "fork",
     "multiprocessing start method for DataLoader worker processes "
     "(`spawn` needs a `__main__` guard).")
_var("MXTPU_DATALOADER_TIMEOUT", "float", 300.0,
     "seconds to wait for a worker batch before raising (dead-worker "
     "detection).")
_var("MXTPU_DATALOADER_PROBE_TIMEOUT", "float", 20.0,
     "seconds the DataLoader's worker-viability probe (one sample round-"
     "tripped through a real worker process) may take before the loader "
     "falls back to in-process loading; the legit probe path touches no "
     "jax and returns in well under a second.")
_var("MXTPU_DATA_PREFETCH", "bool", False,
     "`1` wraps the `module.fit` batch iterator in the mxnet_tpu.data "
     "DevicePrefetcher: batch N+1's host decode + async host->device copy "
     "overlap batch N's compute (docs/data_pipeline.md).")
_var("MXTPU_DATA_PREFETCH_DEPTH", "int", 2,
     "batches the DevicePrefetcher stages ahead (double-buffering). Depth "
     "d absorbs producer jitter up to d x step-time; sizing math in "
     "docs/data_pipeline.md.")
_var("MXTPU_DATA_JOIN_TIMEOUT_S", "float", 30.0,
     "seconds the data pipeline's close()/reset() wait for producer and "
     "decode-worker threads to stop before raising (rewinding reader "
     "state under a live reader would corrupt the next epoch).")

# -- test suite -------------------------------------------------------------
_var("MXTPU_TEST_SEED", "int", None,
     "fixed seed for `test_utils.with_seed` tests (printed on failure for "
     "replay; tools/flakiness_checker.py sets both this and "
     "`MXNET_TEST_SEED`).")
_var("MXTPU_TEST_EXAMPLES_FULL", "bool", False,
     "`1` runs the examples CI at full configs instead of the <60s smoke "
     "configs.")
_var("MXTPU_TEST_LARGE_FULL", "bool", False,
     "`1` runs the allocation-heavy (>2 GiB) large-tensor tests (the "
     "reference keeps these in tests/nightly); default runs keep only the "
     "allocation-free checks.")
_var("MXTPU_TEST_CONVERGENCE_FULL", "bool", False,
     "`1` runs the long eager convergence fits (SSD, NLP models) the "
     "default suite skips.")
_var("MXTPU_TEST_TOTAL_STEPS", "int", None,
     "resilience/flight-recorder test workers: total training steps "
     "(worker-specific defaults).")
_var("MXTPU_TEST_STEP_SLEEP", "float", 0.05,
     "flight-recorder test worker: per-step sleep (hang-detection "
     "timing base).")
_var("MXTPU_TEST_CKPT_EVERY", "int", 2,
     "resilience test worker: checkpoint period in steps.")
_var("MXTPU_WALLTIME_FILE", "str", None,
     "if set, the pytest conftest appends a JSON record of suite wall time "
     "vs. the tier-1 budget to this file (always printed in the terminal "
     "summary).")

# -- distributed: rendezvous + launcher -------------------------------------
_var("MXTPU_COORDINATOR", "str", None,
     "multi-process rendezvous coordinator address, emitted by "
     "`tools/launch.py` and consumed by `parallel.collectives."
     "init_process_group`.")
_var("MXTPU_NUM_WORKERS", "int", None,
     "process-group size for the rendezvous protocol (alias: "
     "`DMLC_NUM_WORKER`).")
_var("MXTPU_PROCESS_ID", "int", None,
     "this process's rank in the rendezvous protocol (alias: "
     "`DMLC_WORKER_ID`).")
_var("MXTPU_RENDEZVOUS_TIMEOUT", "int", 300,
     "seconds `init_process_group` / `kv.create('dist_sync')` waits for "
     "the group to assemble before raising a diagnosable `MXNetError` "
     "(instead of hanging on a peer that never arrives — "
     "docs/fault_tolerance.md §2).")
_var("MXTPU_RENDEZVOUS_RETRIES", "int", 0,
     "redial count (exponential backoff) for *transient* rendezvous "
     "errors; deadline expiries are not retried.")
_var("MXTPU_RESTART_GENERATION", "int", 0,
     "set by the `tools/launch.py --max-restarts` supervisor: which "
     "respawn generation this worker belongs to (`parallel.resilience."
     "restart_generation()`; fault injection defaults to generation 0 "
     "only).")
_var("MXTPU_TEARDOWN_GRACE", "float", 10.0,
     "launcher escalation window: seconds between group SIGTERM and "
     "SIGKILL on first failure.")
_var("MXTPU_CPU_COLLECTIVES", "str", "gloo",
     "cross-process collectives implementation selected when the platform "
     "is explicitly CPU (multi-process CPU groups need one; `none` "
     "disables).")

# -- resilience -------------------------------------------------------------
_var("MXTPU_FAULT_INJECT", "str", None,
     "deterministic fault injection at the trainer step boundary, e.g. "
     "`kill@step=7,rank=1`, `exc@step=3`, `hang@step=5,rank=1` (park the "
     "rank forever — watchdog/flight-recorder test vector), "
     "`corrupt_ckpt@step=5,dir=/ckpts`, `preempt@step=7,rank=1,grace=30` "
     "(SIGTERM-with-grace — the cloud preemption notice), "
     "`kill_during_ckpt@step=4,rank=0` (die mid-save, pre-publish — the "
     "torn-write window) (docs/fault_tolerance.md §5).")
_var("MXTPU_CKPT_DIR", "str", None,
     "default checkpoint directory for the `corrupt_ckpt` injection "
     "action (tests' resilience workers also read it).")
_var("MXTPU_CKPT_ASYNC", "bool", True,
     "route `CheckpointManager.save_async`/`save_sharded_async` through "
     "the named background writer thread (`mxtpu-ckpt-writer`): the "
     "training thread pays only the host snapshot, serialize+fsync+"
     "atomic-rename happen off-thread (at-most-one in flight, honest "
     "backpressure). `0` degrades both to the synchronous save path — "
     "the escape hatch when the extra host copy is the scarcer resource "
     "(docs/fault_tolerance.md §Preemption & elastic resume).")
_var("MXTPU_CKPT_SHARD_TIMEOUT_S", "float", 120.0,
     "sharded checkpoints: how long rank 0 waits for every peer rank's "
     "staged shard before abandoning the manifest publish (the staging "
     "dir stays invisible to `latest()`, so a peer death mid-save can "
     "never tear a checkpoint).")
_var("MXTPU_PREEMPT_GRACE_S", "float", 15.0,
     "graceful-preemption budget: seconds between the SIGTERM notice and "
     "the expected SIGKILL. `maybe_preempt_exit` finishes the in-flight "
     "step and emergency-checkpoints inside this window; a fault entry's "
     "`grace=` or `install_preemption_handler(grace_s=)` overrides it.")
_var("MXTPU_PREEMPT_EXIT_CODE", "int", 83,
     "rc a gracefully-preempted worker exits with after its emergency "
     "checkpoint. `tools/launch.py` treats a generation where any rank "
     "exited with this rc as a preemption: free restart (no "
     "`--max-restarts` budget consumed) and backoff reset. rc+1 (84) "
     "means preempted WITHOUT a checkpoint — budget-consuming.")

# -- serving ----------------------------------------------------------------
_var("MXTPU_SERVE_MAX_BATCH", "int", 32,
     "serving (`mxnet_tpu.serving`): maximum examples coalesced into one "
     "inference batch; also the terminal padding bucket (buckets are the "
     "powers of two up to this value — docs/serving.md).")
_var("MXTPU_SERVE_MAX_DELAY_MS", "float", 5.0,
     "serving: longest the micro-batcher holds an admitted request open "
     "waiting for coalescing partners before dispatching a partial batch.")
_var("MXTPU_SERVE_QUEUE_DEPTH", "int", 256,
     "serving admission control: bounded per-model request queue; a "
     "submit beyond this depth is rejected immediately (HTTP 429).")
_var("MXTPU_SERVE_TIMEOUT_MS", "float", 2000.0,
     "serving: default per-request deadline (queue wait + compute); an "
     "expired request is dropped and answered HTTP 504. A request body "
     "may override it via its `timeout_ms` field.")
_var("MXTPU_SERVE_PORT", "int", 8500,
     "serving: default HTTP port for `tools/serve.py` / `ServingServer` "
     "(0 binds a free port, as the tests and `chipbench/` do).")
_var("MXTPU_SERVE_DRAIN_TIMEOUT_MS", "float", 30000.0,
     "serving: graceful-shutdown budget in ms — how long SIGTERM/`/drainz` "
     "waits for queued + in-flight requests to finish. A wedged executor "
     "must not wedge shutdown forever: on expiry the drain FORCE-completes "
     "every stranded request with a deterministic 503 and the process "
     "exits nonzero (docs/serving.md drain semantics; replaced the "
     "seconds-typed `MXTPU_SERVE_DRAIN_TIMEOUT_S`).")
_var("MXTPU_SERVE_DRAIN_TIMEOUT_S", "float", None,
     "DEPRECATED serving drain budget (seconds-typed predecessor of "
     "`MXTPU_SERVE_DRAIN_TIMEOUT_MS`). Still honored — with a startup "
     "warning — when set and the `_MS` name is not, so existing "
     "deployments' drain settings survive the rename.")
_var("MXTPU_SERVE_REPLICAS", "int", 0,
     "serving: replica worker processes per served model (`tools/serve.py "
     "--replicas`). 0 runs the model in-process (no pool); N >= 1 runs N "
     "supervised replica processes with health-checked failover "
     "(docs/serving.md resilience).")
_var("MXTPU_SERVE_HEARTBEAT_MS", "float", 1000.0,
     "serving replica pool: health-check heartbeat deadline. An idle "
     "replica that misses a ping/pong round trip by this much — or a busy "
     "one silent past its batch deadline plus this grace — is declared "
     "wedged, ejected (process-group teardown) and respawned.")
_var("MXTPU_SERVE_WEDGE_TIMEOUT_MS", "float", 10000.0,
     "serving replica pool: compute-budget FLOOR for busy-replica wedge "
     "detection. A busy replica is ejected only after staying silent past "
     "max(batch deadline budget, this floor) plus the heartbeat grace — "
     "decoupling wedge detection from client deadlines so a model whose "
     "forward legitimately outlasts a request budget is not SIGKILLed "
     "mid-compute (deadline-less batches use the floor alone).")
_var("MXTPU_SERVE_POOL_TOKEN", "str", None,
     "serving replica pool: INTERNAL per-pool handshake secret. Set by "
     "the pool in each replica worker's environment; a connecting worker "
     "must present it before any pickled frame is read, so another local "
     "user cannot reach the router's unpickler or hijack a replica slot. "
     "Not meant to be set by operators.")
_var("MXTPU_SERVE_RESTART_BACKOFF_MS", "float", 200.0,
     "serving replica pool: initial delay before respawning an ejected "
     "replica (doubles per consecutive restart of the same replica, "
     "capped at 60s; resets once a generation serves a batch cleanly).")
_var("MXTPU_SERVE_KV_PAGES", "int", 256,
     "generation serving (`mxnet_tpu.serving.generate`): total fixed-size "
     "KV-cache pages allocated per served LM. The whole pool is allocated "
     "at load (its bytes are part of the model footprint the "
     "`MXTPU_SERVE_MEMORY_BUDGET` admission check prices — a 507 at load "
     "time instead of an OOM mid-decode); the free-list allocator hands "
     "pages to sequences at admission and reclaims them at completion "
     "(`mxtpu_serve_kv_pages_{total,used}`).")
_var("MXTPU_SERVE_KV_PAGE_SIZE", "int", 16,
     "generation serving: tokens per KV-cache page. Smaller pages waste "
     "less on short tails but grow the per-sequence page table (and the "
     "decode executable's gather width); 16 matches the classic "
     "PagedAttention block size.")
_var("MXTPU_SERVE_MAX_NEW_TOKENS", "int", 128,
     "generation serving: cap on a request's `max_new_tokens` (also the "
     "per-request default when the body omits it). Together with "
     "`MXTPU_SERVE_MAX_PROMPT` it bounds the pages a sequence can ever "
     "need, so admission reserves worst-case pages up front and a "
     "running batch can never deadlock on the page pool.")
_var("MXTPU_SERVE_MAX_PROMPT", "int", 64,
     "generation serving: longest admissible prompt in tokens. Prompts "
     "pad to power-of-two prefill buckets up to this length — one cached "
     "prefill executable per bucket, so steady-state admission never "
     "compiles.")

# -- elastic autoscaling (docs/serving.md §Autoscaling) ---------------------
_var("MXTPU_AUTOSCALE", "bool", False,
     "arm the elastic autoscaler in `tools/serve.py`: one named "
     "controller thread per server (`serving.Autoscaler`) that consumes "
     "`slo.verdicts()` and resizes replica pools in place — scale up on "
     "sustained SLO breach (admitted against `MXTPU_SERVE_MEMORY_BUDGET` "
     "headroom, warm via manifest prefetch), scale down + drain on idle. "
     "Library callers construct `Autoscaler` directly; this gate is the "
     "launcher's.")
_var("MXTPU_AUTOSCALE_INTERVAL_MS", "float", 1000.0,
     "autoscaler evaluation-lap period. Each lap reads the current SLO "
     "verdicts and takes at most one scaling action per model.")
_var("MXTPU_AUTOSCALE_UP_WINDOWS", "int", 2,
     "consecutive breached evaluation laps (any paging SLO objective "
     "scoped to the model) before a scale-up — the fast-side hysteresis: "
     "one noisy window never adds a replica.")
_var("MXTPU_AUTOSCALE_IDLE_S", "float", 60.0,
     "sustained idle (seconds since the model's request counters last "
     "moved — the windowed staleness view) before the autoscaler drains "
     "one replica away, never below the model's `min_replicas`. Also the "
     "\"cold\" threshold budget-pressure shrinking uses.")
_var("MXTPU_AUTOSCALE_COOLDOWN_S", "float", 5.0,
     "minimum seconds between two scaling actions on one model (up or "
     "down), so a decision's effect — a warming replica, a drained one — "
     "lands in the windows before the next decision reads them.")
_var("MXTPU_AUTOSCALE_MIN_REPLICAS", "int", 1,
     "default per-model replica floor for scale-down and budget-pressure "
     "shrinking (`ModelRepository.load(min_replicas=)` overrides per "
     "model).")
_var("MXTPU_AUTOSCALE_MAX_REPLICAS", "int", 8,
     "default per-model replica ceiling for scale-up "
     "(`ModelRepository.load(max_replicas=)` overrides per model); a "
     "breach at the ceiling records an `autoscale_blocked` decision "
     "instead of growing.")
_var("MXTPU_AUTOSCALE_EVICT_TTL_S", "float", 300.0,
     "budget-pressure eviction TTL: a model idle longer than this (and "
     "not `pinned`) may be UNLOADED by `ModelRepository.reclaim_memory` "
     "when a new load or scale-up needs headroom — coldest first, after "
     "shrinking pooled models toward their floors. Its persisted warmup "
     "manifest makes a later reload warm in seconds.")

# -- telemetry / flight recorder --------------------------------------------
_var("MXTPU_TELEMETRY", "bool", True,
     "master switch for the always-on metrics/flight-recorder layer "
     "(docs/observability.md); `0` turns every counter/event into a "
     "no-op.")
_var("MXTPU_TELEMETRY_DIR", "str", None,
     "directory for telemetry output: periodic per-process "
     "`telemetry-rank<R>-pid<P>.jsonl` snapshots, `launcher-events.jsonl` "
     "(tools/launch.py supervision events) and `flightrec-*.json` hang "
     "dumps. Also arms the import-time SIGUSR1 dump handler. Read once at "
     "first use — set before the process starts recording.")
_var("MXTPU_TELEMETRY_FLUSH_S", "float", 10.0,
     "period of the JSONL flusher thread (a final flush always runs at "
     "exit).")
_var("MXTPU_TELEMETRY_PORT", "int", None,
     "base port for the Prometheus text-exposition endpoint; each rank "
     "serves `/metrics` on `port + rank` (stdlib http.server; default off "
     "— metrics-on/endpoint-off posture).")
_var("MXTPU_WATCHDOG_TIMEOUT", "float", None,
     "hang watchdog: seconds without a completed training step (armed by "
     "the FIRST completed step, so initial compile never trips it) before "
     "the flight recorder dumps all-thread stacks + recent events.")
_var("MXTPU_WATCHDOG_ACTION", "str", "abort",
     "what follows a watchdog dump: `abort` exits the process (code "
     "`MXTPU_WATCHDOG_EXIT_CODE`, 43) so the launcher tears down/restarts "
     "the group; `dump` keeps the process alive and re-arms.")
_var("MXTPU_WATCHDOG_EXIT_CODE", "int", 43,
     "exit status of a watchdog abort (distinct from the fault-injection "
     "code 42).")
_var("MXTPU_FLIGHTREC_EVENTS", "int", 512,
     "flight-recorder ring size (recent telemetry events kept per process "
     "for dumps).")
_var("MXTPU_DUMP_GRACE", "float", 1.0,
     "launcher teardown: seconds between the SIGUSR1 (flight-recorder "
     "dump) broadcast and SIGTERM. The broadcast only happens when "
     "`MXTPU_TELEMETRY_DIR` is set (the same condition that installs the "
     "worker-side dump handler at import); otherwise teardown starts "
     "directly at SIGTERM.")
_var("MXTPU_MEMORY_POLL_MS", "float", None,
     "period of the background memory-gauge poller "
     "(`telemetry.memory.sample`: device `memory_stats()`, process "
     "RSS/VmHWM, NDArray live bytes). Default off — gauges still refresh "
     "at every JSONL flush, Prometheus scrape and training step; the "
     "poller is for catching spikes inside long forwards between steps.")
_var("MXTPU_SERVE_MEMORY_BUDGET", "str", None,
     "serving memory budget in bytes (suffixes K/M/G/T accepted, e.g. "
     "`24G`): `ModelRepository.load` computes each model's device "
     "footprint from per-executable `memory_analysis()` figures "
     "(docs/observability.md §Memory) and REJECTS a load whose footprint "
     "would exceed the budget (typed `MemoryBudgetError`). A `warn:` "
     "prefix (e.g. `warn:24G`) logs + emits an event instead of "
     "rejecting. Unset (default) disables the check; loads whose "
     "footprint is unknown (no figures recorded) are never rejected.")
_var("MXTPU_STEP_FLOPS", "float", None,
     "model FLOPs per training step; when set, `observe_step` publishes "
     "achieved MFU (`mxtpu_step_mfu`) against `runtime.chip_peak_tflops` "
     "× local device count (API spelling: `telemetry.set_step_flops`). "
     "Overrides the automatic cost-analysis accounting "
     "(`MXTPU_TRACE_FLOPS`).")
_var("MXTPU_GOODPUT", "bool", True,
     "per-step goodput attribution (docs/observability.md §Goodput): "
     "every training step decomposes into exhaustive, non-overlapping "
     "phases (`data_wait`/`host_dispatch`/`compile`/`compute`/"
     "`checkpoint_stall`/`collective`/`other`) published as "
     "`mxtpu_step_phase_seconds{phase=}` plus the rolling "
     "`mxtpu_goodput_fraction` gauge. `0` turns the accountant into a "
     "no-op (the legacy `module.fit` data-wait split keeps working).")
_var("MXTPU_GOODPUT_WINDOW_STEPS", "int", 128,
     "steps in the rolling window behind `mxtpu_goodput_fraction` and the "
     "`/statusz` `training` block (windowed compute ÷ wall, top stall "
     "phase).")

# -- SLO engine -------------------------------------------------------------
_var("MXTPU_SLO", "bool", True,
     "master switch for the SLO engine (docs/observability.md §SLOs): "
     "objective registration, the burn-rate evaluator thread and the "
     "`mxtpu_slo_*` gauges. `0` disables everything except the raw "
     "windowed-view machinery (rings still roll on the flusher cadence).")
_var("MXTPU_SLO_SPEC", "str", None,
     "path of a JSON SLO spec file (`{\"objectives\": [...]}`); objectives "
     "declared there are registered next to the built-in serving/training "
     "ones at evaluator start. Malformed JSON, an unknown objective kind "
     "or an unknown metric name raise a typed `SLOSpecError` EAGERLY — a "
     "typo'd objective silently never evaluating would be an alert that "
     "can never fire.")
_var("MXTPU_SLO_WINDOW_MS", "float", 5000.0,
     "resolution of the windowed-telemetry snapshot rings: how often "
     "`roll_windows` appends one per-metric snapshot (rolled on the JSONL "
     "flusher cadence and each SLO evaluator lap, throttled to this "
     "period). Windowed `rate(60s)` / `quantile(0.99, 60s)` views diff "
     "the live value against the ring.")
_var("MXTPU_SLO_EVAL_MS", "float", None,
     "period of the SLO evaluator thread's laps (compute burn rates, "
     "publish `mxtpu_slo_*` gauges, emit breach/recovery events). Default: "
     "the `MXTPU_SLO_WINDOW_MS` resolution.")
_var("MXTPU_SLO_FAST_WINDOWS", "str", "60,300",
     "comma-separated fast (page-level) burn-rate windows in seconds, "
     "SRE-style: an objective pages only when EVERY fast window is "
     "burning (the short window proves it is happening now, the long one "
     "that it is not a blip).")
_var("MXTPU_SLO_SLOW_WINDOW_S", "float", 1800.0,
     "slow (ticket-level) burn-rate window in seconds; also sizes the "
     "snapshot rings (ring length = slow window / resolution, capped at "
     "4096 entries).")
_var("MXTPU_SLO_BURN_PAGE", "float", 1.0,
     "fast-window burn-rate threshold for the page-level (breaching) "
     "verdict: 1.0 pages as soon as the objective is violated at a "
     "budget-consuming rate across every fast window; raise it to page "
     "only on faster budget burn.")
_var("MXTPU_SLO_BURN_TICKET", "float", 1.0,
     "slow-window burn-rate threshold for the ticket-level verdict.")
_var("MXTPU_SLO_ALERTS", "int", 64,
     "size of the bounded alerts ring (last `slo_breach`/`slo_recovered` "
     "transitions) carried in flight-recorder dumps and `/statusz` — a "
     "watchdog/SIGUSR1 dump names which objective was burning when the "
     "process hung.")
_var("MXTPU_SLO_SERVE_P99_MS", "float", 1000.0,
     "built-in serving latency objective: p99 of "
     "`mxtpu_serve_request_seconds` (admission to resolution, per model) "
     "must stay under this many ms. Registered for every served model at "
     "load.")
_var("MXTPU_SLO_SERVE_AVAILABILITY", "float", 0.999,
     "built-in serving availability objective: the fraction of requests "
     "NOT deterministically rejected (429/504/503 sheds) must stay at or "
     "above this target; the error budget is `1 - target`.")
_var("MXTPU_SLO_SERVE_QUEUE_FRAC", "float", 0.8,
     "built-in serving queue-depth ceiling: `mxtpu_serve_queue_depth` "
     "must stay under this fraction of `MXTPU_SERVE_QUEUE_DEPTH` — the "
     "queue sitting near its admission limit is the page BEFORE 429s "
     "start (and the ROADMAP item-4 autoscaler's scale-up signal).")
_var("MXTPU_SLO_INTERTOKEN_P99_MS", "float", 250.0,
     "built-in generation objective: p99 of "
     "`mxtpu_serve_intertoken_seconds` (what a streaming client feels) "
     "must stay under this many ms.")
_var("MXTPU_SLO_KV_OCCUPANCY", "float", 0.95,
     "built-in generation objective: `mxtpu_serve_kv_occupancy` (used/"
     "total KV pages) ceiling — occupancy pinned above it means "
     "admissions are about to queue on page pressure.")
_var("MXTPU_SLO_STEP_SECONDS", "float", None,
     "optional training objective (registered at the first `observe_step` "
     "when set): p99 step time in seconds per trainer kind — a fleet's "
     "step-time regression page.")
_var("MXTPU_SLO_MFU_FLOOR", "float", None,
     "optional training objective (registered at the first `observe_step` "
     "when set): `mxtpu_step_mfu` floor, 0..1 — pages when achieved MFU "
     "drops below it (input starvation, a de-optimized step, a sick "
     "chip).")
_var("MXTPU_SLO_GOODPUT_FLOOR", "float", None,
     "optional training objective (registered at the first `observe_step` "
     "when set): `mxtpu_goodput_fraction` floor, 0..1 — pages when the "
     "windowed compute ÷ wall fraction drops below it (input stalls, "
     "checkpoint stalls, recompile storms; docs/observability.md "
     "§Goodput).")
_var("MXTPU_SLO_STEP_STALENESS_S", "float", None,
     "optional training staleness objective (registered at the first "
     "`observe_step` when set): seconds `mxtpu_steps_total` may sit "
     "without advancing before the objective burns — the SLO-shaped "
     "cousin of the flight-recorder watchdog.")

# -- distributed tracing ----------------------------------------------------
_var("MXTPU_TRACE_SAMPLE", "float", 0.0,
     "distributed tracing (docs/observability.md §Tracing): fraction of "
     "new root traces (serving requests, training steps) that record "
     "spans, 0.0..1.0. Default 0 — spans cost nothing unless sampled in; "
     "an incoming `x-mxtpu-trace` header / wire context with the sampled "
     "flag is always honored regardless of the local rate.")
_var("MXTPU_TRACE_SLOW_MS", "float", None,
     "always-sample-on-slow escape hatch: when set, unsampled root spans "
     "are buffered locally and RETROACTIVELY emitted if the root runs "
     "longer than this many milliseconds — every slow request/step leaves "
     "a trace even at sample rate 0. (Local-process spans only: a child "
     "process cannot know the root overran.)")
_var("MXTPU_TRACE_CONTEXT", "str", None,
     "inherited trace context, `<trace_id>-<span_id>-<flags>` (the "
     "`x-mxtpu-trace` header format). Set by `tools/launch.py` for each "
     "worker so training-step root spans join the launch's generation "
     "span; honored as the ambient parent for root spans minted in this "
     "process.")
_var("MXTPU_TRACE_FLOPS", "bool", True,
     "automatic FLOP accounting: derive per-executable FLOPs from JAX's "
     "lowered-HLO cost analysis at the unified executable registry's "
     "fill hook (`mxnet_tpu.compile` — eager ops, autograd backward, "
     "Executor builds, CachedOp, serving bucket warm) and "
     "accumulate executed FLOPs so `observe_step` publishes MFU with no "
     "manual `set_step_flops`. `0` disables the accounting (and the "
     "per-shape lowering it pays on each cache fill).")


# ---------------------------------------------------------------------------
# docs generation
# ---------------------------------------------------------------------------

def markdown_table():
    """The docs/env_vars.md Framework table, generated from the registry
    (one row per variable, declaration order). The env-registry lint
    checker proves the committed table matches this registry."""
    lines = ["| Variable | Default | Effect |", "|---|---|---|"]
    for var in _REGISTRY.values():
        doc = " ".join(var.doc.split())
        lines.append("| `%s` | %s | %s |" % (var.name, var.default_str(),
                                             doc))
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys

    args = sys.argv[1:]
    if args in ([], ["--markdown"]):
        sys.stdout.write(markdown_table())
    elif args == ["--names"]:
        sys.stdout.write("\n".join(names()) + "\n")
    else:
        sys.stderr.write("usage: python -m mxnet_tpu.env "
                         "[--markdown | --names]\n")
        sys.exit(2)
