"""Tests for ci/mxlint — the AST static-analysis suite.

Each checker gets fixture trees with known violations (positive), known-good
code (negative), pragma suppression, and the baseline workflow; plus the
regression that the pre-mxlint ``ci/lint_print.py`` CLI still works
standalone. The real-tree cleanliness gate lives in
``test_infra.py::test_mxlint_clean`` (tier-1).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT) if ROOT not in sys.path else None

from ci.mxlint import Repo, load_baseline, run_checkers  # noqa: E402
from ci.mxlint.checkers import CHECKERS  # noqa: E402
from ci.mxlint.checkers.concurrency import (LockDisciplineChecker,  # noqa: E402
                                            LockOrderChecker,
                                            ThreadHygieneChecker,
                                            build_lock_graph)
from ci.mxlint.checkers.env_registry import EnvRegistryChecker  # noqa: E402
from ci.mxlint.checkers.host_sync import HostSyncChecker  # noqa: E402
from ci.mxlint.checkers.metric_registry import MetricRegistryChecker  # noqa: E402
from ci.mxlint.checkers.registry_parity import RegistryParityChecker  # noqa: E402
from ci.mxlint.checkers.signal_safety import SignalSafetyChecker  # noqa: E402
from ci.mxlint.checkers.bare_print import BarePrintChecker  # noqa: E402
from ci.mxlint.checkers.compile_registry import CompileRegistryChecker  # noqa: E402
from ci.mxlint.checkers.tracer_leak import TracerLeakChecker  # noqa: E402
from ci.mxlint.checkers.trace_purity import TracePurityChecker  # noqa: E402
from ci.mxlint.checkers.retrace_hazard import RetraceHazardChecker  # noqa: E402
from ci.mxlint.checkers.donation_discipline import (  # noqa: E402
    DonationDisciplineChecker)


def _tree(tmp_path, files):
    for rel, content in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content))
    return Repo(str(tmp_path))


def _findings(checker, repo):
    return list(checker.run(repo))


def _lines(findings):
    return sorted((f.path, f.line) for f in findings)


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_host_sync_positive_roots_and_propagation(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/ops/myops.py": """\
        import functools
        import jax
        import numpy as _np
        from . import register

        @register("badop")
        def badop(x, axis=0):
            return float(x)            # line 8: cast of array param

        @jax.jit
        def jitted(x):
            return x.asnumpy()         # line 12: asnumpy under jit

        def helper(y):
            return y.asnumpy()         # line 15: traced via caller

        @functools.partial(jax.jit, static_argnums=(1,))
        def outer(x, n):
            return helper(x)

        def fwd(x):
            return _np.asarray(x)      # line 22: traced via defvjp

        def bwd(res, g):
            return (g,)

        @jax.custom_vjp
        def diffop(x):
            return x
        diffop.defvjp(fwd, bwd)
        """})
    got = _lines(_findings(HostSyncChecker(), repo))
    assert ("mxnet_tpu/ops/myops.py", 8) in got
    assert ("mxnet_tpu/ops/myops.py", 12) in got
    assert ("mxnet_tpu/ops/myops.py", 15) in got
    assert ("mxnet_tpu/ops/myops.py", 22) in got


def test_host_sync_negative(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/ops/okops.py": """\
        import jax
        import numpy as _np
        from . import register

        @register("hostop", host=True)
        def hostop(csr):
            return csr.asnumpy()       # host op: eager by design

        @register("okop")
        def okop(x, axis=0, k=1):
            pad = _np.asarray(-_np.inf, x.dtype)  # static constant
            return x + int(axis) + int(k)         # attr coercions

        def eager_helper(arr):
            return arr.asnumpy()       # never traced: no jit root calls it
        """})
    assert _findings(HostSyncChecker(), repo) == []


def test_host_sync_pallas_kernel_body(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/ops/pk.py": """\
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...].asnumpy()  # line 4

        def launch(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
        """})
    got = _lines(_findings(HostSyncChecker(), repo))
    assert ("mxnet_tpu/ops/pk.py", 4) in got


# ---------------------------------------------------------------------------
# signal-safety
# ---------------------------------------------------------------------------

_CORE_OK = """\
    def snapshot():
        return {}

    def rank():
        import os
        return 0
"""


def test_signal_safety_positive(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/telemetry/core.py": _CORE_OK,
        "mxnet_tpu/telemetry/recorder.py": """\
        import logging
        import threading
        from . import core

        _lock = threading.Lock()

        def dump(reason):
            logging.getLogger("x").warning("dumping")   # line 8
            with _lock:                                 # line 9
                pass
            t = threading.Thread(target=dump)           # line 11
            core.snapshot()
            unknowable()                                # line 13

        def _on_sigusr1(signum, frame):
            dump("sig")
        """})
    got = _lines(_findings(SignalSafetyChecker(), repo))
    for line in (8, 9, 11, 13):
        assert ("mxnet_tpu/telemetry/recorder.py", line) in got, got


def test_signal_safety_computed_receiver_and_subscripted_lock(tmp_path):
    """Regression: a lock reached through a computed receiver
    (`self._locks[i].acquire()`, `with _LOCKS[0]:`) must still be flagged —
    dotted-name resolution alone cannot see it."""
    repo = _tree(tmp_path, {
        "mxnet_tpu/telemetry/core.py": _CORE_OK,
        "mxnet_tpu/telemetry/recorder.py": """\
        from . import core

        _LOCKS = [None]

        def dump(reason):
            _LOCKS[0].acquire()        # line 6: computed receiver
            with _LOCKS[0]:            # line 7: subscripted lock
                pass

        def _on_sigusr1(signum, frame):
            dump("sig")
        """})
    got = _lines(_findings(SignalSafetyChecker(), repo))
    assert ("mxnet_tpu/telemetry/recorder.py", 6) in got, got
    assert ("mxnet_tpu/telemetry/recorder.py", 7) in got, got


def test_signal_safety_negative_and_pragma(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/telemetry/core.py": _CORE_OK,
        "mxnet_tpu/telemetry/recorder.py": """\
        import json
        import os
        import sys
        import threading
        import time
        from . import core

        def _stacks():
            return [t.name for t in threading.enumerate()]

        def dump(reason):
            payload = {"r": reason, "s": _stacks(), "m": core.snapshot(),
                       "t": time.time(), "rank": core.rank()}
            with open(os.path.join("/tmp", "d.json"), "w") as f:
                json.dump(payload, f)
            sys.stderr.write("dumped\\n")
            cb = getattr(dump, "_cb", None)
            if callable(cb):
                cb(reason)  # mxlint: disable=signal-safety

        def _on_sigusr1(signum, frame):
            dump("sig")
        """})
    findings = _findings(SignalSafetyChecker(), repo)
    kept, by_pragma, _ = run_checkers(repo, [SignalSafetyChecker()])
    assert kept == [] and len(by_pragma) == 1, _lines(findings)


_RECORDER_OK = """\
    from . import core

    def dump(reason):
        return core.snapshot()

    def _on_sigusr1(signum, frame):
        dump("sig")
"""


def test_signal_safety_serving_handlers(tmp_path):
    """ISSUE-6 satellite: the serving signal handlers (the replica
    worker's module-level `_on_term` and the frontend's NESTED
    `_on_signal`) are entry points too — a thread start or logging call
    smuggled into either is flagged; the real flag-flip/Event-set shape
    passes clean."""
    dirty = _tree(tmp_path / "dirty", {
        "mxnet_tpu/telemetry/core.py": _CORE_OK,
        "mxnet_tpu/telemetry/recorder.py": _RECORDER_OK,
        "mxnet_tpu/serving/supervisor.py": """\
        import logging

        _STOP = [False]

        def _on_term(signum, frame):
            logging.getLogger("x").info("stopping")   # line 6
            _STOP[0] = True
        """,
        "mxnet_tpu/serving/server.py": """\
        import threading

        class ServingServer:
            def install_signal_handlers(self):
                def _on_signal(signum, frame):
                    t = threading.Thread(target=self.drain)   # line 6
                    t.start()                                 # line 7
                return _on_signal
        """})
    got = _lines(_findings(SignalSafetyChecker(), dirty))
    assert ("mxnet_tpu/serving/supervisor.py", 6) in got, got
    assert ("mxnet_tpu/serving/server.py", 6) in got, got
    assert ("mxnet_tpu/serving/server.py", 7) in got, got

    clean = _tree(tmp_path / "clean", {
        "mxnet_tpu/telemetry/core.py": _CORE_OK,
        "mxnet_tpu/telemetry/recorder.py": _RECORDER_OK,
        "mxnet_tpu/serving/supervisor.py": """\
        _STOP = [False]

        def _on_term(signum, frame):
            _STOP[0] = True
        """,
        "mxnet_tpu/serving/server.py": """\
        import threading

        class ServingServer:
            def install_signal_handlers(self):
                def _on_signal(signum, frame):
                    self._drain_shutdown = True
                    self._drain_event.set()
                return _on_signal
        """})
    assert _findings(SignalSafetyChecker(), clean) == []


# ---------------------------------------------------------------------------
# env-registry
# ---------------------------------------------------------------------------

_ENV_PY = """\
    _REGISTRY = {}

    def _var(name, vtype, default, doc):
        _REGISTRY[name] = (vtype, default, doc)

    _var("MXTPU_KNOWN", "str", None, "a documented knob")
    _var("MXTPU_ORPHAN", "int", 3, "registered but undocumented")
"""

_DOCS_MD = """\
    # Environment variables

    ## Framework (`MXTPU_*`)

    | Variable | Default | Effect |
    |---|---|---|
    | `MXTPU_KNOWN` | unset | a documented knob |
    | `MXTPU_GHOST` | `1` | documented but not registered |

    ## Other
"""


def test_env_registry_all_directions(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/env.py": _ENV_PY,
        "docs/env_vars.md": _DOCS_MD,
        "mxnet_tpu/lib.py": """\
        import os
        from . import env as _env

        raw = os.environ.get("MXTPU_RAW_READ")        # line 4: raw read
        sub = os.environ["MXTPU_SUB_READ"]            # line 5: raw read
        ok = _env.get("MXTPU_KNOWN")                  # fine
        bad = _env.get("MXTPU_UNDECLARED")            # line 7: unregistered
        os.environ["MXTPU_WRITE_OK"] = "1"            # writes are fine
        """,
        "tools/probe.py": """\
        import os
        x = os.environ.get("MXTPU_TOOL_ONLY")         # line 2: unregistered
        y = os.environ.get("MXTPU_KNOWN", "d")        # registered: fine
        """,
        "tools/serve.py": "import os\nz = os.environ.get('MXTPU_KNOWN')\n",
    })
    findings = _findings(EnvRegistryChecker(), repo)
    got = _lines(findings)
    assert ("mxnet_tpu/lib.py", 4) in got
    assert ("mxnet_tpu/lib.py", 5) in got
    assert ("mxnet_tpu/lib.py", 7) in got
    assert ("tools/probe.py", 2) in got
    messages = "\n".join(f.message for f in findings)
    assert "MXTPU_ORPHAN" in messages      # registered, undocumented
    assert "MXTPU_GHOST" in messages       # documented, unregistered
    assert "MXTPU_WRITE_OK" not in messages
    assert len(findings) == 6, got


def test_env_registry_clean_tree(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/env.py": _ENV_PY.replace(
            '_var("MXTPU_ORPHAN", "int", 3, "registered but undocumented")',
            ""),
        "docs/env_vars.md": _DOCS_MD.replace(
            "| `MXTPU_GHOST` | `1` | documented but not registered |\n", ""),
        "mxnet_tpu/lib.py":
            "from . import env as _env\nv = _env.raw('MXTPU_KNOWN')\n",
    })
    assert _findings(EnvRegistryChecker(), repo) == []


# ---------------------------------------------------------------------------
# registry-parity
# ---------------------------------------------------------------------------

_OPS_PY = """\
    from . import register

    @register("Convolution", aliases=("conv2d",))
    def convolution(data, weight, bias=None, kernel=()):
        return data

    register("identity", aliases=("_copy",))(lambda data: data)
"""


def test_registry_parity_stale_table_and_unwired_vjp(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/ops/nn.py": _OPS_PY,
        "mxnet_tpu/symbol/register.py": """\
        _INPUT_SLOTS = {
            "Convolution": (["data", "weight", "bias"], []),
            "Deconvolution": (["data", "weight"], []),
        }
        _SHAPE_TRANSPARENT = {"identity", "_copy", "amp_cast"}
        _OPTIONAL_DROP = {}
        _ARG_SHAPE_RULES = {"conv2d": None}

        def populate(d):
            for name in ("Convolution",):
                if name.startswith("_contrib_"):
                    pass
            d["contrib"] = 1
        """,
        "mxnet_tpu/ndarray/register.py": """\
        def populate(d):
            for name in ("Convolution",):
                if name.startswith("_contrib_"):
                    pass
                if name.startswith("_linalg_"):
                    pass
            d["contrib"] = 1
            d["linalg"] = 1
        """,
        "mxnet_tpu/ops/vjp.py": """\
        import functools
        import jax

        @jax.custom_vjp
        def wired(x):
            return x

        def fwd(x):
            return x, None

        def bwd(res, g):
            return (g,)
        wired.defvjp(fwd, bwd)

        @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
        def unwired(x, n):
            return x
        """})
    findings = _findings(RegistryParityChecker(), repo)
    messages = "\n".join(f.message for f in findings)
    assert "Deconvolution" in messages           # stale _INPUT_SLOTS key
    assert "amp_cast" in messages                # stale transparent entry
    assert "'_linalg_'" in messages              # prefix routed nd-only
    assert "'linalg'" in messages                # namespace nd-only
    assert "`unwired`" in messages and "defvjp" in messages
    assert "wired`" not in messages.replace("`unwired`", "")
    assert "identity" not in messages            # call-form registration seen
    assert "conv2d" not in messages              # alias resolved


# ---------------------------------------------------------------------------
# bare-print (ported lint_print) + old CLI regression
# ---------------------------------------------------------------------------

_PRINTY = """\
    x = 1
    print("no")
    y = 2  # print("in comment") is fine
    s = "print(also fine)"
    pprint(1)
    obj.print(2)
    print("ok")  # allow-print
"""


# ---------------------------------------------------------------------------
# metric-registry
# ---------------------------------------------------------------------------

_METRIC_DOCS = """\
# Observability

## Metrics

| Metric | Labels | Source |
|---|---|---|
| `mxtpu_good_total` | — | documented and emitted |
| `mxtpu_stale_total` | — | documented, nothing emits it |

## Tracing

| Span | Component | What |
|---|---|---|
| `serve.good` | server | documented and emitted |
| `train.stale` | train | documented, nothing emits it |
"""

_METRIC_EMITTERS = """\
from . import telemetry
from .telemetry import tracing
from .telemetry.core import counter as _tm_counter

def hot():
    telemetry.counter("mxtpu_good_total").inc()
    _tm_counter("mxtpu_aliased_total").inc()   # line 7: aliased + undocumented
    telemetry.gauge("mxtpu_undocumented").set(1)  # line 8: undocumented
    with tracing.root("serve.good", component="server"):
        with tracing.span("serve.undocumented"):  # line 10: undocumented span
            pass
"""


def test_metric_registry_both_directions(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/emit.py": _METRIC_EMITTERS,
        "docs/observability.md": _METRIC_DOCS,
    })
    got = _findings(MetricRegistryChecker(), repo)
    lines = _lines(got)
    # undocumented emissions point at the emitting line (aliased factory
    # names like _tm_counter are matched on their suffix)
    assert ("mxnet_tpu/emit.py", 7) in lines
    assert ("mxnet_tpu/emit.py", 8) in lines
    assert ("mxnet_tpu/emit.py", 10) in lines
    # stale docs rows point at the docs file
    stale = [f.message for f in got if f.path == "docs/observability.md"]
    assert any("mxtpu_stale_total" in m for m in stale), stale
    assert any("train.stale" in m for m in stale), stale
    # documented-and-emitted names produce no finding
    assert not any("mxtpu_good_total" in f.message or
                   "serve.good" in f.message for f in got)


def test_metric_registry_clean_and_unverifiable(tmp_path):
    clean = _tree(tmp_path / "clean", {
        "mxnet_tpu/emit.py": """\
            from . import telemetry

            def hot():
                telemetry.counter("mxtpu_good_total").inc()
            """,
        "docs/observability.md": """\
            ## Metrics

            | Metric | Labels |
            |---|---|
            | `mxtpu_good_total` | — |
            """,
    })
    assert _findings(MetricRegistryChecker(), clean) == []
    # a moved/emptied Metrics section is one loud finding, not silence
    blank = _tree(tmp_path / "blank", {
        "mxnet_tpu/emit.py": "x = 1\n",
        "docs/observability.md": "# nothing here\n",
    })
    got = _findings(MetricRegistryChecker(), blank)
    assert len(got) == 1 and "unverifiable" in got[0].message


def test_metric_registry_covers_memory_metrics():
    """The §Memory metrics (telemetry/memory.py) are visible to the
    checker — labeled emissions (`core.gauge(name, labels)`) parse to
    literal names — and every one is documented, both directions."""
    from ci.mxlint.checkers.metric_registry import (documented_names,
                                                    emitted_names)

    repo = Repo(ROOT)
    emitted, _ = emitted_names(repo)
    documented, _ = documented_names(repo)
    for name in ("mxtpu_device_bytes_in_use", "mxtpu_device_bytes_peak",
                 "mxtpu_device_bytes_limit", "mxtpu_process_rss_bytes",
                 "mxtpu_process_vmhwm_bytes", "mxtpu_ndarray_live",
                 "mxtpu_ndarray_live_bytes", "mxtpu_step_peak_bytes_delta",
                 "mxtpu_donation_declared_bytes",
                 "mxtpu_donation_alias_bytes",
                 "mxtpu_serve_model_memory_bytes"):
        assert name in emitted, "library no longer emits %s" % name
        assert name in documented, "%s missing from observability.md" % name


def test_metric_registry_dynamic_names_skipped(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/emit.py": """\
            from . import telemetry

            def hot(name):
                telemetry.counter("mxtpu_dyn_%s_total" % name).inc()
            """,
        "docs/observability.md": _METRIC_DOCS,
    })
    # dynamic names are invisible (no literal first arg) — nothing to flag
    got = [f for f in _findings(MetricRegistryChecker(), repo)
           if f.path.startswith("mxnet_tpu/")]
    assert got == []


# ---------------------------------------------------------------------------
# compile-registry
# ---------------------------------------------------------------------------

def test_compile_registry_positive_patterns(tmp_path):
    """The three ad-hoc executable-cache spellings all flag: an
    lru_cache-wrapped jit builder, a direct subscript store of a jit
    result, a name-laundered subscript store, and a setdefault store."""
    repo = _tree(tmp_path, {"mxnet_tpu/holders.py": """\
        import functools
        import jax

        @functools.lru_cache(maxsize=128)
        def jitted(name):                      # line 4: hidden cache
            def call(x):
                return x
            return jax.jit(call)

        class Holder:
            def __init__(self):
                self._cache = {}

            def direct(self, sig, fn):
                self._cache[sig] = jax.jit(fn)        # line 14

            def laundered(self, sig, fn):
                exe = jax.jit(fn)
                self._cache[sig] = exe                # line 18

            def via_setdefault(self, sig, fn):
                return self._cache.setdefault(sig, jax.jit(fn))  # line 21
        """})
    got = _lines(_findings(CompileRegistryChecker(), repo))
    assert got == [("mxnet_tpu/holders.py", 5),    # def jitted
                   ("mxnet_tpu/holders.py", 15),   # direct subscript store
                   ("mxnet_tpu/holders.py", 19),   # laundered via name
                   ("mxnet_tpu/holders.py", 22)]   # setdefault


def test_compile_registry_negative_and_scope(tmp_path):
    """Not flagged: the registry package itself, non-jit lru_caches,
    single module-global jits (keyed by nothing), registry-routed fills,
    and pragma'd exceptions."""
    repo = _tree(tmp_path, {
        "mxnet_tpu/compile/registry.py": """\
            import jax

            class Registry:
                def fill(self, table, key, fn):
                    table[key] = jax.jit(fn)   # the ONE allowed home
            """,
        "mxnet_tpu/clean.py": """\
            import functools
            import jax
            from . import compile as _compile

            @functools.lru_cache(maxsize=8)
            def parse(spec):                   # lru_cache without jit: fine
                return tuple(spec.split(","))

            _BARRIER = jax.jit(lambda v: v.sum())   # unkeyed singleton: fine

            def routed(key, fn):
                return _compile.get_or_build(key, lambda: jax.jit(fn))

            class Ok:
                def __init__(self):
                    self._cache = {}

                def store_routed(self, sig, key, fn):
                    # registry result in a local dict: not a jit holder
                    self._cache[sig] = routed(key, fn)
            """,
        "mxnet_tpu/excused.py": """\
            import jax
            _T = {}

            def special(sig, fn):
                _T[sig] = jax.jit(fn)  # mxlint: disable=compile-registry
            """,
    })
    from ci.mxlint import run_checkers

    kept, by_pragma, _ = run_checkers(repo, [CompileRegistryChecker()])
    assert _lines(kept) == []
    assert _lines(by_pragma) == [("mxnet_tpu/excused.py", 5)]


def test_compile_registry_real_tree_is_clean():
    """The live tree: every executable factory resolves through
    mxnet_tpu/compile (the acceptance criterion for the migration)."""
    repo = Repo(ROOT)
    assert _lines(_findings(CompileRegistryChecker(), repo)) == []


def test_bare_print_checker_semantics(tmp_path):
    repo = _tree(tmp_path, {
        "mxnet_tpu/bad.py": _PRINTY,
        "mxnet_tpu/notebook/show.py": "print('notebook display ok')\n",
        "mxnet_tpu/test_utils.py": "print('harness ok')\n",
    })
    got = _lines(_findings(BarePrintChecker(), repo))
    assert got == [("mxnet_tpu/bad.py", 2)]


def test_lint_print_old_cli_still_catches(tmp_path):
    """Satellite regression: the standalone ci/lint_print.py CLI (pre-mxlint
    interface, used by external scripts) still exits nonzero on a bare
    print and 0 on a clean tree."""
    bad = tmp_path / "mxnet_tpu"
    bad.mkdir()
    (bad / "bad.py").write_text(textwrap.dedent(_PRINTY))
    lint = os.path.join(ROOT, "ci", "lint_print.py")
    r = subprocess.run([sys.executable, lint, str(tmp_path)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 1 and "bad.py:2" in r.stdout, r.stdout
    (bad / "bad.py").write_text("x = 1\n")
    r = subprocess.run([sys.executable, lint, str(tmp_path)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# concurrency suite: lock-discipline / lock-order / thread-hygiene
# ---------------------------------------------------------------------------

def test_lock_discipline_unguarded_cross_root_write(tmp_path):
    """A worker thread and the public API both write an attribute with no
    lock anywhere: every exposed write site flags; the lock-guarded
    attribute next to it stays quiet."""
    repo = _tree(tmp_path, {"mxnet_tpu/svc.py": """\
        import threading

        class Service:
            def __init__(self):
                self._lock = threading.Lock()
                self.counter = 0
                self.guarded = 0
                self._t = threading.Thread(target=self._loop,
                                           name="w", daemon=True)
                self._t.start()

            def _loop(self):
                self.counter += 1           # line 13: worker write
                with self._lock:
                    self.guarded += 1       # guarded everywhere: quiet

            def bump(self):
                self.counter += 1           # line 18: api write
                with self._lock:
                    self.guarded += 1
        """})
    got = _lines(_findings(LockDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/svc.py", 13), ("mxnet_tpu/svc.py", 18)], got


def test_lock_discipline_inconsistent_guarding(tmp_path):
    """An attribute written under the lock in one method and bare in
    another (single api root — the registry's lock-free-hit-path shape)
    flags only the exposed site, and held-lock context PROPAGATES through
    same-class calls: a write inside a helper invoked under `with
    self._lock` is guarded."""
    repo = _tree(tmp_path, {"mxnet_tpu/reg.py": """\
        import threading

        class Table:
            def __init__(self):
                self._lock = threading.Lock()
                self._stamps = {}

            def touch(self, key):
                self._stamps[key] = 1       # line 9: exposed

            def _store(self, key):
                self._stamps[key] = 2       # guarded via caller: quiet

            def insert(self, key):
                with self._lock:
                    self._store(key)
        """})
    got = _lines(_findings(LockDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/reg.py", 9)], got


def test_lock_discipline_gil_atomic_annotation_honored(tmp_path):
    """`# mxlint: gil-atomic — <why>` on the write line suppresses the
    finding — intent becomes machine-checked documentation."""
    repo = _tree(tmp_path, {"mxnet_tpu/svc.py": """\
        import threading

        class Service:
            def __init__(self):
                self.flag = False
                t = threading.Thread(target=self._loop, name="w",
                                     daemon=True)
                t.start()

            def _loop(self):
                self.flag = True  # mxlint: gil-atomic — monotonic flag

            def stop(self):
                self.flag = True  # mxlint: gil-atomic — monotonic flag
        """})
    assert _findings(LockDisciplineChecker(), repo) == []


def test_lock_discipline_thread_in_lambda_root_discovery(tmp_path):
    """A `Thread(target=lambda: ...)` root expands through the lambda into
    the method it calls — the write inside is still attributed to the
    worker root."""
    repo = _tree(tmp_path, {"mxnet_tpu/svc.py": """\
        import threading

        class Service:
            def __init__(self):
                self.state = 0
                t = threading.Thread(target=lambda: self._work(),
                                     name="w", daemon=True)
                t.start()

            def _work(self):
                self.state = 1              # line 11: via lambda root

            def poke(self):
                self.state = 2              # line 14: api root
        """})
    got = _lines(_findings(LockDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/svc.py", 11), ("mxnet_tpu/svc.py", 14)], got


def test_lock_discipline_sync_object_reassigned_under_use(tmp_path):
    """The io.py race shape: a worker reads `self._queue` live while
    reset() swaps in a fresh Queue — the reassignment flags. The
    capture-as-local worker (image.py's shape) is clean."""
    racy = _tree(tmp_path / "racy", {"mxnet_tpu/it.py": """\
        import queue
        import threading

        class Prefetch:
            def __init__(self):
                self._queue = queue.Queue(maxsize=2)
                self._start()

            def _start(self):
                def run():
                    self._queue.put(1)
                t = threading.Thread(target=run, name="w", daemon=True)
                t.start()

            def reset(self):
                self._queue = queue.Queue(maxsize=2)   # line 16
                self._start()
        """})
    got = _findings(LockDisciplineChecker(), racy)
    assert _lines(got) == [("mxnet_tpu/it.py", 16)], _lines(got)
    assert "replaced outside __init__" in got[0].message

    clean = _tree(tmp_path / "clean", {"mxnet_tpu/it.py": """\
        import queue
        import threading

        class Prefetch:
            def __init__(self):
                self._queue = queue.Queue(maxsize=2)
                self._start()

            def _start(self):
                q = self._queue

                def run():
                    q.put(1)
                t = threading.Thread(target=run, name="w", daemon=True)
                t.start()

            def reset(self):
                self._queue = queue.Queue(maxsize=2)
                self._start()
        """})
    assert _findings(LockDisciplineChecker(), clean) == []


def test_lock_order_cycle_and_clean(tmp_path):
    """Two locks taken in opposite orders across serving classes is a
    deadlock finding; a consistent order is clean."""
    cyclic = _tree(tmp_path / "cyc", {"mxnet_tpu/serving/ab.py": """\
        import threading

        class A:
            def __init__(self, b):
                self._lock = threading.Lock()
                self._b = b

            def forward(self):
                with self._lock:
                    self._b.enter()

            def reenter(self):
                with self._lock:
                    pass

        class B:
            def __init__(self, a):
                self._lock = threading.Lock()
                self._a = a

            def enter(self):
                with self._lock:
                    pass

            def backward(self):
                with self._lock:
                    self._a.reenter()
        """})
    got = _findings(LockOrderChecker(), cyclic)
    assert len(got) == 1 and "lock-order cycle" in got[0].message, \
        [f.render() for f in got]
    assert "A._lock" in got[0].message and "B._lock" in got[0].message

    acyclic = _tree(tmp_path / "ok", {"mxnet_tpu/serving/ab.py": """\
        import threading

        class A:
            def __init__(self, b):
                self._lock = threading.Lock()
                self._b = b

            def forward(self):
                with self._lock:
                    self._b.enter()

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def enter(self):
                with self._lock:
                    pass
        """})
    assert _findings(LockOrderChecker(), acyclic) == []


def test_lock_order_self_deadlock_reacquire(tmp_path):
    """Re-acquiring a non-reentrant Lock down a call chain is flagged;
    the same shape on an RLock — or a default Condition, whose internal
    lock IS an RLock — is legal."""
    repo = _tree(tmp_path, {"mxnet_tpu/serving/re.py": """\
        import threading

        class P:
            def __init__(self):
                self._lock = threading.Lock()
                self._rlock = threading.RLock()
                self._cv = threading.Condition()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:        # line 14: self-deadlock
                    pass

            def outer_r(self):
                with self._rlock:
                    self._inner_r()

            def _inner_r(self):
                with self._rlock:       # RLock: fine
                    pass

            def outer_cv(self):
                with self._cv:
                    self._inner_cv()

            def _inner_cv(self):
                with self._cv:          # default Condition: fine
                    pass
        """})
    got = _findings(LockOrderChecker(), repo)
    assert _lines(got) == [("mxnet_tpu/serving/re.py", 14)], \
        [f.render() for f in got]
    assert "re-acquired" in got[0].message


def test_lock_order_real_graph_nonvacuous_and_acyclic():
    """Acceptance: the live serving/telemetry/compile lock graph is
    ACYCLIC — and non-vacuously so: the checker must still see the known
    batcher-submit -> admission-gate -> pool-lock edge (if this edge
    disappears, the walker regressed and the acyclicity proof is hollow)."""
    graph = build_lock_graph(Repo(ROOT))
    edges = set(graph.edges)
    assert ("mxnet_tpu/serving/batcher.py:DynamicBatcher._cv",
            "mxnet_tpu/serving/replica_pool.py:ReplicaPool._lock") in edges, \
        sorted(edges)
    assert graph.cycles() == []
    assert graph.reacquires == []


def test_thread_hygiene_unnamed_and_unjoined(tmp_path):
    """Library threads must pass name= and be daemon or joined; the
    pragma works like every other rule's."""
    repo = _tree(tmp_path, {"mxnet_tpu/w.py": """\
        import threading

        def spawn():
            t = threading.Thread(target=spawn)          # line 4: both
            t.start()

        def ok():
            t = threading.Thread(target=ok, name="mxtpu-x", daemon=True)
            t.start()

        def joined_ok():
            t = threading.Thread(target=ok, name="mxtpu-y")
            t.start()
            t.join()

        def excused():
            t = threading.Thread(target=ok)  # mxlint: disable=thread-hygiene
            t.start()
            t.join()

        def decoy(out_t, parts):
            t = threading.Thread(target=ok, name="mxtpu-z")  # line 22
            t.start()
            out_t.join()        # OTHER object's join must not excuse t
            return ",".join(parts)

        def timer_bad():
            t = threading.Timer(5.0, ok)                     # line 28
            t.start()

        def timer_ok():
            t = threading.Timer(5.0, ok)
            t.name = "mxtpu-timer"
            t.daemon = True
            t.start()
        """})
    kept, by_pragma, _ = run_checkers(repo, [ThreadHygieneChecker()])
    msgs = [(f.line, f.message) for f in kept]
    assert [line for line, _ in msgs] == [4, 4, 22, 28, 28], msgs
    assert sum("without a name" in m for _, m in msgs) == 2
    assert sum("never joined" in m for _, m in msgs) == 3
    assert len(by_pragma) == 1


def test_concurrency_rules_real_tree_clean():
    """The live tree is clean under all three concurrency rules (real
    races fixed, deliberate lock-free state gil-atomic-annotated — the
    acceptance criterion for this suite)."""
    repo = Repo(ROOT)
    assert _lines(_findings(ThreadHygieneChecker(), repo)) == []
    assert _lines(_findings(LockOrderChecker(), repo)) == []
    kept, _, _ = run_checkers(repo, [LockDisciplineChecker()])
    assert _lines(kept) == []


def test_lock_discipline_real_tree_annotations_load_bearing():
    """The committed gil-atomic annotations are LOAD-BEARING: stripping
    them re-surfaces findings (i.e. the checker still sees those sites —
    an annotation on dead code would rot silently)."""
    import re

    repo = Repo(ROOT)
    checker = LockDisciplineChecker()
    rel = "mxnet_tpu/telemetry/recorder.py"
    src = repo.read(rel)
    assert "mxlint: gil-atomic" in src
    stripped = re.sub(r"# mxlint: gil-atomic[^\n]*", "", src)
    repo._cache = {}
    lines = stripped.splitlines()
    import ast as _ast

    repo._cache[rel] = (_ast.parse(stripped, filename=rel), lines)
    got = [f for f in checker.run(repo) if f.path == rel]
    assert got, "stripping recorder.py annotations surfaces nothing — " \
        "the checker no longer sees the ring/last_step writes"


# ---------------------------------------------------------------------------
# runner: pragmas, baseline, CLI
# ---------------------------------------------------------------------------

def test_pragma_suppresses_only_named_rule(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/p.py": """\
        import os
        a = os.environ.get("MXTPU_X")  # mxlint: disable=env-registry
        b = os.environ.get("MXTPU_Y")  # mxlint: disable=host-sync
        """,
        "mxnet_tpu/env.py": "def _var(n, t, d, doc):\n    pass\n"
                            "_var('MXTPU_Q', 'str', None, 'q')\n",
        "docs/env_vars.md": "## Framework (`MXTPU_*`)\n\n"
                            "| Variable | Default | Effect |\n|---|---|---|\n"
                            "| `MXTPU_Q` | unset | q |\n"})
    kept, by_pragma, _ = run_checkers(repo, [EnvRegistryChecker()])
    assert [(f.path, f.line) for f in kept] == [("mxnet_tpu/p.py", 3)]
    assert len(by_pragma) == 1


def test_baseline_grandfathers_and_expires_on_edit(tmp_path):
    files = {
        "mxnet_tpu/env.py": "def _var(n, t, d, doc):\n    pass\n"
                            "_var('MXTPU_Q', 'str', None, 'q')\n",
        "docs/env_vars.md": "## Framework (`MXTPU_*`)\n\n"
                            "| Variable | Default | Effect |\n|---|---|---|\n"
                            "| `MXTPU_Q` | unset | q |\n",
        "mxnet_tpu/old.py": "import os\nv = os.environ.get('MXTPU_LEGACY')\n",
    }
    repo = _tree(tmp_path, files)
    checker = EnvRegistryChecker()
    (kept, _, _) = run_checkers(repo, [checker])
    assert len(kept) == 1
    baseline_file = tmp_path / "baseline.txt"
    baseline_file.write_text(kept[0].key(repo) + "\n")
    baseline = load_baseline(str(baseline_file))
    kept2, _, by_baseline = run_checkers(repo, [checker], baseline)
    assert kept2 == [] and len(by_baseline) == 1
    # editing the flagged line invalidates its grandfathering
    (tmp_path / "mxnet_tpu/old.py").write_text(
        "import os\nv = os.environ.get('MXTPU_LEGACY2')\n")
    repo2 = Repo(str(tmp_path))
    kept3, _, by3 = run_checkers(repo2, [checker], baseline)
    assert len(kept3) == 1 and by3 == []


def test_update_baseline_with_rule_keeps_other_rules(tmp_path):
    """Regression: `--rule X --update-baseline` must not discard other
    rules' grandfathered entries."""
    _tree(tmp_path, {
        "mxnet_tpu/env.py": "def _var(n, t, d, doc):\n    pass\n"
                            "_var('MXTPU_Q', 'str', None, 'q')\n",
        "docs/env_vars.md": "## Framework (`MXTPU_*`)\n\n"
                            "| Variable | Default | Effect |\n|---|---|---|\n"
                            "| `MXTPU_Q` | unset | q |\n",
        "mxnet_tpu/v.py": "import os\nv = os.environ.get('MXTPU_V')\n",
    })
    base = tmp_path / "b.txt"
    base.write_text("host-sync\tmxnet_tpu/other.py\tx.asnumpy()\n")
    r = subprocess.run(
        [sys.executable, "-m", "ci.mxlint", "--root", str(tmp_path),
         "--rule", "env-registry", "--baseline", str(base),
         "--update-baseline"],
        capture_output=True, text=True, cwd=ROOT, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    content = base.read_text()
    assert "host-sync\tmxnet_tpu/other.py" in content, content  # preserved
    assert "env-registry\tmxnet_tpu/v.py" in content, content   # added


@pytest.mark.parametrize("args,expect_rc", [
    (["--list-rules"], 0),
    (["--rule", "definitely-not-a-rule"], 2),
])
def test_cli_modes(args, expect_rc):
    r = subprocess.run([sys.executable, "-m", "ci.mxlint"] + args,
                       capture_output=True, text=True, cwd=ROOT, timeout=240)
    assert r.returncode == expect_rc, r.stdout + r.stderr
    if expect_rc == 0:
        for rule in ("host-sync", "signal-safety", "env-registry",
                     "registry-parity", "compile-registry", "bare-print",
                     "lock-discipline", "lock-order", "thread-hygiene",
                     "tracer-leak", "trace-purity", "retrace-hazard",
                     "donation-discipline"):
            assert rule in r.stdout


def test_cli_nonzero_on_violation_and_update_baseline(tmp_path):
    _tree(tmp_path, {
        "mxnet_tpu/env.py": "def _var(n, t, d, doc):\n    pass\n"
                            "_var('MXTPU_Q', 'str', None, 'q')\n",
        "docs/env_vars.md": "## Framework (`MXTPU_*`)\n\n"
                            "| Variable | Default | Effect |\n|---|---|---|\n"
                            "| `MXTPU_Q` | unset | q |\n",
        "mxnet_tpu/v.py": "import os\nv = os.environ.get('MXTPU_V')\n",
    })
    base = str(tmp_path / "b.txt")
    cmd = [sys.executable, "-m", "ci.mxlint", "--root", str(tmp_path),
           "--rule", "env-registry", "--baseline", base]
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 1 and "MXTPU_V" in r.stdout, r.stdout
    r = subprocess.run(cmd + ["--update-baseline"], capture_output=True,
                       text=True, cwd=ROOT, timeout=240)
    assert r.returncode == 0, r.stdout
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 0 and "1 baselined" in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# the typed env registry itself
# ---------------------------------------------------------------------------

def test_env_module_typed_accessors(monkeypatch):
    from mxnet_tpu import env

    monkeypatch.delenv("MXTPU_FLIGHTREC_EVENTS", raising=False)
    assert env.get("MXTPU_FLIGHTREC_EVENTS") == 512
    monkeypatch.setenv("MXTPU_FLIGHTREC_EVENTS", "64")
    assert env.get("MXTPU_FLIGHTREC_EVENTS") == 64
    monkeypatch.setenv("MXTPU_FLIGHTREC_EVENTS", "junk")
    assert env.get("MXTPU_FLIGHTREC_EVENTS") == 512  # malformed -> default
    monkeypatch.setenv("MXTPU_TELEMETRY", "off")
    assert env.get("MXTPU_TELEMETRY") is False
    monkeypatch.setenv("MXTPU_TELEMETRY", "1")
    assert env.get("MXTPU_TELEMETRY") is True
    assert env.raw("MXTPU_TELEMETRY") == "1"
    monkeypatch.setenv("MXTPU_CKPT_DIR", "")
    assert not env.is_set("MXTPU_CKPT_DIR")
    with pytest.raises(KeyError):
        env.get("MXTPU_NOT_REGISTERED")
    with pytest.raises(KeyError):
        env.raw("MXTPU_NOT_REGISTERED")
    assert env.get("MXTPU_TEST_SEED", default=400) == 400  # per-site dflt
    table = env.markdown_table()
    assert table.splitlines()[0] == "| Variable | Default | Effect |"
    assert all("| `MXTPU_" in line for line in table.splitlines()[2:])


# ---------------------------------------------------------------------------
# trace-discipline suite: tracer-leak / trace-purity / retrace-hazard /
# donation-discipline
# ---------------------------------------------------------------------------

def test_tracer_leak_pr9_rng_chain_shape(tmp_path):
    """The PR-9 bug class verbatim: a lazy key mint inside an AOT trace
    calls into the global threefry chain and stores the resulting tracer
    into closed-over state — both halves must be flagged."""
    repo = _tree(tmp_path, {"mxnet_tpu/aot.py": """\
        import jax
        from mxnet_tpu import random as _random

        _CHAIN = {}

        @jax.jit
        def fill(params):
            key = _random.next_key()     # line 8: RNG-chain mutator
            _CHAIN["key"] = key          # line 9: closed-over store
            return params
        """})
    got = _lines(_findings(TracerLeakChecker(), repo))
    assert got == [("mxnet_tpu/aot.py", 8), ("mxnet_tpu/aot.py", 9)]


def test_tracer_leak_instance_state_and_propagation(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/cachey.py": """\
        import jax

        class Builder:
            @jax.jit
            def traced(self, x):
                self._cached = x          # line 6: instance store
                self._log.append(x)       # line 7: mutator on self
                return self._store(x)

            def _store(self, x):
                self._entries[0] = x      # line 11: traced via self-call
                return x

        @jax.jit
        def g(x):
            global _K
            _K = x                        # line 17: global store
            return x
        """})
    got = _lines(_findings(TracerLeakChecker(), repo))
    assert got == [("mxnet_tpu/cachey.py", n) for n in (6, 7, 11, 17)]


def test_tracer_leak_negative_locals_and_aliases(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/scratch.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fine(x):
            parts = []
            parts.append(x)            # local temp: trace scratch
            acc = {}
            acc["x"] = x               # local subscript
            y = jnp.append(x, x)       # module-alias call, not a mutator
            return y

        def eager(state):
            state.key = 1              # never traced: no jit reaches it
        """})
    assert _findings(TracerLeakChecker(), repo) == []


def test_tracer_leak_trace_pure_annotation_placements(tmp_path):
    """All three blessed placements: on the flagged line, in the comment
    block above a passed-by-name traced fn's def, and in the block above
    a decorated fn's decorators. An unannotated store still fires."""
    repo = _tree(tmp_path, {"mxnet_tpu/bless.py": """\
        import jax

        _CACHE = {}

        @jax.jit
        def inline(x):
            _CACHE["a"] = x  # mxlint: trace-pure — deliberate fill
            _CACHE["b"] = x              # line 8: NOT blessed
            return x

        # The builder populates its cache entry during the trace by
        # design. mxlint: trace-pure — trace-time bookkeeping.
        def blessed(x):
            _CACHE["c"] = x
            return x

        _exe = jax.jit(blessed)

        # mxlint: trace-pure — whole-body bookkeeping, above decorator
        @jax.jit
        def blessed_deco(x):
            _CACHE["d"] = x
            return x
        """})
    got = _lines(_findings(TracerLeakChecker(), repo))
    assert got == [("mxnet_tpu/bless.py", 8)]


def test_tracer_leak_pragma_suppression(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/prag.py": """\
        import jax

        _S = {}

        @jax.jit
        def f(x):
            _S["k"] = x  # mxlint: disable=tracer-leak
            return x
        """})
    kept, by_pragma, _ = run_checkers(repo, [TracerLeakChecker()])
    assert kept == [] and len(by_pragma) == 1


def test_trace_purity_positive(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/pure.py": """\
        import logging
        import os
        import time

        import jax

        from mxnet_tpu import env
        from mxnet_tpu.telemetry import metrics

        log = logging.getLogger(__name__)

        @jax.jit
        def step(params):
            flat = env.get("MXTPU_FLATTEN")        # line 14: config read
            raw = os.environ["MXTPU_RAW"]          # line 15: environ read
            t0 = time.monotonic()                  # line 16: clock
            metrics.counter("steps")               # line 17: telemetry
            log.info("tracing step")               # line 18: logging
            return params
        """})
    got = _lines(_findings(TracePurityChecker(), repo))
    assert got == [("mxnet_tpu/pure.py", n) for n in (14, 15, 16, 17, 18)]


def test_trace_purity_negative_shadow_and_jnp_log(tmp_path):
    """A LOCAL `env` dict is not the config registry (autograd's
    scalar_fn shape), `jnp.log` is not a logger, and untraced code may
    read whatever it wants."""
    repo = _tree(tmp_path, {"mxnet_tpu/pureok.py": """\
        import time

        import jax
        import jax.numpy as jnp

        from mxnet_tpu import env

        @jax.jit
        def scalar_fn(x):
            env = {"x": x}
            return env.get("x") + jnp.log(x)

        def eager():
            return env.get("MXTPU_FLATTEN"), time.time()
        """})
    assert _findings(TracePurityChecker(), repo) == []


def test_trace_purity_deliberate_specialization_annotated(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/spec.py": """\
        import jax

        from mxnet_tpu import env

        @jax.jit
        def step(x):
            # the mode deliberately specializes the executable; changing
            # it requires a rebuild. mxlint: trace-pure — deliberate.
            mode = env.get("MXTPU_FUSION_MODE")
            return x + 1 if mode else x
        """})
    assert _findings(TracePurityChecker(), repo) == []


def test_retrace_hazard_unrouted_jit_and_nonliteral_static(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/rh.py": """\
        import jax

        class Runner:
            def __init__(self, fwd, axes):
                self._exe = jax.jit(fwd)                   # line 5: unrouted
                self._axes = axes

            def call(self, fwd, axes):
                return jax.jit(fwd, static_argnums=axes)   # line 9: both
        """})
    got = _lines(_findings(RetraceHazardChecker(), repo))
    assert got.count(("mxnet_tpu/rh.py", 5)) == 1
    assert got.count(("mxnet_tpu/rh.py", 9)) == 2  # unrouted + non-literal


def test_retrace_hazard_routed_and_singletons_allowed(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/rhok.py": """\
        import jax

        def _fwd(x):
            return x

        _SINGLETON = jax.jit(_fwd)        # module level: traced per import

        _LAZY = None

        def barrier():
            global _LAZY
            if _LAZY is None:
                _LAZY = jax.jit(_fwd)     # global-declared lazy singleton
            return _LAZY

        class Engine:
            def _build(self, n):
                return jax.jit(_fwd, static_argnums=(0,))

            def step(self, registry, key, n):
                return registry.get_or_build(key, lambda: self._build(n))
        """})
    assert _findings(RetraceHazardChecker(), repo) == []


def test_retrace_hazard_trace_time_capture_and_branching(tmp_path):
    """R3/R4 inside a traced root: a value branch and a self.* data read
    fire; metadata branches (`.ndim`), `is None` guards on optional
    attrs, and a trace-pure-annotated capture stay quiet."""
    repo = _tree(tmp_path, {"mxnet_tpu/rh3.py": """\
        import jax
        import jax.numpy as jnp

        class Model:
            @jax.jit
            def fwd(self, data, layout=None):
                if data > 0:                      # line 7: value branch
                    data = data + self._bias      # line 8: self read
                if data.ndim == 3:                # metadata: static
                    data = data[0]
                if layout is None:                # optional attr: static
                    layout = "NCHW"
                # the head is a per-instance static by design
                # mxlint: trace-pure — baked head is deliberate
                return jnp.dot(data, self._head)
        """})
    got = _lines(_findings(RetraceHazardChecker(), repo))
    assert got == [("mxnet_tpu/rh3.py", 7), ("mxnet_tpu/rh3.py", 8)]


def test_donation_literal_and_signature_drift(tmp_path):
    repo = _tree(tmp_path, {"mxnet_tpu/don.py": """\
        import jax

        SPEC = (1,)

        def _step(params, state):
            return params, state

        def _vstep(*bufs):
            return bufs

        bad_spec = jax.jit(_step, donate_argnums=SPEC)       # line 11: D0
        bad_pos = jax.jit(_step, donate_argnums=(5,))        # line 12: D1
        ok = jax.jit(_step, donate_argnums=(1,))
        ok_vararg = jax.jit(_vstep, donate_argnums=(3,))
        """})
    got = _lines(_findings(DonationDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/don.py", 11), ("mxnet_tpu/don.py", 12)]


def test_donation_use_after_donate_fixture(tmp_path):
    """THE use-after-donate shape: a step executable donating params and
    optimizer state; the canonical re-store is safe, reading the donated
    binding afterwards is flagged."""
    repo = _tree(tmp_path, {"mxnet_tpu/uad.py": """\
        import jax

        from mxnet_tpu.compile import ExecutableKey

        class Trainer:
            def _build(self):
                def step(params, states, batch):
                    return params, states
                return jax.jit(step, donate_argnums=(0, 1))

            def train_step(self, batch):
                fn = self._resolve(
                    ExecutableKey("step", donation=(0, 1)),
                    lambda: self._build())
                self._params, new_states = fn(
                    self._params, self._states, batch)
                self._states = new_states
                return self._states

            def broken_step(self, batch):
                fn = self._resolve(
                    ExecutableKey("step2", donation=(0, 1)),
                    lambda: self._build())
                out = fn(self._params, self._states, batch)
                return self._states       # line 25: read-after-donate
        """})
    got = _lines(_findings(DonationDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/uad.py", 25)]


def test_donation_key_coverage_and_shape_b_invocation(tmp_path):
    """D3: a donating builder's ExecutableKey must declare a matching
    donation= (the fill-hook verifier's coverage contract); D2 shape B:
    `self._decode_exe(n)(...)` invocations of a method that returns the
    resolve call."""
    repo = _tree(tmp_path, {"mxnet_tpu/kv.py": """\
        import jax

        from mxnet_tpu.compile import ExecutableKey

        class Engine:
            def _build_decode(self, n):
                def step(params, pool, tok):
                    return tok, pool
                return jax.jit(step, donate_argnums=(1,))

            def _decode_exe(self, n):
                key = ExecutableKey("decode", bucket=n)     # 12: no donation=
                return self._resolve(key, lambda: self._build_decode(n))

            def _prefill_exe(self, n):
                key = ExecutableKey("prefill", bucket=n,
                                    donation=(2,))          # 17: mismatch
                return self._resolve(key, lambda: self._build_decode(n))

            def decode(self, tok):
                new_tok, pool = self._decode_exe(3)(
                    self._params, self._pool, tok)
                self._pool = pool               # re-stored first: safe
                return new_tok

            def peek(self, tok):
                out = self._decode_exe(3)(self._params, self._pool, tok)
                return self._pool.mean()        # line 28: read-after-donate
        """})
    got = _lines(_findings(DonationDisciplineChecker(), repo))
    assert got == [("mxnet_tpu/kv.py", n) for n in (12, 17, 28)]


def test_trace_discipline_real_tree_clean():
    """The live tree is clean under all four trace-discipline rules —
    the triage acceptance criterion: every real finding fixed (the
    serving KV-pool key now declares donation=), deliberate trace-time
    effects trace-pure-annotated, the one one-shot export trace
    pragma'd, nothing baselined."""
    repo = Repo(ROOT)
    assert _lines(_findings(TracerLeakChecker(), repo)) == []
    assert _lines(_findings(TracePurityChecker(), repo)) == []
    assert _lines(_findings(DonationDisciplineChecker(), repo)) == []
    kept, by_pragma, _ = run_checkers(repo, [RetraceHazardChecker()])
    assert _lines(kept) == []
    assert len(by_pragma) == 1  # predict.py's one-shot export trace


def test_trace_pure_real_tree_annotations_load_bearing():
    """The committed trace-pure annotations are LOAD-BEARING: stripping
    them from gluon/block.py re-surfaces tracer-leak findings (an
    annotation on dead code would rot silently)."""
    import ast as _ast
    import re

    repo = Repo(ROOT)
    rel = "mxnet_tpu/gluon/block.py"
    src = repo.read(rel)
    assert "mxlint: trace-pure" in src
    stripped = re.sub(r"mxlint: trace-pure[^\n]*", "", src)
    repo._cache[rel] = (_ast.parse(stripped, filename=rel),
                        stripped.splitlines())
    got = [f for f in TracerLeakChecker().run(repo) if f.path == rel]
    assert got, "stripping block.py annotations surfaces nothing — the " \
        "checker no longer sees the cache-entry fills"


# ---------------------------------------------------------------------------
# runner: --format json and --changed-only
# ---------------------------------------------------------------------------

_LEAKY = """\
    import jax

    _S = {}

    @jax.jit
    def f(x):
        _S["k"] = x
        return x
"""


def test_cli_json_format(tmp_path):
    _tree(tmp_path, {"mxnet_tpu/leak.py": _LEAKY})
    cmd = [sys.executable, "-m", "ci.mxlint", "--root", str(tmp_path),
           "--rule", "tracer-leak", "--format", "json"]
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["rules"] == 1
    assert [(f["rule"], f["path"], f["line"]) for f in payload["findings"]] \
        == [("tracer-leak", "mxnet_tpu/leak.py", 7)]
    assert payload["pragma_suppressed"] == 0
    (tmp_path / "mxnet_tpu" / "leak.py").write_text("def f(x):\n"
                                                    "    return x\n")
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert json.loads(r.stdout)["findings"] == []


def test_changed_only_scoping_and_degrade(tmp_path):
    """Repo.scoped_files honors the changed set for per-file rules while
    py_files (whole-repo parity rules) still sees everything; outside a
    git checkout changed_files() degrades to 'no restriction'."""
    from ci.mxlint import changed_files

    repo = _tree(tmp_path, {"mxnet_tpu/a.py": "A = 1\n",
                            "mxnet_tpu/b.py": "B = 1\n"})
    assert repo.scoped_files("mxnet_tpu") == ["mxnet_tpu/a.py",
                                              "mxnet_tpu/b.py"]
    scoped = Repo(str(tmp_path), changed=frozenset({"mxnet_tpu/b.py"}))
    assert scoped.scoped_files("mxnet_tpu") == ["mxnet_tpu/b.py"]
    assert scoped.py_files("mxnet_tpu") == ["mxnet_tpu/a.py",
                                            "mxnet_tpu/b.py"]
    assert changed_files(str(tmp_path)) is None  # not a checkout


def test_cli_changed_only_end_to_end(tmp_path):
    """--changed-only catches a violation introduced in the working tree
    (here: an untracked file) after a clean pass on the committed seed."""
    _tree(tmp_path, {"mxnet_tpu/clean.py": "X = 1\n"})

    def git(*a):
        return subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t"] + list(a),
            cwd=str(tmp_path), capture_output=True, text=True, timeout=60)

    assert git("init", "-q").returncode == 0
    git("add", "-A")
    assert git("commit", "-q", "-m", "seed").returncode == 0
    cmd = [sys.executable, "-m", "ci.mxlint", "--root", str(tmp_path),
           "--rule", "tracer-leak", "--changed-only"]
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    (tmp_path / "mxnet_tpu" / "leak.py").write_text(
        textwrap.dedent(_LEAKY))
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       timeout=240)
    assert r.returncode == 1 and "leak.py:7" in r.stdout, \
        r.stdout + r.stderr


def test_env_registry_covers_every_checker_rule():
    """Meta: the shipped checker set is exactly the documented
    fourteen."""
    assert sorted(c.rule for c in CHECKERS) == [
        "bare-print", "compile-registry", "donation-discipline",
        "env-registry", "host-sync", "lock-discipline", "lock-order",
        "metric-registry", "registry-parity", "retrace-hazard",
        "signal-safety", "thread-hygiene", "trace-purity", "tracer-leak"]
