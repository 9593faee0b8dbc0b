"""mxnet_tpu: a TPU-native deep-learning framework with the capabilities of
Apache MXNet (incubating).

This is a ground-up rebuild of the reference (/root/reference, MXNet ~1.4)
for TPU hardware: the compute path is JAX/XLA (+Pallas kernels), the
execution model is compiled-graph-first (jit/pjit over a device Mesh), and
the distributed layer is XLA collectives over ICI/DCN instead of
ps-lite/NCCL. See SURVEY.md at the repo root for the full component mapping.

Public surface mirrors `import mxnet as mx`:
    mx.nd, mx.sym, mx.gluon, mx.autograd, mx.optimizer, mx.metric, mx.io,
    mx.kv/kvstore, mx.context/cpu/gpu/tpu, mx.init(ializer), mx.mod(ule),
    mx.random, mx.profiler, mx.lr_scheduler, mx.callback, mx.test_utils
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()    # the start-up account's first stamp

__version__ = "0.1.0"

from . import env
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus
from . import engine
from . import random
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import autograd

# subsystem imports are appended as the build widens (round-1 scaffold keeps
# this list in sync with the modules that exist)
_SUBMODULES = [
    "telemetry",
    "optimizer", "initializer", "lr_scheduler", "metric", "symbol", "executor",
    "module", "io", "data", "recordio", "image", "kvstore", "gluon", "callback",
    "model", "profiler", "runtime", "test_utils", "visualization", "monitor",
    "parallel", "attribute", "name", "operator", "contrib", "rtc",
    "torch_bridge", "registry", "log", "libinfo", "util",
    "kvstore_server", "executor_manager", "rnn", "serving",
    # legacy-name shims (reference top-level module map)
    "misc", "ndarray_doc", "symbol_doc", "torch",
]
import importlib as _importlib
import os as _os

for _m in _SUBMODULES:
    if _os.path.exists(_os.path.join(_os.path.dirname(__file__), _m + ".py")) or \
       _os.path.isdir(_os.path.join(_os.path.dirname(__file__), _m)):
        globals()[_m] = _importlib.import_module("." + _m, __name__)

# reference __init__.py aliases `torch` as `th` too
if "torch" in globals():
    th = globals()["torch"]

if "kvstore_server" in globals() and _os.environ.get("DMLC_ROLE") in (
        "server", "scheduler"):
    # reference parity: mxnet/__init__ runs the PS server loop for
    # server-role processes; ours logs the collectives architecture note
    # and exits so reference launch scripts keep a correct worker count
    kvstore_server._maybe_exit_non_worker()  # noqa: F821

# telemetry-configured processes (MXTPU_TELEMETRY_DIR set — launched jobs)
# get the SIGUSR1 flight-recorder dump handler from import time, so even a
# hang BEFORE the first training step (rendezvous, compile) is diagnosable
# via the launcher's SIGUSR1-then-SIGTERM teardown
if "telemetry" in globals() and env.is_set("MXTPU_TELEMETRY_DIR"):
    telemetry.install_signal_handler()  # noqa: F821

if "symbol" in globals():
    sym = symbol  # noqa: F821
    Symbol = symbol.Symbol  # noqa: F821
if "module" in globals():
    mod = module  # noqa: F821
if "kvstore" in globals():
    kv = kvstore  # noqa: F821
if "initializer" in globals():
    init = initializer  # noqa: F821
if "visualization" in globals():
    viz = visualization  # noqa: F821
if "attribute" in globals():
    AttrScope = attribute.AttrScope  # noqa: F821

# the start-up account's first span, from this file's first line to its last
# (docs/observability.md §Start-up)
if "telemetry" in globals():
    with telemetry.goodput.span("import", t0=_T_IMPORT):  # noqa: F821
        pass
