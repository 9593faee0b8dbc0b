"""Base utilities: errors, registries, dtype handling.

TPU-native rebuild of the reference's `python/mxnet/base.py` role (ctypes
plumbing, error translation — reference: python/mxnet/base.py). Here there is
no C ABI to cross for the frontend: the "backend" is JAX/XLA, so this module
only carries the shared error type, the string/dtype conversion helpers, and
the small registry machinery the op/optimizer/metric/initializer registries use
(reference: python/mxnet/registry.py).
"""
from __future__ import annotations

import os
import tempfile

import numpy as _np

__all__ = ["MXNetError", "string_types", "numeric_types", "integer_types",
           "atomic_writer", "unpad_outputs"]

# Host-array mode: when True, host-side pipeline stages (image decode,
# dataset __getitem__) hand back plain numpy instead of NDArray. Set in
# DataLoader worker processes, where touching the (forked) jax runtime
# deadlocks and the accelerator belongs to the parent. See
# gluon/data/dataloader.py.
HOST_ARRAY_MODE = False


def enable_persistent_compile_cache():
    """Arm jax's own persistent compilation cache (executables keyed by
    HLO + backend, so a repeated run skips XLA backend compilation; each
    process still pays trace + lower) and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and no
    directory is set in code; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    must never move between runs. Called by the entry points that run on
    an accelerator (chip_smoke.py), not at package import: the
    CPU test suite stays uncached (XLA:CPU AOT reloads warn about
    machine-feature mismatches and save little). This sits UNDER the
    framework's own executable-artifact tier (``MXTPU_COMPILE_CACHE`` →
    `mxnet_tpu.compile`, docs/compile_cache.md), which skips trace, lower
    AND compile; the two compose."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


class MXNetError(RuntimeError):
    """Error raised by the framework (reference: python/mxnet/base.py:49)."""


class atomic_writer:
    """Crash-consistent file write: ``with atomic_writer(path, 'wb') as f``
    writes to a same-directory temp file, fsyncs it, and atomically renames
    onto `path` only if the block completed — a process killed mid-write can
    leave a stale temp file but never a truncated `path`. Readers therefore
    always see either the previous complete file or the new complete file
    (the reference's single-file NDArray::Save had no such guarantee; a kill
    mid-save corrupted the checkpoint). The rename is same-filesystem by
    construction (temp lives next to the target)."""

    def __init__(self, path, mode="wb"):
        self._path = os.fspath(path)
        self._mode = mode
        self._tmp = None
        self._f = None

    def __enter__(self):
        d = os.path.dirname(os.path.abspath(self._path)) or "."
        fd, self._tmp = tempfile.mkstemp(
            dir=d, prefix=os.path.basename(self._path) + ".tmp-")
        # mkstemp creates 0600; the rename would stamp that onto the target.
        # Preserve an existing target's mode, else honor the umask like a
        # plain open() would — shared-directory checkpoints must stay
        # readable by their consumers (eval/monitoring processes).
        try:
            mode = os.stat(self._path).st_mode & 0o7777
        except OSError:
            umask = os.umask(0)
            os.umask(umask)
            mode = 0o666 & ~umask
        try:
            os.fchmod(fd, mode)
        except OSError:
            pass
        self._f = os.fdopen(fd, self._mode)
        return self._f

    def __exit__(self, exc_type, exc, tb):
        try:
            try:
                if exc_type is None:
                    self._f.flush()
                    os.fsync(self._f.fileno())
            finally:
                # close unconditionally — a flush/fsync failure (ENOSPC)
                # must not leak the temp fd on every retried checkpoint
                self._f.close()
            if exc_type is None:
                os.replace(self._tmp, self._path)
                self._tmp = None
                _fsync_dir(os.path.dirname(os.path.abspath(self._path)) or ".")
        finally:
            if self._tmp is not None and os.path.exists(self._tmp):
                os.unlink(self._tmp)
        return False


def _fsync_dir(path):
    """Persist a rename by fsyncing the containing directory (POSIX: the
    rename itself is atomic but only durable once the dir entry is synced).
    Best-effort — some filesystems refuse O_RDONLY dir fsync."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def unpad_outputs(outputs, pad, copy=False):
    """Drop the trailing ``pad`` rows from every array in ``outputs``.

    The shared unpad for every padded-batch consumer: a DataIter's last
    batch carries ``pad`` filler rows (module predict/iter_predict), and the
    serving micro-batcher pads coalesced batches up to a power-of-two bucket
    (serving/batcher.py). Works on anything row-sliceable (NDArray, numpy).
    ``copy=True`` detaches each slice from the padded buffer (callers that
    retain results past the next forward need it).
    """
    out = []
    for o in outputs:
        s = o[0:o.shape[0] - pad] if pad else o
        out.append(s.copy() if copy else s)
    return out


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# dtype name <-> numpy dtype mapping (reference keeps int codes in
# python/mxnet/base.py via _DTYPE_NP_TO_MX; we key on names since XLA is typed)
_DTYPE_ALIASES = {
    "float32": _np.float32,
    "float64": _np.float64,
    "float16": _np.float16,
    "bfloat16": "bfloat16",  # resolved lazily via ml_dtypes through jax.numpy
    "uint8": _np.uint8,
    "int8": _np.int8,
    "int32": _np.int32,
    "int64": _np.int64,
    "bool": _np.bool_,
}


def np_dtype(dtype):
    """Normalize a user-provided dtype (string/np.dtype/jnp dtype) to numpy dtype."""
    import jax.numpy as jnp

    if dtype is None:
        return _np.dtype(_np.float32)
    if isinstance(dtype, str) and dtype == "bfloat16":
        return _np.dtype(jnp.bfloat16)
    return _np.dtype(dtype)


_ALL_REGISTRIES = {}


class _Registry:
    """Simple name->object registry with alias support
    (reference: python/mxnet/registry.py:30 `get_register_func`)."""

    def __init__(self, kind):
        self.kind = kind
        self._map = {}
        # kind-keyed directory so mx.registry's functional surface
        # (registry.py) resolves onto the SAME storage as the subsystem
        # registries (optimizer/metric/initializer) — first instance wins
        _ALL_REGISTRIES.setdefault(kind, self)

    def register(self, obj, name=None, aliases=()):
        key = (name or getattr(obj, "__name__", str(obj))).lower()
        self._map[key] = obj
        for a in aliases:
            self._map[a.lower()] = obj
        return obj

    def get(self, name):
        key = name.lower()
        if key not in self._map:
            raise MXNetError(
                "Cannot find %s '%s'. Valid: %s"
                % (self.kind, name, sorted(self._map))
            )
        return self._map[key]

    def create(self, name, *args, **kwargs):
        return self.get(name)(*args, **kwargs)

    def __contains__(self, name):
        return name.lower() in self._map

    def keys(self):
        return list(self._map)

def device_int_dtype():
    """The documented int64 policy (README "int64") in one place: device
    index/shape integers are int32 (XLA-native) under the default config,
    int64 when large-tensor mode has scoped x64 live
    (ndarray._x64_if_large)."""
    import jax
    import jax.numpy as jnp

    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
