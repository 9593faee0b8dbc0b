"""Persistent executable artifacts: the on-disk tier of the compile cache.

Each artifact is ONE compiled XLA executable, serialized with
`jax.experimental.serialize_executable` and written crash-consistently
(`base.atomic_writer` — same-directory temp + fsync + one atomic rename,
the `CheckpointManager` discipline), so a reader only ever sees a complete
previous file or a complete new file. Layout under the cache directory
(``MXTPU_COMPILE_CACHE``):

    <dir>/objects/<digest>.mxe      one executable per file
    <dir>/manifests/<model>.json    warmup manifests (see manifest.py)

Artifact format (``MXTPUEXE1``): magic, 8-byte little-endian header
length, a JSON header (format version, the canonical key JSON, label, jax
version, backend, FLOPs-per-execution from compile-time cost analysis,
payload length + crc32), then the pickled ``(payload, in_tree, out_tree)``
triple from ``serialize_executable.serialize``.

Every read re-verifies magic, format, jax version, backend and the
payload crc; ANY mismatch or decode error is a miss, never a fatal error
— a corrupt/truncated/stale artifact costs one recompile, nothing else.

Trust model: loading an artifact unpickles it, so the cache directory
must be exactly as trusted as a checkpoint directory or jax's own
persistent compilation cache — writable only by the deployment. The
serving wire protocol's pickle paranoia (supervisor.py) does NOT apply
here: these are local files under an operator-chosen path, not a socket
any local user can dial.
"""
from __future__ import annotations

import json
import logging
import os
import pickle
import time
import zlib

from .. import env as _env
from ..base import atomic_writer
from ..telemetry import recorder as _tm_rec

_LOG = logging.getLogger("mxnet_tpu.compile")

__all__ = ["cache_dir", "artifact_path", "store", "load", "scan",
           "read_header", "prune", "MAGIC", "FORMAT"]

MAGIC = b"MXTPUEXE1\n"
FORMAT = 1
_FALSY = ("0", "off", "none", "disable", "false", "no")


def cache_dir(create=False):
    """The persistent tier's directory from ``MXTPU_COMPILE_CACHE``
    (``1``/``on`` -> the repo-local ``.mxtpu_compile_cache`` default), or
    None when the tier is disabled. Read per call, so arming the cache
    after import just works."""
    choice = _env.raw("MXTPU_COMPILE_CACHE") or ""
    if not choice or choice.lower() in _FALSY:
        return None
    if choice.lower() in ("1", "on", "true", "yes"):
        d = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".mxtpu_compile_cache")
    else:
        d = choice
    if create:
        os.makedirs(os.path.join(d, "objects"), exist_ok=True)
    return d


def artifact_path(directory, digest):
    return os.path.join(directory, "objects", digest + ".mxe")


def _backend():
    import jax

    return jax.default_backend()


def _jax_version():
    import jax

    return jax.__version__


def store(directory, key, compiled, label=None, flops=None, memory=None):
    """Serialize ``compiled`` (a jax Compiled) under ``key``; returns the
    digest, or None when this executable/backend cannot serialize (a
    cache store is always best-effort). ``memory`` is the compile-time
    `memory_analysis()` figures dict (argument/output/temp/generated-
    code/alias bytes) — persisted in the header so a zero-compile cold
    start still knows the executable's footprint
    (docs/compile_cache.md)."""
    from jax.experimental import serialize_executable as _se

    backend, jaxver = _backend(), _jax_version()
    digest = key.digest(backend, jaxver)
    try:
        payload = pickle.dumps(_se.serialize(compiled),
                               protocol=pickle.HIGHEST_PROTOCOL)
    except Exception as e:  # noqa: BLE001 — whatever the backend raises
        # not stored, and said so: a tier that cannot serialise this
        # backend's executables must not look like one that merely missed
        _LOG.warning("compile cache: %s executable %r cannot be "
                     "serialized, not stored: %r", backend, label, e)
        _tm_rec.record_event("compile_persist_unserializable", op=label,
                             backend=backend, error=repr(e)[:300])
        return None
    header = json.dumps({
        "format": FORMAT,
        "digest": digest,
        # the devices the executable runs on, in its own order: jax 0.9
        # loads a serialized executable onto EVERY device of the backend
        # unless told, which a one-device program in an eight-device
        # process (or on a four-chip host) then refuses to run on
        "devices": [d.id for d in
                    compiled.runtime_executable().local_devices()],
        "key": key.to_json(),
        "label": label,
        "jax": jaxver,
        "backend": backend,
        "flops": flops,
        "memory": memory,
        "created": time.time(),
        "payload_len": len(payload),
        "payload_crc32": zlib.crc32(payload) & 0xFFFFFFFF,
    }, sort_keys=True).encode()
    path = artifact_path(directory, digest)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        with atomic_writer(path, "wb") as f:
            f.write(MAGIC)
            f.write(len(header).to_bytes(8, "little"))
            f.write(header)
            f.write(payload)
    except OSError:
        return None  # full/read-only cache disk never breaks compilation
    return digest


def _read(path, want_payload):
    """(header, payload|None) for a verified artifact, or (None, None) on
    ANY problem — corrupt, truncated, foreign, stale-versioned."""
    try:
        with open(path, "rb") as f:
            if f.read(len(MAGIC)) != MAGIC:
                return None, None
            hlen = int.from_bytes(f.read(8), "little")
            if not 0 < hlen < (1 << 24):
                return None, None
            header = json.loads(f.read(hlen).decode())
            if header.get("format") != FORMAT:
                return None, None
            if not want_payload:
                return header, None
            payload = f.read()
    except (OSError, ValueError, UnicodeDecodeError):
        return None, None
    if len(payload) != header.get("payload_len") or \
            (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("payload_crc32"):
        return None, None
    return header, payload


def read_header(path):
    """Verified header of one artifact file (no payload/crc check), or
    None. The CLI's list/inspect read."""
    return _read(path, want_payload=False)[0]


def load(directory, key):
    """Deserialize the executable stored under ``key``. Returns
    ``(callable, flops, memory)`` or ``(None, None, None)`` on miss/
    corruption/version skew — loading NEVER raises."""
    path = artifact_path(directory, key.digest(_backend(), _jax_version()))
    return load_path(path)


def load_path(path):
    """`load` by explicit artifact path (manifest prefetch)."""
    header, payload = _read(path, want_payload=True)
    if header is None:
        return None, None, None
    # version/backend double-check: the digest already encodes both, but a
    # renamed/copied file must not smuggle a foreign executable in
    if header.get("jax") != _jax_version() or \
            header.get("backend") != _backend():
        return None, None, None
    try:
        import jax
        from jax.experimental import serialize_executable as _se

        by_id = {d.id: d for d in jax.devices()}
        payload_bytes, in_tree, out_tree = pickle.loads(payload)
        fn = _se.deserialize_and_load(
            payload_bytes, in_tree, out_tree,
            execution_devices=[by_id[i] for i in header["devices"]])
    except Exception:
        return None, None, None
    return fn, header.get("flops"), header.get("memory")


def scan(directory):
    """Yield ``(path, header_or_None)`` for every ``*.mxe`` object file
    (header None = unreadable/corrupt/foreign — prune targets)."""
    objects = os.path.join(directory, "objects")
    try:
        names = sorted(os.listdir(objects))
    except OSError:
        return
    for name in names:
        if not name.endswith(".mxe"):
            continue
        path = os.path.join(objects, name)
        yield path, read_header(path)


def prune(directory, older_than_s=None, bad_only=False, jax_mismatch=False):
    """Delete artifacts: all (default), only unreadable/corrupt ones
    (``bad_only``), only other-jax/backend ones (``jax_mismatch``), or
    those older than ``older_than_s`` seconds. Returns paths removed."""
    now = time.time()
    removed = []
    for path, header in scan(directory):
        if bad_only:
            drop = header is None
        elif jax_mismatch:
            drop = header is not None and (
                header.get("jax") != _jax_version()
                or header.get("backend") != _backend())
        elif older_than_s is not None:
            created = (header or {}).get("created") or 0
            drop = (now - created) > older_than_s
        else:
            drop = True
        if drop:
            try:
                os.unlink(path)
                removed.append(path)
            except OSError:
                pass
    return removed
