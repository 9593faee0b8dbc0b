"""Runtime feature introspection + the per-chip peak table.

TPU-native equivalent of the reference's `python/mxnet/runtime.py` +
`src/libinfo.cc` (build-feature flags queryable at runtime: `Features()`,
`feature_list()`, `is_enabled` — reference runtime.py:28). Features here
describe the JAX/XLA backend actually present in the process instead of
compile-time `USE_*` flags.
"""
from __future__ import annotations

import collections

from .base import MXNetError

__all__ = ["Feature", "Features", "feature_list",
           "PEAK_BF16_TFLOPS", "chip_peak_tflops"]

Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _detect():
    feats = {}

    def add(name, fn):
        try:
            feats[name] = bool(fn())
        except Exception:
            feats[name] = False

    import jax

    platforms = {d.platform for d in jax.devices()}
    add("TPU", lambda: "tpu" in platforms)
    add("GPU", lambda: "gpu" in platforms or "cuda" in platforms)
    add("CPU", lambda: True)
    add("F16C", lambda: True)          # fp16 compute available through XLA
    add("BF16", lambda: True)          # native MXU dtype
    add("INT8", lambda: True)          # int8 dot via XLA (quantization path)
    add("PALLAS", _pallas_available)
    add("DIST_KVSTORE", lambda: True)  # collectives-backed kvstore
    add("OPENCV", _cv_available)       # image decode path
    add("NATIVE_IO", _native_io_available)  # C++ recordio/pipeline library
    add("SIGNAL_HANDLER", lambda: True)
    add("PROFILER", lambda: True)
    return feats


def _pallas_available():
    from jax.experimental import pallas  # noqa: F401

    return True


def _cv_available():
    try:
        import cv2  # noqa: F401

        return True
    except ImportError:
        from PIL import Image  # noqa: F401

        return True


def _native_io_available():
    from .lib import native

    return native.available()


class Features(collections.OrderedDict):
    """Mapping name -> Feature (reference: runtime.py:45 class Features)."""

    instance = None

    def __init__(self):
        super().__init__(
            (name, Feature(name, enabled)) for name, enabled in _detect().items())

    def __repr__(self):
        return "[%s]" % ", ".join(
            "%s%s" % ("✔ " if f.enabled else "✖ ", f.name) for f in self.values())

    def is_enabled(self, feature_name):
        """reference: runtime.py:78 Features.is_enabled."""
        feature_name = feature_name.upper()
        if feature_name not in self:
            raise RuntimeError("feature '%s' is unknown" % feature_name)
        return self[feature_name].enabled


def feature_list():
    """List of Feature tuples (reference: runtime.py:95 feature_list)."""
    return list(Features().values())


# ---------------------------------------------------------------------------
# chip peak FLOPs table (telemetry MFU, chip_smoke.py)
# ---------------------------------------------------------------------------

# Peak dense-matmul TFLOP/s per chip in bf16, keyed by the `device_kind`
# jax reports (fp32 runs the MXU in multi-pass mode at a fraction of the
# bf16 peak; callers report fp32 MFU against the bf16 peak so numbers stay
# conservative and comparable). Each row cites where its figure is
# published; a kind that is not here is an error, never a default.
PEAK_BF16_TFLOPS = {
    # Google Cloud docs, "TPU v2" system architecture: 45 TFLOP/s per chip
    # (180 per 4-chip board); jax reports per-core devices on v2/v3
    "TPU v2": 45.0,
    # Google Cloud docs, "TPU v3": 123 TFLOP/s per chip
    "TPU v3": 123.0,
    # Google Cloud docs, "TPU v4": 275 TFLOP/s per chip
    "TPU v4": 275.0,
    # Google Cloud docs, "TPU v5e": 197 TFLOP/s bf16 per chip (393 int8,
    # 16 GB HBM at 819 GB/s); jax reports it as "TPU v5 lite"
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    # Google Cloud docs, "TPU v5p": 459 TFLOP/s per chip
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    # Google Cloud docs, "TPU v6e" (Trillium): 918 TFLOP/s per chip
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
    # Google Cloud docs, "TPU7x" (Ironwood): 2307 TFLOP/s bf16 per chip
    # (4614 is the fp8 figure)
    "TPU7x": 2307.0,
}


def chip_peak_tflops(device):
    """Peak bf16 TFLOP/s for a jax device. Raises `MXNetError` for a
    `device_kind` the table does not list: every caller divides by the
    result, and a silent None made MFU vanish downstream."""
    kind = getattr(device, "device_kind", "") or ""
    # longest table key first so "TPU v5 lite" wins over "TPU v5"
    for name, peak in sorted(PEAK_BF16_TFLOPS.items(),
                             key=lambda kv: -len(kv[0])):
        if kind.lower().startswith(name.lower()):
            return peak
    raise MXNetError(
        "no bf16 peak for device_kind %r in runtime.PEAK_BF16_TFLOPS "
        "(known: %s) — add the chip's published figure with its source"
        % (kind, ", ".join(sorted(PEAK_BF16_TFLOPS))))
