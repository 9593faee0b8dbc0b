#!/usr/bin/env python
"""Environment diagnostic (reference: tools/diagnose.py — platform/python/
dependency report for bug filing). TPU-native version adds the accelerator
check, probed in a subprocess: the chip belongs to one process at a time,
and this report must neither claim it nor fail because another process
holds it."""
from __future__ import annotations

import os
import platform
import subprocess
import sys
import time


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("cores        :", os.cpu_count())


def check_deps():
    print("----------Dependencies---------")
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax", "orbax",
                "torch", "PIL"):
        try:
            m = __import__(mod)
            print("%-12s : %s" % (mod, getattr(m, "__version__", "present")))
        except Exception as e:
            print("%-12s : MISSING (%s)" % (mod, e))


def check_mxnet_tpu(timeout=120):
    """Probed in a CPU-pinned subprocess: feature detection runs jax
    computations, and in-process they would claim the chip."""
    print("----------mxnet_tpu------------")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    code = (
        "import os, mxnet_tpu as mx\n"
        "print('ok', os.path.dirname(mx.__file__))\n"
        "from mxnet_tpu.runtime import feature_list\n"
        "print(', '.join('%s=%d' % (f.name, f.enabled)"
        " for f in feature_list()))\n")
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             timeout=timeout, env=env)
        lines = out.stdout.strip().splitlines()
        if out.returncode == 0 and len(lines) >= 2:
            print("import       :", lines[0])
            print("features     :", lines[1])
        else:
            print("import       : FAILED rc=%d  %s" % (
                out.returncode, out.stderr.strip()[-300:]))
    except subprocess.TimeoutExpired:
        print("import       : TIMED OUT (>%ds)" % timeout)


def check_accelerator(timeout=60):
    """Probe jax.devices() in a subprocess (see the module docstring)."""
    print("----------Accelerator----------")
    t0 = time.time()
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices()[0]; "
             "print(d.platform, '|', d.device_kind, '|', len(jax.devices()))"],
            capture_output=True, text=True, timeout=timeout)
        dt = time.time() - t0
        if out.returncode == 0 and out.stdout.strip():
            print("devices      : %s  (%.1fs)" % (
                out.stdout.strip().splitlines()[-1], dt))
        else:
            print("devices      : FAILED rc=%d  %s" % (
                out.returncode, out.stderr.strip()[-200:]))
    except subprocess.TimeoutExpired:
        print("devices      : no answer within %ds" % timeout)


def main():
    check_python()
    check_os()
    check_deps()
    check_mxnet_tpu()
    check_accelerator()


if __name__ == "__main__":
    main()
