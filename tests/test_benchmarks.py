"""Run every benchmark/python harness as a subprocess at a tiny smoke
config (reference: benchmark/python/{gluon,sparse,control_flow,
quantization} — SURVEY §6's in-tree harnesses). Each must emit at least
one parseable JSON result line."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HARNESSES = [
    ("gluon/benchmark_gluon.py",
     ["--models", "resnet18_v1", "--batch-sizes", "2",
      "--image-size", "64", "--iters", "2", "--warmup", "1"]),
    ("sparse/sparse_op.py",
     ["--rows", "512", "--cols", "256", "--out-cols", "64",
      "--densities", "0.05", "--iters", "2", "--warmup", "1"]),
    ("control_flow/rnn.py",
     ["--seq-lens", "8", "--batch-sizes", "2", "--iters", "2",
      "--warmup", "1"]),
    ("quantization/benchmark_op.py",
     ["--configs", "2x8x16x16x8", "--iters", "2", "--warmup", "1"]),
]


@pytest.mark.parametrize("script,args", HARNESSES,
                         ids=[s for s, _ in HARNESSES])
def test_benchmark_harness(script, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmark", "python", script)] + args,
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("{")]
    assert lines, res.stdout[-2000:]
    for ln in lines:
        rec = json.loads(ln)
        assert "error" not in rec, rec

