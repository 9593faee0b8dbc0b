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


BENCH_MODES = ["train", "score", "score_int8", "bert", "lstm",
               "train_sharded", "goodput", "train_input"]


def _assert_no_chip_line(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, "stdout must be ONE JSON line, got %r" % lines
    out = json.loads(lines[0])
    for field in ("metric", "value", "unit", "vs_baseline", "error"):
        assert field in out, field
    assert out["value"] is None and "no accelerator" in out["error"]
    assert not any(k.startswith("stale") for k in out), out
    return out


def test_bench_without_chip_reports_no_value():
    """bench.py measures on the chip and nowhere else: on a CPU-only box it
    prints exactly ONE JSON line with the driver's fields, a null value and
    the reason — no stale number echoed from an old capture — and exits
    non-zero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               MXTPU_BENCH_MODE="train", MXTPU_BENCH_BATCH="2",
               MXTPU_BENCH_NET="resnet50",  # pin: ambient env must not leak
               MXTPU_BENCH_LAYOUT="NCHW")
    res = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0, res.stdout[-2000:]
    out = _assert_no_chip_line(res.stdout)
    assert out["metric"] == "resnet50_train_bs2_imgs_per_sec"


@pytest.mark.parametrize("mode", BENCH_MODES)
def test_bench_refuses_every_mode_without_chip(mode, monkeypatch, capsys):
    """The same refusal for every mode, under the mode's own metric name
    (in-process: the subprocess case above covers the command line)."""
    monkeypatch.syspath_prepend(REPO)
    import bench

    monkeypatch.setattr(bench, "MODE", mode)
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code != 0
    out = _assert_no_chip_line(capsys.readouterr().out)
    assert ("train" if mode in ("goodput", "train_input") else
            mode.split("_")[0]) in out["metric"]


def test_bench_train_mfu_segments(monkeypatch):
    """The train mode's self-diagnosis: the segment harness fills the fwd /
    fwd+bwd / matmul-ceiling decomposition fields next to the headline
    MFU. Driven in-process on a two-layer net (bench.py itself refuses to
    run without a chip); the device only names the peak to divide by."""
    import types

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("MXTPU_BENCH_SEG_MM_N", "128")
    monkeypatch.delenv("MXTPU_BENCH_SEGMENTS", raising=False)
    import bench

    ctx = mx.cpu()
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1), gluon.nn.Activation("relu"),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
            gluon.nn.Dense(4))
    net.initialize(ctx=ctx)
    x = mx.nd.ones((2, 3, 16, 16), ctx=ctx)
    net(x)
    out = {}
    bench._mfu_segments(
        out, types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite"),
        net, ctx, x, fwd_flops_per_img=1e6, iters=2)
    assert "seg_error" not in out, out["seg_error"]
    for field in ("seg_matmul_tflops", "seg_fwd_ms", "seg_fwd_dgrad_ms",
                  "seg_fwd_mfu"):
        assert out.get(field, 0) > 0, (field, out)
