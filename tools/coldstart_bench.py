"""Cold-start bench: serving replica time-to-ready, cold vs warm
persistent compile cache (docs/compile_cache.md).

Exports a model, then spawns a 1-replica pool TWICE against the same
`MXTPU_COMPILE_CACHE` directory:

  * run 1 (**cold**): empty cache — every bucket executable is traced
    and compiled; the warm writes the artifacts + the warmup manifest;
  * run 2 (**warm**): a fresh worker process prefetches the manifest and
    deserializes every executable — the acceptance contract is ZERO
    ``jit_compile`` events in its telemetry and a measurably lower
    time-to-ready.

Each run's worker telemetry JSONL is read back for the jit_compile /
compile_persist_hit counts; the JSON row lands on stdout
(`bench_capture.sh` archives it as ``BENCH_<tag>_coldstart.json``).

Usage: python tools/coldstart_bench.py [--net resnet18|mlp]
       [--image-size 32] [--max-batch 8] [--cache-dir DIR]
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def log(msg):
    sys.stderr.write("[coldstart_bench] %s\n" % msg)
    sys.stderr.flush()


def _jsonl_events(tdir):
    counts = {}
    for name in sorted(os.listdir(tdir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(tdir, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") == "event":
                    ev = rec.get("event")
                    counts[ev] = counts.get(ev, 0) + 1
    return counts


def _spawn_run(tag, prefix, input_shapes, max_batch, cache_dir, workdir,
               timeout_s):
    from mxnet_tpu.serving.model_repository import ServedModel
    from mxnet_tpu.telemetry import memory as _tm_memory

    import numpy as np

    tdir = os.path.join(workdir, "telemetry_" + tag)
    os.makedirs(tdir, exist_ok=True)
    t0 = time.monotonic()
    model = ServedModel.pooled(
        "coldstart", 1, prefix, replicas=1, input_shapes=input_shapes,
        max_batch=max_batch,
        extra_env={"MXTPU_COMPILE_CACHE": cache_dir,
                   "MXTPU_TELEMETRY_DIR": tdir},
        spawn_timeout_s=timeout_s)
    ready_s = time.monotonic() - t0
    try:
        shape = (2,) + tuple(input_shapes["data"])
        out = model.predict({"data": np.zeros(shape, np.float32)},
                            timeout_ms=60000)
        buckets = list(model.buckets)
        row = {
            "ready_s": round(ready_s, 3),
            "worker_warm_s": round(model.warm_seconds or 0.0, 3),
            "buckets": buckets,
            "first_predict_ok": bool(out and out[0].shape[0] == 2),
            "compile_digests": len(model.compile_digests),
            # ready-frame memory attribution + this phase's peak RSS
            # (docs/observability.md §Memory)
            "model_memory_bytes": model.memory_bytes,
            "memory": _tm_memory.read_process_memory(),
        }
    finally:
        model.close(drain=True, timeout=10)
    time.sleep(1.0)  # let the worker's exit flush land
    events = _jsonl_events(tdir)
    row["jit_compiles"] = events.get("jit_compile", 0)
    row["persist_hits"] = events.get("compile_persist_hit", 0)
    row["persist_bad"] = events.get("compile_persist_bad", 0)
    return row


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--net", choices=("mlp", "resnet18"), default="resnet18")
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--cache-dir", default=None,
                   help="persistent cache dir (default: fresh temp dir)")
    p.add_argument("--timeout", type=float, default=600.0,
                   help="per-run spawn->ready budget (seconds)")
    args = p.parse_args(argv)

    # the bench process itself must not populate the cache the COLD run
    # is supposed to find empty
    os.environ.pop("MXTPU_COMPILE_CACHE", None)

    from serve_bench import _build_mlp, _build_resnet18  # noqa: E402

    workdir = tempfile.mkdtemp(prefix="coldstart_bench_")
    cache_dir = args.cache_dir or os.path.join(workdir, "compile_cache")
    os.makedirs(cache_dir, exist_ok=True)

    log("building %s ..." % args.net)
    if args.net == "mlp":
        prefix, input_shapes = _build_mlp(workdir)
    else:
        prefix, input_shapes = _build_resnet18(workdir, args.image_size)

    log("run 1/2: COLD (empty cache %s)" % cache_dir)
    cold = _spawn_run("cold", prefix, input_shapes, args.max_batch,
                      cache_dir, workdir, args.timeout)
    log("cold: ready %.2fs, warm %.2fs, %d jit_compiles"
        % (cold["ready_s"], cold["worker_warm_s"], cold["jit_compiles"]))

    artifacts = 0
    artifact_bytes = 0
    objects = os.path.join(cache_dir, "objects")
    if os.path.isdir(objects):
        for name in os.listdir(objects):
            artifacts += 1
            artifact_bytes += os.path.getsize(os.path.join(objects, name))

    log("run 2/2: WARM (populated cache)")
    warm = _spawn_run("warm", prefix, input_shapes, args.max_batch,
                      cache_dir, workdir, args.timeout)
    log("warm: ready %.2fs, warm %.2fs, %d jit_compiles, %d persist hits"
        % (warm["ready_s"], warm["worker_warm_s"], warm["jit_compiles"],
           warm["persist_hits"]))

    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))),
                             timeout=10).stdout.strip() or None
    except Exception:
        sha = None
    result = {
        "metric": "coldstart_%s_mb%d" % (args.net, args.max_batch),
        "net": args.net,
        "max_batch": args.max_batch,
        "image_size": args.image_size if args.net == "resnet18" else None,
        "cold": cold,
        "warm": warm,
        "ready_speedup": round(cold["ready_s"] / warm["ready_s"], 2)
        if warm["ready_s"] else None,
        "warm_speedup": round(
            cold["worker_warm_s"] / warm["worker_warm_s"], 2)
        if warm["worker_warm_s"] else None,
        "zero_compile_on_warm": warm["jit_compiles"] == 0,
        "cache_artifacts": artifacts,
        "cache_bytes": artifact_bytes,
        "backend": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
        else "device",
        "sha": sha,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    # acceptance: the warm replica must not have compiled anything
    return 0 if result["zero_compile_on_warm"] else 4


if __name__ == "__main__":
    sys.exit(main())
