#!/usr/bin/env python3
"""chipbench: run one cell of BENCHMARK.json once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``chipbench/configs/...json``) and a traffic
mix (``chipbench/traffic/<traffic>.json``); the configuration names its runner,
model factory and plain reference by dotted name; every metric is a reader of
its own in ``chipbench/metrics/<name>.py``.  Nothing here knows a cell, a
configuration or a metric by name: see chipbench/README.md.

The last line of standard output is the result, one JSON object.  Without an
accelerator, or with fewer chips than the cell asks for, the process exits
non-zero and prints no result.  The command takes these four flags and no
other; a builder's sweeps, controls, series and CPU rehearsals go through
``chipbench/tools/builder.py``, which calls `run_cell` with more `Options`.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _merge(base, over):
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, rehearse=False, root=ROOT, overrides=None):
    """(benchmark, cell, configuration, traffic) of a workload, found by the
    names in BENCHMARK.json."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("chipbench: no workload %r in BENCHMARK.json (have "
                         "%s)" % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic_dir = os.path.join(root, os.path.dirname(os.path.dirname(
        entry["file"])), "traffic")
    traffic = load_json(os.path.join(traffic_dir, cell["traffic"] + ".json"))
    if rehearse:
        config = _merge(config, config.get("rehearse", {}))
        traffic = _merge(traffic, traffic.get("rehearse", {}))
    for key, value in (overrides or {}).items():
        # a builder's sweep (tools/builder.py); never the driver's
        which, name = key.split(".", 1)
        {"traffic": traffic, "config": config}[which][name] = value
    return bench, cell, config, traffic


def load_reader(name, root=ROOT):
    """The reader of a metric: ``chipbench/metrics/<name>.py``, function
    ``read(facts)``; a reader that finds nothing to read returns None."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arm_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), or where
    JAX_COMPILATION_CACHE_DIR says; every program is cached, however fast it
    compiled."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def metrics_of(bench, cell, facts, traced, rehearse, root=ROOT):
    """The cell's metrics as the result line carries them."""
    out = {}
    listed = bench["per_layer"] if traced else bench["end_to_end"]
    for m in listed:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = load_reader(m["name"], root)(facts)
        if value is None:
            if not traced:
                raise RuntimeError("end-to-end metric %s has no value in %s"
                                   % (m["name"], cell["name"]))
            continue
        if rehearse and m["source"] != "program_counter":
            value = None            # a CPU run gives no time, rate or share
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Options:
    """What the command line gives (seed, seconds, trace), and what only a
    builder's tool or a test sets: ``rehearse`` walks the cell at the tiny
    sizes its files give under "rehearse" on whatever JAX has and reports
    counts only; ``series`` is called with the run's per-step series and its
    reduced trace;
    ``control`` names lower precisions whose numbers are printed beside the
    check's; ``break_step`` is handed the trainer or the model to break;
    ``overrides`` alters values of the traffic or the configuration."""

    def __init__(self, seed, seconds, trace, rehearse=False, series=None,
                 control=None, break_step=None, overrides=None):
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.rehearse, self.series, self.control = rehearse, series, control
        self.break_step, self.overrides = break_step, overrides


def run_cell(workload, opts, t_process=None, root=ROOT):
    """Run one cell in this process and return the result line's object.
    ``root`` holds BENCHMARK.json and the data files (the checkout; a test
    may point it at a copy with a cell added)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    bench, cell, config, traffic = load_cell(workload, opts.rehearse, root,
                                             opts.overrides)
    if not opts.rehearse:
        arm_compile_cache()
    runner = importlib.import_module(config["runner"])
    facts = runner.run(cell, config, traffic, opts,
                       T_PROCESS if t_process is None else t_process)
    from chipbench.lib import device as devlib

    dev = devlib.describe(facts["devices"], facts["memory_peak_bytes"])
    result = {"correct": bool(facts["correct"]),
              "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]),
              "metrics": metrics_of(bench, cell, facts, opts.trace,
                                    opts.rehearse, root),
              "device": dev}
    if opts.rehearse:
        result["rehearsal"] = True
    if opts.trace:
        tr = facts.get("trace")
        if tr is None and not opts.rehearse:
            raise RuntimeError("the traced run holds no device operation")
        if tr is not None and not opts.rehearse:
            dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
            result["breakdown"] = tr["breakdown"]
    # every number compared, beside its limit: the line's last key (a
    # number that is not finite is null there: the line stays JSON)
    result["checks"] = {
        name: {"value": float(c["value"]) if math.isfinite(c["value"])
               else None, "limit": c["limit"]}
        for name, c in facts["checks"].items()}
    return result


def emit(result):
    """Each number compared beside its limit as the last lines of standard
    error, and the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print("check %s %s limit %s" % (name, c["value"], c["limit"]),
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    emit(run_cell(args.workload, Options(args.seed, args.seconds, args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
