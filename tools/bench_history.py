#!/usr/bin/env python
"""bench_history: aggregate committed ``BENCH_*.json`` evidence into one
trajectory table.

20+ bench artifacts are committed at the repo root (bench.py rows,
serve_bench, failover, coldstart, memory rows — every PR adds more), but
a reviewer asking "how has throughput moved across PRs?" has to open
them one by one. This tool reads every ``BENCH_*.json``, extracts each
row's headline figure with schema-aware extractors (the artifacts were
never one schema and never will be — stale/error rows are kept and
labeled, not hidden), and writes:

  * ``docs/bench_trajectory.md`` — the human table, sorted by capture
    round then row name;
  * ``BENCH_TRAJECTORY.json`` — the machine-readable rows (plots, CI
    trend checks).

Run it directly or let ``tools/bench_capture.sh`` append the current
capture's rows at the end of every run:

    python tools/bench_history.py [--root DIR] [--quiet]

``--check`` turns the trajectory from write-only evidence into a
regression gate: for each headline metric family (serving rps, decode
tokens/sec, failover rps, cold-start time-to-ready, training MFU), the
newest round's row is compared against the BEST prior non-stale,
non-failed row of the same kind; a >15% regression (``--tolerance``)
prints a table and exits 2. ``bench_capture.sh`` runs it warn-only at
the end of every capture; CI can run it blocking.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

# the row group is LAZY so a trailing `_stale` relabel (committed captures
# from before PR 23 carry it) lands in the stale group instead of being
# swallowed into the row name — stale captures must render as stale
_NAME_RE = re.compile(r"BENCH_(?:(?P<scope>local)_)?r(?P<round>\d+)"
                      r"(?:_(?P<row>[A-Za-z0-9_]+?))?(?P<stale>_stale)?"
                      r"\.json$")


def _fmt(v, nd=2):
    if v is None:
        return ""
    if isinstance(v, float):
        return ("%%.%df" % nd) % v
    return str(v)


def _extract(doc):
    """(metric, value, unit, detail) headline for one artifact, by schema
    family. Unknown schemas degrade to a labeled raw row, never a skip."""
    if not isinstance(doc, dict):
        return ("unparsed", None, "", "non-object JSON")
    # bench_capture probe-failure rows ({"n":..,"rc":..,"tail":..} or
    # explicit error/stale labels)
    if doc.get("error") or ("rc" in doc and doc.get("rc") not in (0, None)):
        return ("capture_failed", None, "",
                str(doc.get("error") or "rc=%s" % doc.get("rc"))[:60])
    mode = doc.get("mode")
    if mode == "serve_bench":
        b = doc.get("batched") or {}
        s = doc.get("sequential") or {}
        detail = "seq %s rps, x%s, p99 %sms" % (
            _fmt(s.get("rps"), 1),
            _fmt(doc.get("speedup_batched_vs_sequential")),
            _fmt(b.get("p99_ms"), 1))
        return ("serve_batched_rps", b.get("rps"), "req/s", detail)
    if mode == "serve_decode":
        kv = doc.get("kv") or {}
        detail = "inter-token p99 %sms, kv peak %s/%s pages, %s jit " \
                 "after warm" % (
                     _fmt(doc.get("intertoken_p99_ms"), 1),
                     _fmt(kv.get("peak_pages_used"), 0),
                     _fmt(kv.get("pages_total"), 0),
                     _fmt(doc.get("jit_compiles_after_warmup"), 0))
        return ("decode_tokens_per_sec", doc.get("tokens_per_sec"),
                "tok/s", detail)
    if mode == "serve_failover":
        lw = doc.get("loss_window") or {}
        return ("failover_rps", doc.get("rps_overall"), "req/s",
                "loss-window %s rps, %s errors, recovery %ss" % (
                    _fmt(lw.get("rps"), 1), _fmt(doc.get("unresolved"), 0),
                    _fmt(doc.get("recovery_s"), 1)))
    if mode == "serve_autoscale":
        return ("autoscale_scale_up_s", doc.get("scale_up_latency_s"), "s",
                "1->%s replicas, p99 recovered %ss, down %ss, 500s=%s" % (
                    _fmt(doc.get("scaled_to"), 0),
                    _fmt(doc.get("p99_recovery_s"), 1),
                    _fmt(doc.get("scale_down_s"), 1),
                    "no" if doc.get("zero_500s") else "YES"))
    if mode == "serve_memory":
        return ("serve_memory", doc.get("footprint_bytes"), "bytes",
                "budget reject=%s accept=%s, donation aliased=%s" % (
                    doc.get("over_budget_rejected"),
                    doc.get("within_budget_accepted"),
                    _fmt((doc.get("donation") or {}).get(
                        "aliased_fraction"))))
    metric = doc.get("metric") or ""
    if metric.startswith("coldstart"):
        warm, cold = doc.get("warm") or {}, doc.get("cold") or {}
        return (metric, warm.get("ready_s"), "s ready (warm)",
                "cold %ss, x%s, %s jit on warm" % (
                    _fmt(cold.get("ready_s"), 1),
                    _fmt(doc.get("ready_speedup")),
                    _fmt(warm.get("jit_compiles"), 0)))
    if "train_sharded" in metric and "value" in doc:
        # the hot-path promotion A/B row (bench.py bench_train_sharded):
        # surface the fused-vs-op-by-op evidence, the dispatch-overhead
        # delta, the donation aliasing and the data-wait share
        detail = []
        if doc.get("speedup_fused_vs_opbyop") is not None:
            detail.append("x%s vs op-by-op"
                          % _fmt(doc["speedup_fused_vs_opbyop"]))
        if doc.get("dispatch_per_step_opbyop") is not None:
            detail.append("dispatch %s->%s/step" % (
                _fmt(doc["dispatch_per_step_opbyop"], 0),
                _fmt(doc.get("dispatch_per_step_fused"), 0)))
        if doc.get("aliased_fraction") is not None:
            detail.append("aliased %s" % _fmt(doc["aliased_fraction"]))
        if doc.get("data_wait_fraction") is not None:
            detail.append("wait %s%%"
                          % _fmt(100 * doc["data_wait_fraction"], 1))
        if doc.get("stale"):
            detail.append("STALE")
        return (metric, doc.get("value"), doc.get("unit") or "",
                ", ".join(detail))
    if "train_input" in metric and "value" in doc:
        # the input-pipeline A/B row (bench.py bench_train_input):
        # headline is the prefetched imgs/sec; detail surfaces the
        # data-wait contrast and the row's self-checks (loss-trajectory
        # equality, post-warm compiles, attributor coverage)
        detail = []
        if doc.get("speedup_prefetched_vs_sync") is not None:
            detail.append("x%s vs sync"
                          % _fmt(doc["speedup_prefetched_vs_sync"]))
        if doc.get("data_wait_fraction_sync") is not None:
            detail.append("wait %s%%->%s%%" % (
                _fmt(100 * doc["data_wait_fraction_sync"], 1),
                _fmt(100 * (doc.get("data_wait_fraction_prefetched")
                            or 0.0), 1)))
        if doc.get("data_wait_reduction") is not None:
            detail.append("wait /%s" % _fmt(doc["data_wait_reduction"], 1))
        if doc.get("loss_trajectory_match") is False:
            detail.append("TRAJECTORY DIVERGED")
        if doc.get("jit_compiles_after_warm"):
            detail.append("%s jit after warm"
                          % _fmt(doc["jit_compiles_after_warm"], 0))
        if doc.get("goodput_coverage_prefetched") is not None:
            detail.append("coverage %s"
                          % _fmt(doc["goodput_coverage_prefetched"]))
        if doc.get("platform"):
            detail.append(str(doc["platform"]))
        if doc.get("stale"):
            detail.append("STALE")
        return (metric, doc.get("value"), doc.get("unit") or "",
                ", ".join(detail))
    if metric == "train_goodput" and "value" in doc:
        # the goodput-attribution A/B row (bench.py bench_train_goodput):
        # headline is the attributed goodput fraction; detail surfaces the
        # stall mix and whether the legacy fit split and the attributor
        # still agree on data-wait (the row's self-check)
        gp = doc.get("goodput") or {}
        fr = gp.get("phase_fractions") or {}
        detail = []
        if fr.get("data_wait") is not None:
            detail.append("wait %s%%" % _fmt(100 * fr["data_wait"], 1))
        stalls = {p: v for p, v in fr.items()
                  if p not in ("compute", "data_wait")}
        if stalls:
            top = max(stalls.items(), key=lambda kv: kv[1])
            detail.append("top stall %s %s%%" % (top[0],
                                                 _fmt(100 * top[1], 1)))
        if doc.get("ab_data_wait_ratio") is not None:
            detail.append("A/B x%s%s" % (
                _fmt(doc["ab_data_wait_ratio"]),
                "" if doc.get("ab_agree_within_10pct") else " DISAGREE"))
        if doc.get("platform"):
            detail.append(str(doc["platform"]))
        if doc.get("stale"):
            detail.append("STALE")
        return (metric, doc.get("value"), doc.get("unit") or "fraction",
                ", ".join(detail))
    if metric == "train_preempt_ckpt_stall" and "value" in doc:
        # the async-vs-sync checkpoint stall A/B (train_restart_bench.py
        # --mode preempt): per-save trainer stall plus the measured
        # steps-lost contrast between a hard kill and a graceful preempt
        sy, asy = doc.get("sync") or {}, doc.get("async") or {}
        lost = doc.get("steps_lost") or {}
        detail = ["sync %sms -> async %sms/save" % (
            _fmt((sy.get("per_save_stall_s") or 0) * 1e3, 0),
            _fmt((asy.get("per_save_stall_s") or 0) * 1e3, 0))]
        if lost:
            detail.append("lost kill=%s preempt=%s" % (
                _fmt(lost.get("steps_lost_hard_kill"), 0),
                _fmt(lost.get("steps_lost_graceful_preempt"), 0)))
        if doc.get("payload_bytes"):
            detail.append("%sMB payload"
                          % _fmt(doc["payload_bytes"] / (1 << 20), 0))
        if doc.get("stale"):
            detail.append("STALE")
        return (metric, doc.get("value"), doc.get("unit") or "x",
                ", ".join(detail))
    if metric and "value" in doc:
        detail = []
        if doc.get("mfu") is not None:
            detail.append("MFU %s" % _fmt(doc["mfu"], 3))
        if doc.get("data_wait_fraction") is not None:
            # data-wait vs compute split of the timed region (train rows)
            detail.append("wait %s%%"
                          % _fmt(100 * doc["data_wait_fraction"], 1))
        if doc.get("vs_baseline") is not None:
            detail.append("x%s vs %s" % (_fmt(doc["vs_baseline"]),
                                         (doc.get("baseline") or {}).get(
                                             "hw", "baseline")))
        if doc.get("stale"):
            detail.append("STALE")
        return (metric, doc.get("value"), doc.get("unit") or "",
                ", ".join(detail))
    return ("unknown_schema", None, "",
            ", ".join(sorted(doc)[:6]))


def collect(root):
    """One trajectory row per BENCH_*.json under ``root``."""
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        base = os.path.basename(path)
        if base == "BENCH_TRAJECTORY.json":
            continue
        m = _NAME_RE.match(base)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            doc = {"error": "unreadable: %s" % e}
        metric, value, unit, detail = _extract(doc)
        device = doc.get("device") or doc.get("backend") \
            if isinstance(doc, dict) else None
        utc = doc.get("utc") if isinstance(doc, dict) else None
        if not utc:
            utc = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                time.gmtime(os.path.getmtime(path)))
        rows.append({
            "file": base,
            "round": int(m.group("round")) if m else None,
            "row": (m.group("row") if m else None) or "",
            "stale": bool(m and m.group("stale")) or bool(
                isinstance(doc, dict) and doc.get("stale")),
            "metric": metric,
            "value": value,
            "unit": unit,
            "device": device,
            # MFU rides along where the artifact reports it, so --check
            # can gate on it next to the throughput headline
            "mfu": doc.get("mfu") if isinstance(doc, dict) else None,
            "detail": detail,
            "utc": utc,
        })
    rows.sort(key=lambda r: (r["round"] if r["round"] is not None else 999,
                             r["row"], r["file"]))
    return rows


def render_markdown(rows):
    lines = [
        "# Bench trajectory",
        "",
        "Generated by `python tools/bench_history.py` from the committed",
        "`BENCH_*.json` evidence files (one row each; `bench_capture.sh`",
        "refreshes this table at the end of every capture). `capture_failed`",
        "rows are kept — a stale/failed capture is evidence too",
        "(ROADMAP item 5).",
        "",
        "| Round | Row | Metric | Value | Unit | Device | Detail | File |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        detail = (r["detail"] or "-").replace("|", "/")
        if r["stale"]:
            detail = ("**STALE** " + detail).rstrip(" -")
        lines.append("| %s | %s | %s | %s | %s | %s | %s | `%s` |" % (
            "r%02d" % r["round"] if r["round"] is not None else "?",
            r["row"] or "-", r["metric"],
            _fmt(r["value"]), r["unit"] or "-", r["device"] or "-",
            detail, r["file"]))
    lines += ["",
              "%d artifact(s); machine-readable mirror: "
              "`BENCH_TRAJECTORY.json`." % len(rows), ""]
    return "\n".join(lines)


# headline metric families the --check gate compares across rounds, and
# which direction is "better". Values are per-row `metric` names from
# `_extract`; MFU is gated separately off each row's `mfu` field.
_CHECK_METRICS = {
    "serve_batched_rps": "higher",
    "decode_tokens_per_sec": "higher",
    "failover_rps": "higher",
    "coldstart_ready": "lower",     # warm time-to-ready, seconds
    # (includes coldstart_train_*: fused-restart time-to-step-1)
    "autoscale_scale_up_s": "lower",  # surge -> grown pool serving
    "train_sharded": "higher",      # promotion A/B imgs/sec, per impl+bs
    "train_input": "higher",        # prefetch A/B imgs/sec, per batch
    "train_preempt_ckpt_stall": "higher",  # sync/async stall reduction, x
    "train_goodput": "higher",      # attributed goodput fraction of wall
}


def _check_one(label, newest, best, direction, tolerance):
    """One comparison row, or None when within tolerance. ``newest`` and
    ``best`` are (value, file) pairs."""
    if not newest[0] or not best[0]:
        return None
    if direction == "higher":
        change = (best[0] - newest[0]) / best[0]
    else:
        change = (newest[0] - best[0]) / best[0]
    if change <= tolerance:
        return None
    return {"metric": label, "newest": newest[0], "newest_file": newest[1],
            "best_prior": best[0], "best_file": best[1],
            "regression_pct": round(change * 100.0, 1),
            "direction": direction}


def check(rows, tolerance=0.15):
    """Regression gate over trajectory rows: for each headline family,
    newest-round row vs the best prior NON-STALE, non-failed row. Returns
    the list of regressions (empty = gate passes)."""
    regressions = []
    usable = [r for r in rows
              if not r["stale"] and r["round"] is not None
              and r["metric"] not in ("capture_failed", "unparsed",
                                      "unknown_schema")]

    def gate(label, group, value_of, direction):
        group = [r for r in group if value_of(r)]
        if len(group) < 2:
            return  # nothing to compare against — not a failure
        newest_round = max(r["round"] for r in group)
        newest = [r for r in group if r["round"] == newest_round]
        prior = [r for r in group if r["round"] < newest_round]
        if not prior:
            return
        pick = max if direction == "higher" else min
        best = pick(prior, key=value_of)
        new = pick(newest, key=value_of)  # best of the newest round
        hit = _check_one(label, (value_of(new), new["file"]),
                         (value_of(best), best["file"]), direction,
                         tolerance)
        if hit:
            regressions.append(hit)

    for metric, direction in _CHECK_METRICS.items():
        if metric == "coldstart_ready":
            # coldstart metric names are per-model-geometry
            # (coldstart_resnet18_mb8, ...): gate each family on its own
            # history — comparing different models' ready-times would
            # both false-alarm and mask real regressions
            names = sorted({str(r["metric"]) for r in usable
                            if str(r["metric"]).startswith("coldstart")})
            for name in names:
                gate(name, [r for r in usable if r["metric"] == name],
                     lambda r: r["value"], direction)
            continue
        if metric in ("train_sharded", "train_input"):
            # per-impl-per-batch families (mlp_train_sharded_fused_bs256_
            # imgs_per_sec, mlp_train_input_prefetch_bs256_..., ...):
            # each name gates on its own history — racing configs would
            # mask one family's regression behind another's improvement
            names = sorted({str(r["metric"]) for r in usable
                            if metric in str(r["metric"])})
            for name in names:
                gate(name, [r for r in usable if r["metric"] == name],
                     lambda r: r["value"], direction)
            continue
        gate(metric, [r for r in usable if r["metric"] == metric],
             lambda r: r["value"], direction)
    # MFU gate: per (metric, row) family so train MFU never races score MFU
    mfu_rows = [r for r in usable if r.get("mfu")]
    for key in sorted({(r["metric"], r["row"]) for r in mfu_rows}):
        group = [r for r in mfu_rows
                 if (r["metric"], r["row"]) == key]
        gate("mfu:%s/%s" % key, group, lambda r: r.get("mfu"), "higher")
    return regressions


def render_check_table(regressions):
    lines = ["| Metric | Newest | Best prior | Regression | Files |",
             "|---|---|---|---|---|"]
    for r in regressions:
        lines.append("| %s | %s | %s | %.1f%% | `%s` vs `%s` |" % (
            r["metric"], _fmt(r["newest"]), _fmt(r["best_prior"]),
            r["regression_pct"], r["newest_file"], r["best_file"]))
    return "\n".join(lines)


def run_check(root, tolerance, quiet=False):
    """The --check entry: prefer the committed BENCH_TRAJECTORY.json
    (what reviewers see), fall back to a fresh collect()."""
    traj = os.path.join(root, "BENCH_TRAJECTORY.json")
    rows = None
    if os.path.exists(traj):
        try:
            with open(traj) as f:
                rows = json.load(f).get("rows")
        except (OSError, ValueError) as e:
            sys.stderr.write("[bench_history] unreadable %s (%s); "
                             "re-collecting\n" % (traj, e))
    if not rows:
        rows = collect(root)
    regressions = check(rows, tolerance)
    if regressions:
        sys.stderr.write(
            "[bench_history] REGRESSION: %d headline metric(s) worse than "
            "%.0f%% vs the best prior non-stale row:\n%s\n"
            % (len(regressions), tolerance * 100.0,
               render_check_table(regressions)))
        return 2
    if not quiet:
        sys.stderr.write("[bench_history] check ok: no headline metric "
                         ">%.0f%% below its best prior non-stale row "
                         "(%d rows)\n" % (tolerance * 100.0, len(rows)))
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=None,
                   help="repo root holding BENCH_*.json (default: the "
                        "checkout this tool lives in)")
    p.add_argument("--check", action="store_true",
                   help="regression gate: compare the newest round's "
                        "headline metrics against the best prior "
                        "non-stale row; exit 2 and print a table on "
                        "a regression beyond --tolerance")
    p.add_argument("--tolerance", type=float, default=0.15,
                   help="--check regression tolerance as a fraction "
                        "(default 0.15 = 15%%)")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)
    root = args.root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    if args.check:
        return run_check(root, args.tolerance, quiet=args.quiet)
    rows = collect(root)
    md_path = os.path.join(root, "docs", "bench_trajectory.md")
    os.makedirs(os.path.dirname(md_path), exist_ok=True)
    with open(md_path, "w") as f:
        f.write(render_markdown(rows))
    json_path = os.path.join(root, "BENCH_TRAJECTORY.json")
    with open(json_path, "w") as f:
        json.dump({"generated_by": "tools/bench_history.py",
                   "rows": rows}, f, indent=1)
        f.write("\n")
    if not args.quiet:
        sys.stderr.write("[bench_history] %d rows -> %s + %s\n"
                         % (len(rows), md_path, json_path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
