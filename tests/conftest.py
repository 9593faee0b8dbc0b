"""Test configuration: run on CPU with 8 virtual XLA devices so multi-device
sharding tests work without TPU hardware (the strategy SURVEY §4 prescribes:
reference tests spawn real localhost processes; we use
xla_force_host_platform_device_count)."""
import os

# The chip is chip_smoke.py's business (through the chip tool); the suite
# itself always runs here.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: left out of tier-1 (`-m 'not slow'`)")
    config._mxtpu_suite_t0 = __import__("time").time()


def _leaked_threads():
    """Non-daemon threads (other than the main thread) still alive at
    session exit: each one blocks interpreter shutdown and points at a
    library/test shutdown path that forgot to join — the runtime shadow
    of mxlint's thread-hygiene rule."""
    import threading

    return sorted(
        t.name for t in threading.enumerate()
        if t.is_alive() and not t.daemon
        and t is not threading.main_thread())


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Record suite wall time + leaked non-daemon threads in every run's
    output (and optionally a file via MXTPU_WALLTIME_FILE) so the tier-1
    CI budget — the 1500s timeout in ROADMAP.md's verify command — is
    visibly respected as the suite grows (VERDICT round-5 item 9), and a
    thread leak shows up next to the walltime it inflates."""
    import json
    import os
    import time

    t0 = getattr(config, "_mxtpu_suite_t0", None)
    if t0 is None:
        return
    wall = time.time() - t0
    budget = 1500  # keep in sync with the ROADMAP.md tier-1 timeout
    # suite peak RSS (ru_maxrss high-water mark) rides the report so the
    # next tier-1 budget renegotiation has memory data, not just wall time
    peak_rss = None
    try:
        import resource

        peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        pass
    leaked = _leaked_threads()
    terminalreporter.write_line(
        "[tier-1] suite wall time: %.0fs (budget %ds, %.0f%% used)%s"
        % (wall, budget, 100.0 * wall / budget,
           "" if peak_rss is None
           else ", peak RSS %.0f MiB" % (peak_rss / (1 << 20))))
    out = os.environ.get("MXTPU_WALLTIME_FILE")
    prev_leaked = None   # None = no prior run to compare against
    if out and os.path.exists(out):
        try:
            with open(out) as f:
                rows = [json.loads(ln) for ln in f if ln.strip()]
            if rows and "leaked_threads" in rows[-1]:
                prev_leaked = len(rows[-1]["leaked_threads"] or [])
        except (OSError, ValueError):
            pass
    if leaked or prev_leaked:
        # growth is only judged against a real prior row — a run without
        # MXTPU_WALLTIME_FILE (or the first row of a fresh file) reports
        # the leak without crying regression
        grew = prev_leaked is not None and len(leaked) > prev_leaked
        terminalreporter.write_line(
            "[tier-1]%s leaked non-daemon threads: %d (%s)%s"
            % (" FAIL-ANNOTATE:" if grew else "", len(leaked),
               ", ".join(leaked) or "-",
               " — GREW from %d; some shutdown path stopped joining"
               % prev_leaked if grew else ""),
            red=grew)
    if out:
        with open(out, "a") as f:
            f.write(json.dumps({"utc": time.strftime("%FT%TZ", time.gmtime()),
                                "wall_s": round(wall, 1),
                                "budget_s": budget,
                                "peak_rss_bytes": peak_rss,
                                "leaked_threads": leaked,
                                "exit": int(exitstatus)}) + "\n")
