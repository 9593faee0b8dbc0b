"""`sampler_greedy_share.serve` on a hand-made ring."""
import pytest

from chipbench import run as bench_run

SERVE = {"kind": "serve"}


def _lap(i, n, sampled=None, traced=True):
    lap = {"t0": float(i), "t1": i + 0.02, "phases": {"decode_wait": 0.015},
           "traced": traced, "cpu_s": 0.004, "n": n, "bucket": 4,
           "model": "lm/1", "step": None}
    if sampled is not None:
        lap["sampled"] = sampled
    return lap


def _read(monkeypatch, ring, facts=SERVE):
    from mxnet_tpu.telemetry import goodput

    monkeypatch.setattr(goodput, "window", lambda kind: list(ring))
    return bench_run.load_reader("sampler_greedy_share.serve")(facts)


def test_share_of_the_traced_laps_that_stepped(monkeypatch):
    # 9 greedy steps and 3 with a sampled row; an untraced sampled lap and a
    # traced lap that did not step count for nothing
    ring = [_lap(0, 4, sampled=4, traced=False)]
    ring += [_lap(1 + i, 4, sampled=(2 if i % 4 == 0 else 0))
             for i in range(12)]
    ring.append(_lap(20, 0, sampled=0))
    assert _read(monkeypatch, ring) == pytest.approx(75.0)
    assert _read(monkeypatch, ring, {"kind": "train"}) is None


def test_a_program_without_the_field_reads_as_nothing(monkeypatch):
    assert _read(monkeypatch, [_lap(i, 4) for i in range(12)]) is None
