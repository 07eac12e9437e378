"""The per-layer readers of the program's build record, and a rehearsal of
the cell whose graph is built under a symmetrized distance."""

import sys

import pytest

import repro.core
from bench import harness
from repro.core import telemetry

ROOT = harness.ROOT
READERS = ("build_evals_per_point", "build_wave_ms")
CELL = "wiki128-kl-avgbuild.stream"


def _record(waves=4, build_evals=12_000, seconds=(0.5, 2.0, 0.25)):
    rec = telemetry.BuildRecord()
    with rec:
        pass
    rec._row[:] = seconds
    rec.waves, rec.build_evals, rec.m_prime = waves, build_evals, 256
    return rec


def test_reads_a_hand_made_record():
    _record()
    run = {"n": 600}
    assert harness.load_reader(ROOT, "build_evals_per_point")(run) == 20.0
    assert harness.load_reader(ROOT, "build_wave_ms")(run) == pytest.approx(500.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read(name, monkeypatch):
    read = harness.load_reader(ROOT, name)
    _record(waves=None, build_evals=None)  # a builder without waves
    assert read({"n": 600}) is None
    # a program with telemetry but no build record (before this reader)
    monkeypatch.delattr(telemetry, "latest_build")
    assert read({"n": 600}) is None
    # a program that keeps no telemetry at all
    monkeypatch.delattr(repro.core, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert read({"n": 600}) is None


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_of_the_symmetrized_build_cell(trace):
    """Off the chip at 600 rows: the line's shape, the check, and the build
    record read (the count; a host-clock time is not measured here)."""
    line = harness.run_cell(CELL, 4294967377, 2.0, trace, rehearsal=600)
    assert line["correct"] and line["failed"] == 0
    assert line["checks"]["recall_at_10"]["ok"]
    m = line["metrics"]
    if trace:
        assert m["build_evals_per_point"]["value"] > 0
        assert m["build_wave_ms"]["value"] is None
    else:
        assert m["recall_at_10"]["value"] >= 0.9
        assert m["build_points_per_s"]["value"] is None
