import importlib.util
import os

import numpy as np
import pytest

from bench import devtrace
from bench.devtrace import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_union_and_clip():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]) == [
        [0, 3], [5, 9], [10, 11]]
    assert devtrace.clip([[0, 3], [5, 9]], 2, 6) == [[2, 3], [5, 6]]


def _synthetic():
    ms = 1e6  # ns per ms
    host = [Event("bench.window", 0, 100 * ms),
            Event("bench.tick", 0, 50 * ms),
            Event("TransferFromDevice", 40 * ms, 50 * ms),
            Event("bench.sleep", 50 * ms, 100 * ms)]
    dev = [Event("fusion.1", -10 * ms, 10 * ms),  # starts before the window
           Event("frontier_scores", 10 * ms, 30 * ms),
           Event("fusion.2", 20 * ms, 35 * ms),  # overlaps the kernel
           Event("fusion.3", 60 * ms, 70 * ms)]
    return Trace({"/device:TPU:0": dev}, host)


def test_reduce_synthetic():
    r = devtrace.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [0, 35] and [60, 70] ms
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["ops"]["frontier_scores"] == pytest.approx(0.020)
    assert r["ops"]["fusion.1"] == pytest.approx(0.010)  # clipped
    gaps = dict(r["idle_gaps"])
    # idle [35, 50] inside a tick, under the read-back; [50, 60] and
    # [70, 100] while the harness slept
    assert gaps == pytest.approx({"tick/TransferFromDevice": 0.015,
                                  "sleep": 0.040})
    assert r["device_ops"][0] == ["frontier_scores", pytest.approx(0.02)]
    idle = _reader("idle_share.stream").read(
        {"kind": "open_loop", "trace": r})
    assert idle == pytest.approx(55.0)
    assert _reader("idle_share.batch").read(
        {"kind": "open_loop", "trace": r}) is None


def test_gather_readers_and_roofline_bytes():
    r = devtrace.reduce(_synthetic())
    rl = _reader("gather_roofline.batch")
    assert rl.useful_bytes(10, 32) == 10 * (32 * 4 + 8)
    run = {"kind": "closed_batches", "trace": r, "config": {"m_prime": 32},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "rec": {"evals": np.full(512, 1_000_000),
                   "traced": np.arange(512) < 256}}
    share = rl.read(run)
    least_s = 256e6 * 136 / 819e9
    assert share == pytest.approx(100 * least_s / 0.045)
    assert 0 < share <= 100
    per_q = _reader("gather_ms_per_query.batch").read(run)
    assert per_q == pytest.approx(1e3 * 0.020 / 256)
    # nothing to read: no trace, or no kernel event
    assert rl.read(dict(run, trace=None)) is None
    r2 = dict(r, ops={"fusion.9": 1.0})
    assert _reader("gather_ms_per_query.batch").read(dict(run, trace=r2)) is None


def test_short_names_and_containers():
    hlo = ("%frontier_scores.9 = f32[256,120]{1,0} custom-call(s32[256,120] "
           "%a, f32[250000,128] %rows), custom_call_target=\"tpu_custom_call\"")
    assert devtrace.short_name(hlo) == "frontier_scores"
    assert devtrace.short_name("%fusion.62 = u32[17280] fusion(%or.92)") == \
        "fusion.62"
    loop = Event("%while.19 = (f32[256,4096]) while(%tuple)", 0, 100)
    body = [Event("a", 10, 20), Event("b", 30, 40)]
    assert devtrace.leaves([loop] + body) == body
    r = devtrace.reduce(Trace({"/device:TPU:0": [loop] + body},
                              [Event("bench.window", 0, 100)]))
    assert set(r["ops"]) == {"a", "b"}
    assert r["busy_s"] == pytest.approx(100e-9)


def test_dump_and_read_back(tmp_path):
    tr = _synthetic()
    devtrace.dump(tr, tmp_path / "t.json")
    back = devtrace.read_json(tmp_path / "t.json")
    assert devtrace.reduce(back) == devtrace.reduce(tr)
