"""trace_reduce on small traces recorded on a TPU v5e, and on hand-made
events: busy, idle, self times and collective totals."""

from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _load(name):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(DATA / name))


def test_recorded_one_chip_trace():
    # one v5e: three runs of one fusion inside the window event, each
    # followed by a sleep; the device clock reads ~1 ms early against the
    # host's, so the first run starts before the window and is left out
    red = trace_reduce.reduce(_load("tpu_1chip.xplane.pb"), n_devices=1)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(9_698_690e-9)
    assert red["busy_s"] == pytest.approx((11_358 + 11_091) * 1e-9)
    assert red["collective_s"] == 0.0
    assert red["top_ops"] == [["fusion kOutput", pytest.approx(22_449e-9)]]
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["idle_gaps"][0][0] == "$time sleep"


def test_recorded_four_chip_trace():
    # a 2x2 v5e host: a loop of a collective-permute and an all-reduce on
    # each chip, three times inside the window event
    red = trace_reduce.reduce(_load("tpu_4chip.xplane.pb"), n_devices=4)
    assert red["devices"] == 4
    assert red["window_s"] == pytest.approx(10_966_719e-9)
    assert red["busy_s_per_device"] == pytest.approx(
        [85_415e-9, 83_755e-9, 83_888e-9, 81_752e-9])
    assert red["busy_s"] == pytest.approx(sum(red["busy_s_per_device"]) / 4)
    # collective-permute start/done and the all-reduce, per chip, averaged
    assert red["collective_s"] == pytest.approx(
        (68_412 + 66_531 + 66_870 + 64_633) / 4 * 1e-9)
    top = dict(red["top_ops"])
    assert top["collective-permute-done"] > top["psum_invariant.9"] > 0
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s_per_device"][0])


def test_no_window_event_reads_nothing():
    pd = NS(planes=[NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        NS(name="%a = f32[] add(f32[] %x)", start_ns=0.0, duration_ns=5.0)])])])
    assert trace_reduce.reduce(pd, 1) is None


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _fake(devices):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("chipbench.traced", 0, 1000),
        _ev("chipbench.solve", 0, 600),
        _ev("to_device_vec", 0, 100),
        _ev("chipbench.wait_arrival", 700, 300)])])
    planes = [host]
    for d, ops in devices.items():
        planes.append(NS(name=f"/device:TPU:{d}",
                         lines=[NS(name="XLA Modules", events=[]),
                                NS(name="XLA Ops", events=ops)]))
    return NS(planes=planes)


def test_nested_ops_self_time_and_collectives():
    ops = [
        _ev("%while.1 = (f32[8]) while((f32[8]) %t), body=%b", 100, 500),
        _ev("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.3), kind=kLoop", 120, 100),
        _ev("%all-reduce.3 = f32[8] all-reduce(f32[8] %p)", 250, 50),
        _ev("%collective-permute-start.4 = (f32[8], f32[8]) "
            "collective-permute-start(f32[8] %q)", 320, 30),
        _ev("%cg_update.5 = (f32[8]) custom-call(f32[8] %r), "
            'custom_call_target="tpu_custom_call"', 400, 150),
    ]
    other = [_ev("%all-reduce.3 = f32[8] all-reduce(f32[8] %p)", 200, 80)]
    red = trace_reduce.reduce(_fake({0: ops, 1: other}), n_devices=2)
    assert red["window_s"] == pytest.approx(1000e-9)
    # device 0 busy 100..600, device 1 busy 200..280
    assert red["busy_s_per_device"] == pytest.approx([500e-9, 80e-9])
    assert red["busy_s"] == pytest.approx(290e-9)
    # collectives: device 0 50 + 30, device 1 80 -> mean 80 ns; the
    # fusion that only reads an all-reduce's result is not one
    assert red["collective_s"] == pytest.approx(80e-9)
    top = dict(red["top_ops"])
    # while: 500 less 100 + 50 + 30 + 150 held inside it = 170, over 2
    assert top["while.1"] == pytest.approx(170e-9 / 2)
    assert top["fusion.2 kLoop"] == pytest.approx(100e-9 / 2)
    assert top["all-reduce.3"] == pytest.approx(130e-9 / 2)
    # device 0 idle: 0..100 under to_device_vec, and 600..1000, whose
    # midpoint falls while the host waited for an arrival
    gaps = dict(red["idle_gaps"])
    assert gaps == {"chipbench.wait_arrival": pytest.approx(400e-9),
                    "to_device_vec": pytest.approx(100e-9)}


def test_ops_of_devices_beyond_the_cell_are_left_out():
    ops = [_ev("%a.1 = f32[8] add(f32[8] %x)", 100, 100)]
    red = trace_reduce.reduce(_fake({0: ops, 1: ops}), n_devices=1)
    assert red["devices"] == 1 and red["busy_s"] == pytest.approx(100e-9)


def test_union():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9]], np.float64)
    np.testing.assert_array_equal(trace_reduce._union(iv),
                                  [[0, 3], [5, 9]])


@pytest.mark.parametrize("text, name, coll", [
    ("%fusion.4 = f32[8]{0:T(1024)} fusion(s32[8] %i), kind=kCustom",
     "fusion.4 kCustom", False),
    ("%all-reduce-start.2 = f32[] all-reduce-start(f32[] %x)",
     "all-reduce-start.2", True),
    ("%broadcast.51 = f32[1,8]{1,0:T(1,128)S(1)} broadcast(f32[] %c)",
     "broadcast.51", False),
])
def test_op_name(text, name, coll):
    assert trace_reduce.op_name(text) == (name, coll)
