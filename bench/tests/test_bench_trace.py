"""The trace reduction: on a hand-built trace with known answers, and on a
small trace recorded on a TPU v5e and committed beside this file."""
import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Event = collections.namedtuple("Event", "name start_ns duration_ns stats")

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "vgg16_fprop_v5e.xplane.pb")


KERNEL = ('%_exec_fprop.1 = f32[14,14,512,1]{3,2,1,0:T(8,128)} custom-call('
          'f32[16,16,512,1]{3,2,1,0:T(8,128)} %pad.0, f32[3,3,512,512]'
          '{3,2,1,0:T(8,128)S(1)} %custom-call), '
          'custom_call_target="tpu_custom_call"')
PAD = ('%pad.0 = f32[16,16,512,1]{3,2,1,0:T(8,128)} pad(f32[14,14,512,1]'
       '{3,2,1,0:T(8,128)} %custom-call, f32[] %constant), padding=1_1x1_1')
CONV = ('%convolution.2 = f32[8,8,4,2]{3,2,1,0} convolution(f32[8,8,3,2]'
        '{3,2,1,0} %p0, f32[3,3,3,4]{3,2,1,0} %p1), window={size=3x3}')
COPY = ('%copy-done = f32[3,3,512,512]{3,2,1,0:T(8,128)} copy-done(('
        'f32[3,3,512,512]{3,2,1,0:T(8,128)}, u32[]{:S(2)}) %copy-start)')


def _ev(name, s, e):
    return Event(name, s, e - s, [])


def _planes():
    ops = Line(trace_reduce.OPS_LINE, [
        _ev(KERNEL, 100, 300),
        _ev(PAD, 300, 350),
        _ev(CONV, 320, 400),
        _ev(COPY, 600, 700),
        _ev(COPY, 800, 900),            # after the window: left out
    ])
    device = Plane("/device:TPU:0", [Line("XLA Modules", []), ops])
    host = Plane("/host:CPU", [Line("main", [
        _ev(trace_reduce.WINDOW_SPAN, 50, 750),
        _ev("PjitFunction(_exec_fprop)", 420, 580),
        _ev("outer", 410, 590),
    ])])
    return [host, device]


def test_hand_built_trace():
    r = trace_reduce.reduce_planes(_planes(), chips=1)
    assert r["window_s"] == pytest.approx(700e-9)
    assert r["busy_s"] == pytest.approx(400e-9)       # [100,400) + [600,700)
    assert r["conv_s"] == pytest.approx(280e-9)       # kernel 200 + conv 80
    assert r["nonconv_s"] == pytest.approx(150e-9)    # pad 50 + copy 100
    assert r["idle_gaps"][0] == ["PjitFunction(_exec_fprop)",
                                 pytest.approx(200e-9)]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [200e-9, 50e-9, 50e-9])
    assert r["device_ops"][0] == ["%_exec_fprop.1 = f32[14,14,512,1]",
                                  pytest.approx(200e-9)]


def test_no_device_plane_reads_nothing():
    host = _planes()[0]
    assert trace_reduce.reduce_planes([host]) is None


def test_conv_ops_are_mosaic_calls_and_xla_convolutions():
    assert trace_reduce.opcode(KERNEL) == "custom-call"
    assert trace_reduce.opcode(COPY) == "copy-done"
    assert trace_reduce.is_conv(KERNEL) and trace_reduce.is_conv(CONV)
    # an operand named %custom-call does not make a pad or a copy a conv
    assert not trace_reduce.is_conv(PAD)
    assert not trace_reduce.is_conv(COPY)


def test_recorded_chip_trace():
    # 4 s of vgg16-fprop-b128 passes on one TPU v5e (bench/run.py --trace 1)
    r = trace_reduce.reduce_file(RECORDED, chips=1)
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] / r["window_s"] > 0.95       # one pass after another
    assert r["conv_s"] > 0 and r["nonconv_s"] > 0
    assert r["nonconv_s"] / r["busy_s"] < 0.1       # pads and copies
    top = r["device_ops"][0][0]
    assert top.startswith("%_exec_fprop") and "f32[224,224,64,128]" in top
    # the ops of one device run one at a time: their sum is the busy time
    assert r["conv_s"] + r["nonconv_s"] == pytest.approx(r["busy_s"],
                                                         rel=1e-3)
    assert len(r["device_ops"]) <= trace_reduce.TOP
    assert len(r["idle_gaps"]) <= trace_reduce.TOP
