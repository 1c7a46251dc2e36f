"""Useful work of a convolution, counted from the configuration's own
geometry, and the least time a chip could take for it.

FLOPs are the multiply-adds the convolution needs, times two:
``2 * B * OC * outH * outW * IC * fltH * fltW``.  Lane padding, channel
padding and the taps that fall on zero padding are not counted, so a
kernel that pads is charged for its padding as lost time, never credited
with it.  Bytes are the unpadded input, filter and output, each read or
written once, in the configuration's dtype.

The least time is ``max(flops / peak_flops, bytes / peak_bandwidth)``, the
roofline of the device named in ``peaks.json``.
"""
from __future__ import annotations

import json
import os

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def out_hw(layer) -> int:
    return (layer["in_hw"] + 2 * layer["pad"] - layer["flt"]) // layer["stride"] + 1


def layer_flops(layer, batch: int) -> int:
    o = out_hw(layer)
    return 2 * batch * layer["OC"] * o * o * layer["IC"] * layer["flt"] ** 2


def layer_bytes(layer, batch: int, dtype: str = "float32") -> int:
    n_in = layer["in_hw"] ** 2 * layer["IC"] * batch
    n_flt = layer["flt"] ** 2 * layer["IC"] * layer["OC"]
    n_out = out_hw(layer) ** 2 * layer["OC"] * batch
    return _DTYPE_BYTES[dtype] * (n_in + n_flt + n_out)


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path}; have {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the roofline's least time and which side bounds it
    (``"compute"`` or ``"memory"``)."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def config_least_time(config, batch: int, peak: dict):
    """Per layer ``(name, flops, bytes, seconds, bound)`` at ``batch``."""
    rows = []
    for layer in config["layers"]:
        f = layer_flops(layer, batch)
        b = layer_bytes(layer, batch, config["dtype"])
        t, bound = least_time(f, b, peak)
        rows.append((layer["name"], f, b, t, bound))
    return rows
