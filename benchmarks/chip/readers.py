"""Arithmetic the per-layer metric readers share.  Each takes the reduced
trace (``trace_reduce.reduce``) and the run's facts, and returns a share in
percent, or None where the trace holds nothing to read.

The facts (``bench.run_cell``): ``chips``; ``traced_units``, the units of
work the traced window completed, ``grad_ticks`` and ``exchanges``, its
gradient ticks and pairwise exchanges; ``flops_per_unit`` and
``parameters`` of the configuration; ``workers_per_chip``; the device's
``peak_flops`` and ``peak_hbm``; and the configuration ``cfg`` and
``traffic`` as run, for a reader that counts its kernel's FLOPs or bytes
with the configuration's own functions."""
from __future__ import annotations


def idle_share(r: dict, facts: dict):
    """1 minus the union of device-op intervals over the traced window."""
    if r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def mfu(r: dict, facts: dict):
    """Model FLOPs of the forward and backward passes of the work the
    traced window completed, over the window, over the chips' peak."""
    if facts["traced_units"] <= 0 or r["window_s"] <= 0:
        return None
    rate = facts["traced_units"] * facts["flops_per_unit"] / r["window_s"]
    return 100.0 * rate / (facts["chips"] * facts["peak_flops"])


def gossip_roofline(r: dict, facts: dict):
    """The gossip kernels' least HBM traffic (x and x~ of every worker on
    the chip read once and written once: 16 bytes per f32 parameter per
    worker) over their summed device time, over the HBM peak."""
    if r["gossip_calls"] == 0 or r["gossip_s"] <= 0:
        return None
    moved = 16.0 * facts["workers_per_chip"] * facts["parameters"] \
        * r["gossip_calls"]
    return 100.0 * moved / r["gossip_s"] / facts["peak_hbm"]

