"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes and keeps a
small record: the harness's host spans (``window``, ``schedule``,
``dispatch``, ``wait``) and, for each TPU, its operations as
[name, start_ns, duration_ns, kind] (the name up to its HLO text's
" = "), where kind is ``gossip`` for the A2CiD2 gossip kernels and ``op``
for anything else.  Host spans and device operations share the profiler's
clock.  ``reduce`` turns that record into busy and idle time and kernel
time over the traced window, and device time and calls of every op by
name; given the compiled program's scope maps (``scopes.maps``), device
time per replay scope and per model scope too.  A loop is busy time, but
its op time and kernel calls are those of the ops in its body: only ops
that hold no other op are counted one by one.
"""
from __future__ import annotations

import collections
import glob
import re

from scopes import UNSCOPED, scope_seconds

HOST_SPANS = ("window", "schedule", "dispatch", "wait")
GOSSIP = re.compile(r"a2cid2_")


def _kind(name: str, detail: str) -> str:
    if GOSSIP.search(name) or GOSSIP.search(detail):
        return "gossip"
    return "op"


def _detail(event) -> str:
    """The event's text stats (HLO long name, custom-call config), where
    a Pallas kernel's name can sit when the op is named by its HLO."""
    out = []
    for _, value in getattr(event, "stats", ()):
        if isinstance(value, str):
            out.append(value[:400])
    return " ".join(out)


def extract(trace_dir: str) -> dict:
    """The compact record of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    data = ProfileData.from_file(paths[0])
    host, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name in HOST_SPANS]
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            ops = lines.get("XLA Ops")
            if ops is None:
                continue
            devices.append({"name": plane.name, "ops": [
                [e.name.split(" = ")[0], e.start_ns, e.duration_ns,
                 _kind(e.name, _detail(e))] for e in ops.events]})
    return {"host": sorted(host, key=lambda s: s[1]), "devices": devices}


def _leaves(ops):
    """The ops that hold no other op: a while loop or a conditional is
    traced as one op around the ops of its body."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    holder, stack = set(), []
    def end(i):
        return ops[i][1] + ops[i][2]
    for i in order:
        while stack and end(stack[-1]) <= ops[i][1]:
            stack.pop()
        if stack and end(i) <= end(stack[-1]):
            holder.add(stack[-1])
        stack.append(i)
    return [ops[i] for i in range(len(ops)) if i not in holder]


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def reduce(record: dict, scopes=None) -> dict:
    """Window, busy and kernel times, in seconds, per chip and averaged,
    plus the breakdown of device time and idle gaps.

    ``op_s`` and ``op_calls`` hold the device seconds and calls of every
    op name, averaged over chips as ``gossip_s`` is.  With ``scopes``
    (``scopes.ScopeMaps``), ``scope_s`` holds the device seconds of each
    replay scope (``scopes.scope_seconds``) and ``model_s`` those of each
    model scope the program marks."""
    windows = [s for s in record["host"] if s[0] == "window"]
    if not windows or not record["devices"]:
        raise ValueError("the trace holds no window span or no device ops")
    lo = windows[0][1]
    hi = windows[0][1] + windows[0][2]
    spans = [s for s in record["host"] if s[0] in ("dispatch", "wait",
                                                   "schedule")]
    per_chip = []
    op_time = collections.Counter()
    op_calls = collections.Counter()
    gaps = []
    for dev in record["devices"]:
        live = [o for o in dev["ops"] if o[1] + o[2] > lo and o[1] < hi]
        busy = _union(_clip([[o[1], o[1] + o[2]] for o in live], lo, hi))
        ops = _leaves(live)
        gossip = [o for o in ops if o[3] == "gossip"]
        per_chip.append({
            "busy_s": _length(busy) * 1e-9,
            "gossip_s": sum(o[2] for o in gossip) * 1e-9,
            "gossip_calls": len(gossip),
        })
        for o in ops:
            op_time[o[0]] += o[2] * 1e-9 / len(record["devices"])
            op_calls[o[0]] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                what = next((sp[0] for sp in spans
                             if sp[1] <= mid <= sp[1] + sp[2]), "host")
                gaps.append([what, (e - s) * 1e-9])
    n = len(per_chip)
    out = {k: sum(c[k] for c in per_chip) / n for k in per_chip[0]}
    out["window_s"] = (hi - lo) * 1e-9
    out["chips"] = n
    out["breakdown"] = {
        "device_ops": [[k, v] for k, v in op_time.most_common(10)],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}
    out["op_s"] = dict(op_time)
    out["op_calls"] = {k: v / n for k, v in op_calls.items()}
    if scopes is not None:
        out["scope_s"] = scope_seconds(record, scopes.replay)
        out["model_s"] = {k: v for k, v in
                          scope_seconds(record, scopes.model).items()
                          if k != UNSCOPED}
    return out
