"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``from_xplane`` keeps, from the ``.xplane.pb`` that ``jax.profiler``
writes, only the device operations (the ``XLA Ops`` line of every
``/device:TPU:n`` plane) and the benchmark's own host annotations
(``bench.*``), clipped to the ``bench.window`` annotation.  The result is
plain lists, so a trace recorded on the chip can be kept as a small test
fixture and every later PR computes the same numbers from it:

    {"window": [t0_ns, t1_ns], "devices": n,
     "ops": [[device, name, t0_ns, t1_ns, {stat: value}], ...],
     "annotations": [[name, t0_ns, t1_ns, {arg: value}], ...]}
"""
from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: the TPU names an op by its HLO text: "%decode_attention.5 = bf16[6,1,2048]..."
_HLO = re.compile(r"^%?([\w.\-]+) = ")
#: characters of the HLO text kept (the op's output shape comes first)
KEEP_TEXT = 160


def _plain(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def from_xplane(path, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, anns = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    m = _HLO.match(ev.name)
                    name = m.group(1) if m else ev.name[:KEEP_TEXT]
                    ops.append([dev, name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                {"long_name": ev.name[:KEEP_TEXT]}])
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        anns.append([ev.name[6:], int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     {k: _plain(v) for k, v in ev.stats}])
    win = [a for a in anns if a[0] == "window"]
    if not win:
        raise RuntimeError("the trace has no bench.window annotation")
    lo, hi = win[0][1], win[0][2]
    ops = [o for o in ops if o[3] > lo and o[2] < hi]
    _mark_parents(ops)
    anns = [a for a in anns if a[0] != "window" and a[2] > lo and a[1] < hi]
    return {"window": [lo, hi], "devices": n_devices, "ops": ops,
            "annotations": anns}


def _mark_parents(ops):
    """Flag ops that enclose others on their device (a ``while`` around
    its body), so the longest-ops list names the work, not its loop."""
    ops.sort(key=lambda o: (o[0], o[2], -o[3]))
    for a, b in zip(ops, ops[1:]):
        if a[0] == b[0] and b[2] < a[3] and b[3] <= a[3]:
            a[4]["parent"] = 1


def _union(intervals, lo, hi):
    """Merged, clipped, sorted intervals."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def window_s(tr: dict) -> float:
    return (tr["window"][1] - tr["window"][0]) * 1e-9


def busy_s(tr: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    lo, hi = tr["window"]
    total = 0
    for dev in range(tr["devices"]):
        total += sum(b - a for a, b in _union(
            [(o[2], o[3]) for o in tr["ops"] if o[0] == dev], lo, hi))
    return total * 1e-9 / tr["devices"]


def idle_share(tr: dict) -> float:
    """Percent of the window in which no operation ran on the device."""
    return 100.0 * (1.0 - busy_s(tr) / window_s(tr))


def idle_gaps(tr: dict, dev: int = 0):
    lo, hi = tr["window"]
    gaps, t = [], lo
    for a, b in _union([(o[2], o[3]) for o in tr["ops"] if o[0] == dev], lo, hi):
        if a > t:
            gaps.append((t, a))
        t = b
    if t < hi:
        gaps.append((t, hi))
    return gaps


def gaps_by_host_span(tr: dict, top: int = 10):
    """Idle seconds of device 0 by the innermost benchmark annotation
    around each gap's middle (``untraced`` where none is)."""
    tot = collections.Counter()
    anns = tr["annotations"]
    for a, b in idle_gaps(tr):
        mid = (a + b) / 2
        around = [x for x in anns if x[1] <= mid <= x[2]]
        name = min(around, key=lambda x: x[2] - x[1])[0] if around else "untraced"
        tot[name] += (b - a) * 1e-9
    return [[k, v] for k, v in tot.most_common(top)]


def top_ops(tr: dict, top: int = 10):
    """Device seconds by operation name (device 0), longest first; ops
    that enclose others are left out."""
    tot = collections.Counter()
    for o in tr["ops"]:
        if o[0] == 0 and not o[4].get("parent"):
            tot[o[1]] += (o[3] - o[2]) * 1e-9
    return [[k, v] for k, v in tot.most_common(top)]


def ops_matching(tr: dict, pred, dev: int = 0):
    """Device-0 ops for which ``pred(name, stats)`` holds."""
    return [o for o in tr["ops"] if o[0] == dev and pred(o[1], o[4])]


def within(ops, t0: int, t1: int):
    """Ops that start inside [t0, t1)."""
    return [o for o in ops if t0 <= o[2] < t1]


def annotations(tr: dict, name: str):
    return [a for a in tr["annotations"] if a[0] == name]


def breakdown(tr: dict) -> dict:
    return {"device_ops": top_ops(tr), "idle_gaps": gaps_by_host_span(tr)}
