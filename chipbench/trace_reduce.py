"""Profiler trace -> device busy time, per-op time and labelled idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with JAX's
own ``ProfileData``.  Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per executed operation.  Host planes
carry the benchmark's ``bench.<name>`` spans (``TraceAnnotation``), which
mark the measured window and label what the host was doing in each gap.

* busy: the union of operation intervals on each device inside the
  window, averaged over the devices used;
* per-op time: summed durations by operation name;
* idle gaps: the holes in that union, longest first, each named by the
  innermost ``bench.`` span open at its midpoint.
"""
from __future__ import annotations

from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def load_events(path) -> dict:
    """-> {"devices": {plane: [(op name, t0_ns, t1_ns)]},
           "spans": [(name, t0_ns, t1_ns)]} from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    t0 = int(ev.start_ns)
                    evs.append((ev.name, t0, t0 + int(ev.duration_ns)))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        t0 = int(ev.start_ns)
                        spans.append((ev.name[len(SPAN_PREFIX):], t0,
                                      t0 + int(ev.duration_ns)))
    return {"devices": devices, "spans": spans}


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce(events: dict, *, window_span: str = "window",
           n_devices: int = None, top: int = 10) -> dict:
    """Busy, per-op and gap summary of the ``window_span`` interval."""
    spans = events["spans"]
    win = [s for s in spans if s[0] == window_span]
    if not win:
        raise ValueError(f"trace holds no bench.{window_span} span")
    w0, w1 = win[0][1], win[0][2]
    window_s = (w1 - w0) * 1e-9
    planes = sorted(events["devices"])
    if n_devices is not None:
        planes = [p for p in planes
                  if int(p.rsplit(":", 1)[1]) < n_devices]
    busy_total = 0.0
    per_op: dict = {}
    gaps = []
    for p in planes:
        clipped = []
        for name, a, b in events["devices"][p]:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            per_op[name] = per_op.get(name, 0.0) + (b - a) * 1e-9
        merged = _merge(clipped)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    n = max(len(planes), 1)
    busy_s = busy_total / n
    inner = [s for s in spans if s[0] != window_span]

    def label(a, b):
        mid = (a + b) / 2
        open_ = [s for s in inner if s[1] <= mid <= s[2]]
        if not open_:
            return "window"
        return min(open_, key=lambda s: s[2] - s[1])[0]

    by_label: dict = {}
    for a, b in gaps:
        lab = label(a, b)
        by_label[lab] = by_label.get(lab, 0.0) + (b - a) * 1e-9 / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    gap_list = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "device_planes": len(planes),
            "idle_share": max(0.0, 1.0 - busy_s / window_s)
            if window_s > 0 else None,
            "op_seconds": {k: v / n for k, v in per_op.items()},
            "breakdown": {"device_ops": [[k, v / n] for k, v in ops[:top]],
                          "idle_gaps": [[k, v] for k, v in
                                        gap_list[:top]]}}


def busy_in(events: dict, intervals_ns) -> tuple:
    """(busy seconds, length seconds) of the device inside a union of
    host intervals, averaged over devices."""
    merged = _merge([list(iv) for iv in intervals_ns])
    length = sum(b - a for a, b in merged) * 1e-9
    planes = sorted(events["devices"])
    total = 0.0
    for p in planes:
        ops = _merge([[a, b] for _, a, b in events["devices"][p]])
        i = j = 0
        while i < len(ops) and j < len(merged):
            a = max(ops[i][0], merged[j][0])
            b = min(ops[i][1], merged[j][1])
            if b > a:
                total += (b - a) * 1e-9
            if ops[i][1] < merged[j][1]:
                i += 1
            else:
                j += 1
    return total / max(len(planes), 1), length

