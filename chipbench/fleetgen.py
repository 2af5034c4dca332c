"""Fleet sensor traces from a seed: the paper's three-stage measurement
model (sensor production, driver publication, tool sampling), vectorised.

A copy of the repository's sensor simulator (``core/sensors.py``,
``NodeFabric.sample_all``) kept with the benchmark so that the inputs do
not change when the program does.  It draws from the same per-sensor
random streams in the same order, so it yields the same traces; what
differs is speed: the IIR filter steps every filtered sensor of the
fleet at once instead of one sample at a time in Python.

Everything is plain numpy and reads the deployment from the
configuration file (sensor inventory, tool, node model, phase schedule).
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


# ---------------------------------------------------------------------------
# piecewise-constant ground truth
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Piecewise:
    """Right-open segments [times[i], times[i+1]) at watts[i]."""
    times: np.ndarray
    watts: np.ndarray

    @property
    def t0(self) -> float:
        return float(self.times[0])

    @property
    def t1(self) -> float:
        return float(self.times[-1])

    def power_at(self, t):
        t = np.asarray(t, np.float64)
        idx = np.clip(np.searchsorted(self.times, t, side="right") - 1,
                      0, len(self.watts) - 1)
        return self.watts[idx]

    def energy_between(self, t_a, t_b):
        t_a = np.asarray(t_a, np.float64)
        t_b = np.asarray(t_b, np.float64)
        edges = self.times
        cum = np.concatenate([[0.0], np.cumsum(self.watts
                                               * np.diff(edges))])

        def cum_at(t):
            tc = np.clip(t, edges[0], edges[-1])
            idx = np.clip(np.searchsorted(edges, tc, side="right") - 1,
                          0, len(self.watts) - 1)
            return cum[idx] + self.watts[idx] * (tc - edges[idx])

        return cum_at(t_b) - cum_at(t_a)

    def average_power(self, t_a, t_b):
        return self.energy_between(t_a, t_b) / np.maximum(t_b - t_a, 1e-12)


def merge_sum(parts, extra=0.0) -> Piecewise:
    times = np.unique(np.concatenate([p.times for p in parts]))
    mids = (times[:-1] + times[1:]) / 2.0
    return Piecewise(times, sum(p.power_at(mids) for p in parts) + extra)


def square_wave(cfg: dict) -> Piecewise:
    """Idle lead, ``cycles`` periods of active/idle halves, idle tail."""
    w = cfg["workload"]
    period, edge = float(w["period_s"]), float(w["edge_s"])
    cycles = int((float(w["capture_s"]) - 2 * edge) // period)
    idle, active = float(w["p_idle_w"]), float(w["p_active_w"])
    times, watts = [0.0, edge], [idle]
    t = edge
    for _ in range(cycles):
        times += [t + period / 2, t + period]
        watts += [active, idle]
        t += period
    times.append(t + edge)
    watts.append(idle)
    return Piecewise(np.asarray(times), np.asarray(watts))


def phases(cfg: dict) -> list:
    """[(name, a, b)]: the square wave's halves, edges excluded."""
    w = cfg["workload"]
    period, edge = float(w["period_s"]), float(w["edge_s"])
    cycles = int((float(w["capture_s"]) - 2 * edge) // period)
    half = period / 2
    return [(f"{'active' if k % 2 == 0 else 'idle'}{k // 2}",
             edge + k * half, edge + (k + 1) * half)
            for k in range(2 * cycles)]


def node_truth(cfg: dict, chip_truth: Piecewise, name: str) -> Piecewise:
    """Ground truth a sensor observes (chips all follow ``chip_truth``)."""
    node = cfg["node_model"]
    chips = [chip_truth] * int(cfg["devices_per_node"])
    if name.startswith(("chip", "pm_accel")):
        return chip_truth
    if name == "pm_cpu_power":
        total = merge_sum(chips)
        act = (total.watts - total.watts.min()) \
            / max(total.watts.max() - total.watts.min(), 1.0)
        return Piecewise(total.times,
                         node["cpu_idle_w"]
                         + node["cpu_activity"] * node["host_cpu_w"] * act)
    if name == "pm_memory_power":
        return Piecewise(np.asarray([chip_truth.t0, chip_truth.t1]),
                         np.asarray([node["ddr_w"]]))
    if name == "pm_node_power":
        cpu = node_truth(cfg, chip_truth, "pm_cpu_power")
        return merge_sum(chips + [cpu], extra=node["ddr_w"]
                         + node["n_nics"] * node["nic_w"])
    raise KeyError(name)


# ---------------------------------------------------------------------------
# the three stages
# ---------------------------------------------------------------------------

def _jittered_grid(t0, t1, interval, jitter, rng):
    n = int((t1 - t0) / interval) + 2
    steps = interval + rng.normal(0.0, jitter, n)
    steps = np.maximum(steps, interval * 0.25)
    t = t0 + np.cumsum(steps)
    return t[t < t1]


def iir_rows(te_rows, seg_rows, taus, y0s, t0s):
    """y_i = a_i y_{i-1} + (1 - a_i) p_i, a_i = exp(-(t_i - t_{i-1})/tau),
    stepped for every row at once (rows of any length)."""
    n = len(te_rows)
    lens = np.asarray([len(t) for t in te_rows])
    order = np.argsort(-lens, kind="stable")
    width = int(lens.max()) if n else 0
    te = np.zeros((n, width))
    seg = np.zeros((n, width))
    for j, r in enumerate(order):
        te[j, :lens[r]] = te_rows[r]
        seg[j, :lens[r]] = seg_rows[r]
    slens = lens[order]
    tau = np.asarray(taus, np.float64)[order]
    y = np.asarray(y0s, np.float64)[order].copy()
    prev = np.asarray(t0s, np.float64)[order].copy()
    out = np.zeros((n, width))
    alive = n
    for i in range(width):
        while alive and slens[alive - 1] <= i:
            alive -= 1
        t = te[:alive, i]
        a = np.exp(-np.maximum(t - prev[:alive], 0.0) / tau[:alive])
        y[:alive] = a * y[:alive] + (1 - a) * seg[:alive, i]
        out[:alive, i] = y[:alive]
        prev[:alive] = t
    return [out[np.nonzero(order == r)[0][0], :lens[r]] for r in range(n)]


@dataclasses.dataclass
class Trace:
    """One tool-sampled stream (the program's ``SensorTrace`` fields)."""
    name: str
    spec: dict
    node: int
    t_read: np.ndarray
    t_measured: np.ndarray
    value: np.ndarray


def sensor_specs(cfg: dict, names=None) -> list:
    """The per-node sensor inventory, in the configuration's order."""
    specs = [dict(s) for s in cfg["sensors"]]
    if names is not None:
        specs = [s for s in specs if s["name"] in set(names)]
    return specs


def sample_nodes(cfg: dict, seed: int, nodes, names=None) -> list:
    """Traces for ``nodes`` (node ids), node-major, inventory order."""
    tool = dict(cfg["tool"])
    specs = sensor_specs(cfg, names)
    truth = square_wave(cfg)
    t0, t1 = truth.t0, truth.t1
    eff = tool["sample_interval_s"] \
        + tool["overhead_s_per_read"] * len(specs)
    pending = []
    iir = []
    for node in nodes:
        for spec in specs:
            tr_truth = node_truth(cfg, truth, spec["name"])
            rng = np.random.default_rng(
                (zlib.crc32(spec["name"].encode())
                 ^ (seed * 1000003 + node)) & 0x7FFFFFFF)
            tm = _jittered_grid(t0, t1, spec["production_interval_s"],
                                spec["production_jitter_s"], rng)
            d = spec.get("delay_s", 0.0)
            te = np.maximum(tm - d, t0) if d else tm
            if spec.get("drift_ppm", 0.0):
                tm = tm + (tm - t0) * (spec["drift_ppm"] * 1e-6)
            item = [node, spec, rng, tm, te, tr_truth, None]
            kind, filt = spec["kind"], spec.get("filter_kind", "none")
            win = spec.get("filter_window_s", 0.0)
            if kind == "energy_cum":
                e = tr_truth.energy_between(t0, te) * spec["scale"] \
                    + spec["offset_w"] * (te - t0)
                ticks = np.floor(e / spec["quantum"])
                if spec.get("wrap_bits", 0):
                    ticks = np.mod(ticks, 2.0 ** spec["wrap_bits"])
                item[6] = ticks * spec["quantum"]
            elif filt == "ma" and win > 0:
                lo = np.maximum(te - win, t0)
                item[6] = tr_truth.energy_between(lo, te) \
                    / np.maximum(te - lo, 1e-9)
            elif filt == "iir" and win > 0:
                seg = tr_truth.average_power(
                    np.concatenate([[t0], te[:-1]]), te)
                iir.append((len(pending), te, seg, win,
                            float(tr_truth.power_at(t0)), t0))
            else:
                item[6] = tr_truth.power_at(te)
            pending.append(item)
    if iir:
        ys = iir_rows([x[1] for x in iir], [x[2] for x in iir],
                      [x[3] for x in iir], [x[4] for x in iir],
                      [x[5] for x in iir])
        for (k, *_), y in zip(iir, ys):
            pending[k][6] = y
    out = []
    for node, spec, rng, tm, te, tr_truth, val in pending:
        if spec["kind"] != "energy_cum":
            val = val * spec["scale"] + spec["offset_w"]
            if spec.get("noise_w", 0.0):
                val = val + rng.normal(0.0, spec["noise_w"], len(val))
            if spec["quantum"]:
                val = np.round(val / spec["quantum"]) * spec["quantum"]
        t_rep = tm + rng.normal(0.0, spec["timestamp_jitter_s"], len(tm))
        # stage 2: driver publication
        tp = _jittered_grid(t0, t1, spec["driver_refresh_s"],
                            spec["driver_jitter_s"], rng)
        idx = np.searchsorted(t_rep, tp, side="right") - 1
        keep = idx >= 0
        tp, tmp, vp = tp[keep], t_rep[idx[keep]], val[idx[keep]]
        # stage 3: tool reads
        tr = _jittered_grid(t0, t1, eff, tool["sample_jitter_s"], rng)
        if tool.get("drop_prob", 0.0) > 0:
            tr = tr[rng.random(len(tr)) > tool["drop_prob"]]
        idx = np.searchsorted(tp, tr, side="right") - 1
        keep = idx >= 0
        tr, idx = tr[keep], idx[keep]
        out.append(Trace(spec["name"], spec, node, tr, tmp[idx],
                         vp[idx]))
    return out


def wrap_period(spec: dict) -> float:
    if spec.get("wrap_range_j", 0.0) > 0.0:
        return float(spec["wrap_range_j"])
    if spec.get("wrap_bits", 0):
        return (2.0 ** spec["wrap_bits"]) * spec["quantum"]
    return 0.0


def corrections(cfg: dict) -> tuple:
    """({sensor name: offset W}, {sensor name: slope}) per node, from the
    configuration's NIC-rail correction set."""
    c = cfg["corrections"]
    offsets, slopes = {}, {}
    for chip in c["nic_rail_chips"]:
        for kind in ("power", "energy"):
            offsets[f"pm_accel{chip}_{kind}"] = float(c["nic_w"])
    for chip in range(int(cfg["devices_per_node"])):
        for kind in ("power", "energy"):
            slopes[f"pm_accel{chip}_{kind}"] = float(c["pm_slope"])
    return offsets, slopes


def group_rows(traces: list, cfg: dict, fused: bool) -> list:
    """Attribution groups as lists of trace indices.

    fused: one group per device (its chip and tray sensors, counters
    first) and one per node-scope sensor; otherwise every trace alone.
    """
    if not fused:
        return [[i] for i in range(len(traces))]
    groups = []
    by_node: dict = {}
    for i, tr in enumerate(traces):
        by_node.setdefault(tr.node, []).append(i)
    for node in sorted(by_node):
        idx = by_node[node]
        for c in range(int(cfg["devices_per_node"])):
            grp = [i for i in idx if traces[i].name.startswith(
                (f"chip{c}_", f"pm_accel{c}_"))]
            grp.sort(key=lambda i: (traces[i].spec["kind"] != "energy_cum",
                                    traces[i].name))
            if grp:
                groups.append(grp)
        groups += [[i] for i in idx if traces[i].spec["scope"] == "node"]
    return groups
