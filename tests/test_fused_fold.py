"""FusedPhaseAttribute's fold: device groups whose rows cover a whole
window fold together, one size class at a time; the rest fold alone.
Checked against the one-group-at-a-time fold (the oracle below), and for
bit-identical sums however the groups are split between stages."""
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import tracing
from repro.fleet.pipeline import FusedPhaseAttributeStage, GriddedWindow


def _oracle_update(phases, sizes, t_prev, integrals, gw):
    """The per-group fold: every group, every window, one product per
    coverage pattern (invalid slots bridged, the first valid slot seeds
    zero-width)."""
    ph = np.asarray(phases, np.float64).reshape(-1, 2)
    a = ph[:, 0][:, None]
    b = ph[:, 1][:, None]
    lo = 0
    for d, k in enumerate(sizes):
        hi = lo + k
        m = gw.mask[lo:hi]
        anyv = m.any(axis=0)
        if anyv.any():
            sel = np.nonzero(anyv)[0]
            tv = gw.grid[sel]
            tp = t_prev[d]
            if not np.isfinite(tp):
                tp = tv[0]
            t_lo = np.concatenate([[tp], tv[:-1]])
            ov = np.clip(np.minimum(tv[None, :], b)
                         - np.maximum(t_lo[None, :], a), 0.0, None)
            mm = m[:, sel]
            vv = gw.values[lo:hi][:, sel].astype(np.float64) * mm
            bits = (1 << np.arange(k, dtype=np.int64))[:, None]
            pat = (mm * bits).sum(axis=0)
            for p in np.unique(pat):
                ps = pat == p
                acc = integrals[d].setdefault(int(p),
                                              np.zeros((len(ph), k)))
                acc += ov[:, ps] @ vv[:, ps].T
            t_prev[d] = tv[-1]
        lo = hi


# g0 5 rows, g1 3, g2 1 (its row starts late), g3 5 (row 2 ends early),
# g4 3 (row 1 drops out mid-window), g5 1, g6 1
SIZES = [5, 3, 1, 5, 3, 1, 1]
N_WIN = 6
# (batched, looped) per window, counted by hand:
#   w0 every group seeds (nothing seeded yet; g2 has no valid slot);
#   w1 g2 covers from a third of the window on (its seed);
#   w2 g4's row 1 drops out for four slots;
#   w3 every row of g0 drops out for three slots (the interval is
#      bridged) and g3's row 2 ends mid-window;
#   w4, w5 g3's row 2 is gone.
COUNTS = [(0, 7), (6, 1), (6, 1), (5, 2), (6, 1), (6, 1)]


def _row(d, j):
    return sum(SIZES[:d]) + j


def _windows(n_slots, seed=0):
    rng = np.random.default_rng(seed)
    n = sum(SIZES)
    dt = 0.01
    out = []
    for w in range(N_WIN):
        grid = 0.5 + dt * (w * n_slots + np.arange(n_slots))
        values = rng.uniform(50.0, 300.0, (n, n_slots)).astype(np.float32)
        mask = np.ones((n, n_slots), bool)
        third = n_slots // 3
        if w == 0:
            mask[_row(2, 0)] = False
        if w == 1:
            mask[_row(2, 0), :third] = False
        if w == 2:
            mask[_row(4, 1), 4:8] = False
        if w == 3:
            mask[_row(0, 0):_row(0, 5), 10:13] = False
            mask[_row(3, 2), n_slots // 2:] = False
        if w >= 4:
            mask[_row(3, 2)] = False
        out.append(GriddedWindow(lo=w * n_slots, grid=grid, values=values,
                                 mask=mask))
    return out


def _phases(n_phases, n_slots):
    """Phase edges off the grid, spanning a little past both ends."""
    t0, t1 = 0.5 - 0.013, 0.5 + 0.01 * N_WIN * n_slots + 0.007
    edges = np.linspace(t0, t1, n_phases + 1)
    return list(zip(edges[:-1], edges[1:]))


@pytest.mark.parametrize("n_slots,n_phases", [(16, 3), (37, 64)])
def test_fold_matches_per_group_oracle(n_slots, n_phases):
    phases = _phases(n_phases, n_slots)
    stage = FusedPhaseAttributeStage(phases, SIZES, None)
    t_prev = np.full((len(SIZES),), np.nan)
    integrals = [{} for _ in SIZES]
    batched = looped = 0
    for gw, (nb, nl) in zip(_windows(n_slots), COUNTS):
        assert stage.update(gw) is None
        _oracle_update(phases, SIZES, t_prev, integrals, gw)
        batched, looped = batched + nb, looped + nl
        assert (stage.groups_batched, stage.groups_looped) == \
            (batched, looped)
        np.testing.assert_array_equal(stage.carry.t_prev, t_prev)
        for d in range(len(SIZES)):
            got, want = stage.carry.integrals[d], integrals[d]
            assert sorted(got) == sorted(want)
            for p in want:
                assert got[p].shape == (n_phases, SIZES[d])
                np.testing.assert_allclose(got[p], want[p], rtol=1e-12,
                                           atol=0)
    # the patterns the partial windows leave: g3 without row 2
    assert sorted(stage.carry.integrals[3]) == [0b11011, 0b11111]
    stage.reset()
    assert (stage.groups_batched, stage.groups_looped) == (0, 0)
    assert all(not i for i in stage.carry.integrals)


def _split_fleet(seed):
    """40 groups of 5, 3 and 1 rows, some rows dropping in and out."""
    rng = np.random.default_rng(seed)
    sizes = list(rng.choice([5, 3, 1], size=40, p=[0.5, 0.2, 0.3]))
    n, n_slots = sum(sizes), 29
    windows = []
    for w in range(5):
        grid = 1.0 + 0.004 * (w * n_slots + np.arange(n_slots))
        values = rng.uniform(10.0, 400.0, (n, n_slots)).astype(np.float32)
        mask = rng.random((n, n_slots)) > 0.002
        windows.append(GriddedWindow(lo=w * n_slots, grid=grid,
                                     values=values, mask=mask))
    return sizes, windows


def _sub_window(gw, rows):
    return GriddedWindow(lo=gw.lo, grid=gw.grid, values=gw.values[rows],
                         mask=gw.mask[rows])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fold_bitwise_invariant_to_group_split(seed):
    """Each group's sums are the same bits whether its size class folds
    with all the fleet's groups or with a random, uneven half of them."""
    sizes, windows = _split_fleet(seed)
    phases = [(0.9, 1.13), (1.13, 1.31), (1.31, 1.7)]
    whole = FusedPhaseAttributeStage(phases, sizes, None)
    rng = np.random.default_rng(100 + seed)
    part_of = np.zeros(len(sizes), int)
    for k in (5, 3, 1):
        ids = np.flatnonzero(np.asarray(sizes) == k)
        rng.shuffle(ids)
        part_of[ids[:max(1, len(ids) // 3)]] = 1      # uneven halves
    off = np.concatenate([[0], np.cumsum(sizes)])
    parts = []
    for h in (0, 1):
        devs = np.flatnonzero(part_of == h)
        rows = np.concatenate([np.arange(off[d], off[d + 1]) for d in devs])
        st = FusedPhaseAttributeStage(phases, [sizes[d] for d in devs],
                                      None)
        parts.append((devs, rows, st))
    for gw in windows:
        whole.update(gw)
        for _, rows, st in parts:
            st.update(_sub_window(gw, rows))
    assert whole.groups_batched > 0 and whole.groups_looped > len(sizes)
    assert sum(st.groups_batched for *_, st in parts) == \
        whole.groups_batched
    for devs, _, st in parts:
        for j, d in enumerate(devs):
            assert st.carry.t_prev[j] == whole.carry.t_prev[d]
            got, want = st.carry.integrals[j], whole.carry.integrals[d]
            assert sorted(got) == sorted(want)
            for p in want:
                assert np.array_equal(got[p], want[p]), (d, p)


def test_fold_span_counts_groups(tmp_path):
    """Under a profiler session each update records one ``fleet.fold``
    span: ``n`` groups batched, the stat ``looped`` the rest."""
    phases = _phases(3, 16)
    stage = FusedPhaseAttributeStage(phases, SIZES, None)
    windows = _windows(16)
    tracing.PROGRAM.flush()
    with jax.profiler.trace(str(tmp_path)):
        for gw in windows:
            stage.update(gw)
    events = [e for e in tracing.PROGRAM.flush() if e.name == "fleet.fold"]
    assert [e.n for e in events] == [nb for nb, _ in COUNTS]
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    looped = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "repro.fleet.fold":
                    st = dict(ev.stats)
                    looped[st["span_id"]] = (st["n"], st["looped"])
    assert [looped[e.span_id] for e in events] == COUNTS
