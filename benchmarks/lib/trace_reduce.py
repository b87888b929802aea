"""Profiler trace (``.xplane.pb``) → the numbers the per-layer readers use.

Run as a process of its own (``python trace_reduce.py <trace_dir>``, JSON
on stdout) AFTER the chip-owning child has exited: it imports jax for
``jax.profiler.ProfileData`` only, is started with ``JAX_PLATFORMS=cpu``
and touches no device. Checked on a small recorded trace in
``benchmarks/tests``.

Per device plane (``/device:TPU:<i>``): the ``XLA Ops`` line holds one
event per executed op, the ``XLA Modules`` line one per executed program.
The window is the ``bench_window`` host event that ``lib/child.py`` opens
around the traced sleep, on the trace's own clock (the profiler records
past it while ``stop_trace`` collects). busy = union of the op intervals
clipped to the window; idle gaps = its complement inside the window; each
of the longest gaps is named by the longest host event (python / runtime
threads of ``/host:CPU``) that overlaps it. A program's execution counts,
whole, when it starts inside the window (``modules``); its overlap with
the window is what a share of the window reads (``module_window_s``).
"""

from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np

TOP = 10
WINDOW_MARKER = "bench_window"      # opened by lib/child.py:_trace


def _union(start: np.ndarray, end: np.ndarray):
    """Sorted disjoint intervals covering the union → (starts, ends)."""
    if not len(start):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def _events(line):
    names, start, dur = [], [], []
    for ev in line.events:
        names.append(ev.name)
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
    return names, np.asarray(start, np.float64), np.asarray(dur, np.float64)


def _by_name(names, dur) -> list:
    tot: dict = {}
    cnt: dict = {}
    for n, d in zip(names, dur):
        tot[n] = tot.get(n, 0.0) + d
        cnt[n] = cnt.get(n, 0) + 1
    return sorted(((n, cnt[n], t * 1e-9) for n, t in tot.items()),
                  key=lambda x: -x[2])


def _marker(host_planes: list):
    """(start, end) ns of the host event ``WINDOW_MARKER``; the longest
    where there are several; None where the trace has none."""
    best = None
    for plane in host_planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == WINDOW_MARKER and (
                        best is None or ev.duration_ns > best[1] - best[0]):
                    best = (float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns))
    return best


def _mods_in(names, start, dur, w0, w1) -> list:
    """(name, executions, seconds, seconds inside [w0, w1]) per program:
    the executions that START inside the window, each with its whole
    duration, so a mean per execution keeps its meaning; and every
    execution's overlap with the window, so a share of the window cannot
    pass 100 %."""
    starts = (start >= w0) & (start < w1)
    overlap = (np.minimum(start + dur, w1) - np.maximum(start, w0)).clip(0)
    rows: dict = {}
    for n, s, d, o in zip(names, starts, dur, overlap):
        r = rows.setdefault(n, [0, 0.0, 0.0])
        if s:
            r[0] += 1
            r[1] += d * 1e-9
        r[2] += o * 1e-9
    return [(n, *r) for n, r in rows.items()]


def reduce_profile(pd) -> dict:
    """Every number over ONE interval on the trace's own clock: the
    ``bench_window`` marker's, or, in a trace without one, the device ops'
    own span (``window_from`` says which). Device ops are clipped to it
    before the union, so busy_s <= window_s by construction."""
    dev = [p for p in pd.planes
           if p.name.startswith(("/device:TPU:", "/device:GPU:"))]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    out = {"planes": [p.name for p in pd.planes], "devices": len(dev)}
    if not dev:
        return out
    per_dev = []
    for plane in dev:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        mods = lines.get("XLA Modules")
        src = ops if ops is not None else mods
        if src is None:
            continue
        per_dev.append((_events(src), _events(mods) if mods is not None
                        else None))
    # the device's own span: a program's event opens a few ns before its
    # first op's
    spans = []
    for pair in per_dev:
        for ev in pair:
            if ev is not None and len(ev[1]):
                spans.append((ev[1].min(), (ev[1] + ev[2]).max()))
    if not spans:
        return out
    traced = (min(a for a, _b in spans), max(b for _a, b in spans))
    window = _marker(host)
    out["window_from"] = "span" if window is None else "marker"
    w0, w1 = traced if window is None else window
    busy, before, after = [], [], []
    mod_rows: dict = {}
    op_rows: dict = {}
    gaps = []
    for i, ((on, os_, od), mods) in enumerate(per_dev):
        if mods is not None:
            for n, c, t, tin in _mods_in(*mods, w0, w1):
                r = mod_rows.setdefault(n, [0, 0.0, 0.0])
                r[0] += c
                r[1] += t
                r[2] += tin
        # what the trace holds outside the window, left out below
        us, ue = _union(os_, os_ + od)
        before.append(float((np.minimum(ue, w0) - us).clip(0).sum()) * 1e-9)
        after.append(float((ue - np.maximum(us, w1)).clip(0).sum()) * 1e-9)
        cs, ce = np.maximum(os_, w0), np.minimum(os_ + od, w1)
        keep = ce > cs
        for n, c, t in _by_name(np.asarray(on, object)[keep],
                                (ce - cs)[keep]):
            r = op_rows.setdefault(n, [0, 0.0])
            r[0] += c
            r[1] += t
        us, ue = _union(cs[keep], ce[keep])
        busy.append(float((ue - us).sum()) * 1e-9)
        if i == 0:
            # idle inside the window, its leading and trailing stretch too
            g0, g1 = np.append(w0, ue), np.append(us, w1)
            for j in np.argsort(g1 - g0)[::-1][:TOP]:
                if g1[j] > g0[j]:
                    gaps.append((float(g0[j]), float(g1[j])))
    n = len(busy)
    out["busy_s"] = sum(busy) / n
    out["window_s"] = float(w1 - w0) * 1e-9
    out["traced_s"] = float(traced[1] - traced[0]) * 1e-9
    out["busy_outside_s"] = [sum(before) / n, sum(after) / n]
    out["modules"] = [[k, v[0] / n, v[1] / n] for k, v in sorted(
        mod_rows.items(), key=lambda kv: -kv[1][1])[:40] if v[0]]
    out["module_window_s"] = {k: v[2] / n for k, v in sorted(
        mod_rows.items(), key=lambda kv: -kv[1][2])[:40] if v[2]}
    out["device_ops"] = [[k, v[1] / n] for k, v in sorted(
        op_rows.items(), key=lambda kv: -kv[1][1])][:TOP]
    out["idle_gaps"] = _name_gaps(gaps, host)
    return out


def _name_gaps(gaps: list, host_planes: list) -> list:
    """Each gap → [what the host was doing, seconds]: the host event with
    the longest overlap, the window's own marker left out; 'host: no
    event' where the trace has none."""
    if not gaps:
        return []
    names, start, end = [], [], []
    for plane in host_planes:
        for ln in plane.lines:
            n, s, d = _events(ln)
            keep = np.asarray([x != WINDOW_MARKER for x in n], bool)
            names += [f"{ln.name.split('/')[0]}: {x}"
                      for x, k in zip(n, keep) if k]
            start.append(s[keep])
            end.append((s + d)[keep])
    if names:
        start, end = np.concatenate(start), np.concatenate(end)
    out = []
    for g0, g1 in gaps:
        label = "host: no event"
        if names:
            ov = np.minimum(end, g1) - np.maximum(start, g0)
            j = int(ov.argmax())
            if ov[j] > 0:
                label = names[j]
        out.append([label[:120], (g1 - g0) * 1e-9])
    return out


def newest_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def main(argv) -> int:
    trace_dir = argv[1]
    path = newest_xplane(trace_dir) if os.path.isdir(trace_dir) \
        else trace_dir
    if path is None:
        print(json.dumps({"error": "no .xplane.pb under " + trace_dir}))
        return 1
    from jax.profiler import ProfileData
    out = reduce_profile(ProfileData.from_file(path))
    out["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
