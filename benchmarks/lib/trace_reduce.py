"""Profiler trace (``.xplane.pb``) → the numbers the per-layer readers use.

Run as a process of its own (``python trace_reduce.py <trace_dir>``, JSON
on stdout) AFTER the chip-owning child has exited: it imports jax for
``jax.profiler.ProfileData`` only, is started with ``JAX_PLATFORMS=cpu``
and touches no device. Checked on a small recorded trace in
``benchmarks/tests``.

Per device plane (``/device:TPU:<i>``): the ``XLA Ops`` line holds one
event per executed op, the ``XLA Modules`` line one per executed program.
busy = union of the op intervals; idle gaps = its complement inside the
traced interval; each of the longest gaps is named by the longest host
event (python / runtime threads of ``/host:CPU``) that overlaps it.
"""

from __future__ import annotations

import glob
import json
import os
import sys

import numpy as np

TOP = 10


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


def reduce_profile(pd, window_s=None) -> dict:
    dev = [p for p in pd.planes
           if p.name.startswith(("/device:TPU:", "/device:GPU:"))]
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    out = {"planes": [p.name for p in pd.planes], "devices": len(dev)}
    if not dev:
        return out
    busy, spans = [], []
    mod_rows: dict = {}
    op_rows: dict = {}
    gaps = []
    for i, plane in enumerate(dev):
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        mods = lines.get("XLA Modules")
        if mods is not None:
            mn, _ms, md = _events(mods)
            for n, c, t in _by_name(mn, md):
                r = mod_rows.setdefault(n, [0, 0.0])
                r[0] += c
                r[1] += t
        src = ops if ops is not None else mods
        if src is None:
            continue
        on, os_, od = _events(src)
        for n, c, t in _by_name(on, od):
            r = op_rows.setdefault(n, [0, 0.0])
            r[0] += c
            r[1] += t
        us, ue = _union(os_, os_ + od)
        busy.append(float((ue - us).sum()) * 1e-9)
        if len(us):
            spans.append((float(us[0]), float(ue[-1])))
        if i == 0 and len(us) > 1:
            g = us[1:] - ue[:-1]
            for j in np.argsort(g)[::-1][:TOP]:
                gaps.append((float(ue[j]), float(us[j + 1])))
    n = max(len(busy), 1)
    out["busy_s"] = sum(busy) / n
    out["traced_s"] = (max(e for _s, e in spans)
                       - min(s for s, _e in spans)) * 1e-9 if spans else 0.0
    out["window_s"] = float(window_s) if window_s else out["traced_s"]
    out["modules"] = [[k, v[0] / n, v[1] / n] for k, v in sorted(
        mod_rows.items(), key=lambda kv: -kv[1][1])][:40]
    out["device_ops"] = [[k, v[1] / n] for k, v in sorted(
        op_rows.items(), key=lambda kv: -kv[1][1])][:TOP]
    out["idle_gaps"] = _name_gaps(gaps, host)
    return out


def _name_gaps(gaps: list, host_planes: list) -> list:
    """Each gap → [what the host was doing, seconds]: the host event with
    the longest overlap; 'host: no event' where the trace has none."""
    if not gaps:
        return []
    names, start, end = [], [], []
    for plane in host_planes:
        for ln in plane.lines:
            n, s, d = _events(ln)
            names += [f"{ln.name.split('/')[0]}: {x}" for x in n]
            start.append(s)
            end.append(s + d)
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
    window_s = None
    done = os.path.join(trace_dir, "trace_done.json")
    if os.path.isdir(trace_dir) and os.path.exists(done):
        with open(done) as f:
            d = json.load(f)
        window_s = d["t_stop"] - d["t_start"]
    from jax.profiler import ProfileData
    out = reduce_profile(ProfileData.from_file(path), window_s)
    out["xplane_bytes"] = os.path.getsize(path)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
