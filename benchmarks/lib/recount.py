"""The plain reference and the comparison that decides ``correct``.

A numpy recount of the records the run itself sent — every pool buffer
weighted by how often the sender sent it — held against what the served
path answered after the window closed. Copied from
``chip_smoke.py:_check_ledger`` / ``_check_answers`` and extended to
multiplicities. It imports nothing of the program and takes nothing the
program made. Every comparison yields one NUMBER with its LIMIT; a run is
correct when every number is within its limit. The limits are the ones the
configuration file states (exact columns: 0; sketches: their documented
bounds; slab probe-failure odds), see ``PERF.md`` §2.
"""

from __future__ import annotations

import math

import numpy as np

from .proto import hexid


FOLDED_REL_LIMIT = 1e-5


class Numbers:
    """The numbers compared, each beside its limit, in order."""

    def __init__(self):
        self.rows: list = []

    def add(self, name: str, value, limit, note: str = "") -> None:
        value = float(value)
        ok = (not math.isnan(value)) and value <= float(limit)
        self.rows.append({"name": name, "value": value,
                          "limit": float(limit), "ok": bool(ok),
                          "note": note})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def table(self) -> dict:
        return {r["name"]: [r["value"], r["limit"]] for r in self.rows}

    def lines(self) -> list:
        return [f"check {r['name']}: {r['value']:.6g} (limit "
                f"{r['limit']:.6g}) {'ok' if r['ok'] else 'FAIL'}"
                + (f"  [{r['note']}]" if r["note"] and not r["ok"] else "")
                for r in self.rows]


# ------------------------------------------------------ weighted columns
def weighted(fleet, totals: dict, kind: str, only_slot=None) -> dict:
    """Concatenate the recount columns of every (socket, slot) buffer sent
    at least once, with a ``w`` column of how often."""
    cols: dict = {}
    ws = []
    for k, b in enumerate(fleet.bufs):
        for slot, m in enumerate(totals["rounds"][k]):
            if only_slot is not None and slot != only_slot:
                continue
            if m <= 0:
                continue
            part = b[kind][slot]
            for name, arr in part.items():
                cols.setdefault(name, []).append(arr)
            ws.append(np.full(len(next(iter(part.values()))), m, np.int64))
    if only_slot is None:
        # socket 0's warm-up pieces (lib/gen.py:_build_warm)
        for piece, m in totals["warm"].items():
            if m > 0 and piece in fleet.warm_cols:
                part = fleet.warm_cols[piece][0 if kind == "conn" else 1]
                for name, arr in part.items():
                    cols.setdefault(name, []).append(arr)
                ws.append(np.full(len(part["svc"]), m, np.int64))
    out = {name: np.concatenate(v) for name, v in cols.items()}
    out["w"] = np.concatenate(ws) if ws else np.zeros(0, np.int64)
    return out


def built_counters(fleet, totals: dict) -> dict:
    """What the server must have accepted, from the sender's totals."""
    conn = sum(sum(r) for r in totals["rounds"]) * fleet.conn_per
    resp = sum(sum(r) for r in totals["rounds"]) * fleet.resp_per
    for piece, m in totals["warm"].items():
        if piece in fleet.warm_cols:
            conn += m * len(fleet.warm_cols[piece][0]["svc"])
            resp += m * len(fleet.warm_cols[piece][1]["svc"])
    return {
        "conn_events": conn, "resp_events": resp,
        "listener_records": sum(
            n * len(b["listener"][0])
            for n, b in zip(totals["n_sweeps"], fleet.bufs))
        + totals["seq"] + totals["warm"].get("lst", 0) * fleet.warm_n["lst"],
        "host_records": sum(
            n * len(b["host"][0])
            for n, b in zip(totals["n_sweeps"], fleet.bufs))
        + totals["warm"].get("host", 0) * fleet.warm_n["host"]}


def weighted_order_stat(svc, val, w, q: float):
    """Per service the ⌈q·n⌉-th smallest of its (weighted) samples — the
    rank a loghist quantile resolves — and, where q·n sits on an integer
    within float32 reach, its lower neighbour too. → (uniq, n, hi, lo)."""
    order = np.lexsort((val, svc))
    k, v, ww = svc[order], val[order], w[order]
    uniq, start = np.unique(k, return_index=True)
    cum = np.cumsum(ww)
    base = np.concatenate([[0], cum[start[1:] - 1]])
    end = np.append(start[1:], len(v))
    n = cum[end - 1] - base
    qn = q * n.astype(np.float64)
    hi = np.clip(np.ceil(qn - 1e-4).astype(np.int64), 1, n)
    lo = np.clip(np.ceil(qn + 1e-4).astype(np.int64), 1, n)
    i_hi = np.searchsorted(cum, base + hi, side="left")
    i_lo = np.searchsorted(cum, base + lo, side="left")
    return uniq, n, v[i_hi], v[i_lo]


def _f32_sum_tol(n: int) -> float:
    return max(n, 1) * 2.0 ** -23 + 1e-6


# ------------------------------------------------------------- the checks
def compare(num: Numbers, fleet, totals: dict, answers: dict, c: dict,
            cfg: dict, ctx: dict) -> None:
    """``answers``: the check queries' answers; ``c``: selfstats counters
    after the tick that closed the check round; ``cfg``: the
    configuration file; ``ctx``: ``check_tick`` (the tick whose snapshot
    must answer), ``sample`` (host range of the sketch pull)."""
    eng, stated = cfg["engine"], cfg["guarantees"]
    pool = fleet.pool
    n_pr = len(fleet.probe_ids)
    probe = min(n_pr, totals["seq"])       # probe services ever written
    conn = weighted(fleet, totals, "conn")
    resp = weighted(fleet, totals, "resp")
    resp_chk = weighted(fleet, totals, "resp", only_slot=pool)
    lst = np.concatenate([b["listener"][totals["last_sweep"][k]]
                          for k, b in enumerate(fleet.bufs)])
    hst = np.concatenate([b["host"][totals["last_sweep"][k]]
                          for k, b in enumerate(fleet.bufs)])
    built = built_counters(fleet, totals)
    if built["conn_events"] != int(conn["w"].sum()) \
            or built["resp_events"] != int(resp["w"].sum()):
        raise AssertionError("the recount's columns and the sender's "
                             "totals disagree")

    # ---- which services have a row (a key finds no slot in the 16-probe
    # slab with probability load^16: allowed four times the expectation)
    rows = answers["fleet_exact"]["recs"]
    by_id = {r["svcid"]: r for r in rows}
    seeded = hexid(fleet.all_svc)
    extra = set(by_id) - set(seeded) - set(hexid(fleet.probe_ids))
    absent = set(seeded) - set(by_id)
    load = fleet.n_svcs / float(eng["svc_capacity"])
    allowed = int(4.0 * fleet.n_svcs * load ** 16 + 0.5)
    num.add("svc_rows_absent", len(absent), allowed,
            f"{len(by_id)} rows of {fleet.n_svcs} seeded")
    num.add("svc_rows_unknown", len(extra), 0)
    ids = [i for i in seeded if i not in absent]

    # ---- ledger: accepted == built, folded == accepted
    absent_u64 = np.array([int(i, 16) for i in absent], np.uint64)
    unknown = int(resp["w"][np.isin(resp["svc"], absent_u64)].sum())
    want = {**built, "engine_resp_unknown_svc": unknown,
            "engine_svc_rows_live": fleet.n_svcs + probe - len(absent),
            "frames_bad": 0, "records_unknown_subtype": 0,
            "conns_framing_errors": 0, "engine_dep_probe_failures": 0,
            "engine_dep_dropped": 0, "ref_fallback_decoded": 0,
            "tick_errors": 0}
    off = {k: (c.get(k, 0), v) for k, v in want.items()
           if float(c.get(k, 0) or 0) != float(v)}
    if c.get("native_decode_available") != 1.0:
        off["native_decode_available"] = (c.get("native_decode_available"),
                                          1.0)
    num.add("ledger_off", len(off), 0, f"(served, recount): {off}")
    # the device's own fold counters are float32 gauges: past 2^24
    # events they round at every addition, so they are held to the
    # accepted counts within float32's reach, not bit for bit (a slab
    # left out reads 1e-4 or more)
    gap = max(abs(float(c.get(k, 0) or 0) - built[e]) / max(built[e], 1)
              for k, e in (("engine_conn_folded", "conn_events"),
                           ("engine_resp_folded", "resp_events")))
    num.add("folded_rel_gap", gap, FOLDED_REL_LIMIT,
            f"conn {c.get('engine_conn_folded')} resp "
            f"{c.get('engine_resp_folded')} vs {built}")

    # ---- answers come from the snapshot of the tick that closed the
    # check round
    # check round (the 5 s columns of the sketch pull; every later
    # snapshot holds the same cumulative columns, no traffic follows)
    tick = answers["fleet_sketch"].get("snaptick")
    num.add("snaptick_off", int(tick != ctx["check_tick"]), 0,
            f"{tick} vs {ctx['check_tick']}")

    # ---- exact svcstate columns, every service
    lst_of = dict(zip(hexid(lst["glob_id"]), range(len(lst))))
    lrow = np.array([lst_of[i] for i in ids], np.int64)
    col = lambda name: np.array(                      # noqa: E731
        [by_id[i][name] for i in ids], np.float64)
    bad = (col("nconns") != lst["nconns"][lrow].astype(np.float64)) \
        | (col("hostid") != lst["host_id"][lrow].astype(np.float64))
    num.add("svc_exact_off", int(bad.sum()), 0,
            "nconns / hostid vs the last sweep, every service")
    if probe:
        # probe j's gauge is the last marker == j (mod n) that was sent
        seq = totals["seq"]
        last = {i: seq - ((seq - j) % n_pr)
                for j, i in enumerate(hexid(fleet.probe_ids))
                if seq - ((seq - j) % n_pr) > 0}
        num.add("probe_gauge_off",
                sum(by_id.get(i, {}).get("nconns") != v
                    for i, v in last.items()), 0,
                f"{len(last)} probe services vs their last markers")

    # ---- sketch columns, on the sampled hosts
    vmin, vmax, nb = stated["resp_loghist"]
    qtol = math.sqrt((vmax / vmin) ** (1.0 / nb)) - 1.0 + 1e-4
    srows = {r["svcid"]: r for r in answers["fleet_sketch"]["recs"]}
    h0, h1 = ctx["sample"]
    in_sample = set(hexid(lst["glob_id"][(lst["host_id"] >= h0)
                                         & (lst["host_id"] < h1)]))
    num.add("sketch_rows_off",
            len((in_sample - absent) ^ set(srows)), 0)
    # nqry5s: max(resp samples folded in the check round's window, the
    # sweep's own gauge), on the sampled hosts
    u, cnt = np.unique(resp_chk["svc"], return_counts=True)
    in_win = dict(zip(hexid(u), cnt))
    num.add("nqry5s_off", sum(
        r["nqry5s"] != max(in_win.get(i, 0),
                           int(lst["nqrys_5s"][lst_of[i]]))
        for i, r in srows.items() if i in lst_of), 0)

    def quantile_err(name, q, r):
        uq, _n, hi, lo = weighted_order_stat(
            r["svc"], np.clip(r["usec"].astype(np.float64), vmin, vmax),
            r["w"], q)
        keep = np.array([i in srows for i in hexid(uq)], bool)
        if not keep.any():
            return float("nan")
        uq, hi, lo = uq[keep], hi[keep], lo[keep]
        got = np.array([srows[i][name] for i in hexid(uq)]) * 1e3
        # answers carry msec to three decimals: half a microsecond of
        # representation on top of the bound
        err = np.minimum((np.abs(got - hi) - 0.5) / hi,
                         (np.abs(got - lo) - 0.5) / lo)
        return float(err.max())

    num.add("loghist_5d_err", max(quantile_err("p50resp5d", 0.5, resp),
                                  quantile_err("p95resp5d", 0.95, resp)),
            qtol, "p50/p95 over everything sent")
    num.add("loghist_5s_err",
            max(quantile_err("p95resp5s", 0.95, resp_chk),
                quantile_err("p99resp5s", 0.99, resp_chk)),
            qtol, "p95/p99 over the check round")
    v = np.clip(resp_chk["usec"].astype(np.float64), vmin, vmax)
    uq, inv = np.unique(resp_chk["svc"], return_inverse=True)
    mean = np.bincount(inv, weights=v) / np.bincount(inv)
    keep = np.array([i in srows for i in hexid(uq)], bool)
    got = np.array([srows[i]["resp5s"] for i in hexid(uq[keep])]) * 1e3
    num.add("loghist_mean_err",
            float(((np.abs(got - mean[keep]) - 0.5) / mean[keep]).max())
            if keep.any() else float("nan"), qtol)
    # distinct clients per service (HLL): the accuracy tests hold a
    # per-entity HLL to 10 %; a service here has tens to hundreds of
    # clients, so the relative bound is floored at 8 clients, and the
    # fleet as a whole stays inside 1.04 / sqrt(m)
    pair = np.unique(np.stack([conn["svc"],
                               conn["cli_ip"].astype(np.uint64)]), axis=1)
    u, cnt = np.unique(pair[0], return_counts=True)
    truth = dict(zip(hexid(u), cnt))
    sid = sorted(srows)
    want_c = np.array([truth.get(i, 0) for i in sid], np.float64)
    got_c = np.array([srows[i]["nclients"] for i in sid], np.float64)
    err = np.abs(got_c - want_c)
    num.add("hll_svc_err", float((err / np.maximum(0.1 * want_c, 8.0))
                                 .max()) if len(sid) else float("nan"),
            1.0, "worst service, in units of max(10%, 8 clients)")
    num.add("hll_fleet_err", float(err.sum() / max(want_c.sum(), 1.0)),
            1.04 / math.sqrt(1 << stated["hll_p_svc"]))

    # ---- filtered + sorted top-100 (tie order is not compared)
    rows = answers["top100"]["recs"]
    present = np.array([i not in absent for i in hexid(lst["glob_id"])])
    keep = (lst["nconns"] > 45) & (lst["host_id"] >= fleet.n_hosts // 2) \
        & present
    want_t = np.sort(lst["nconns"][keep].astype(np.float64))[::-1][:100]
    got_t = np.array([r["nconns"] for r in rows], np.float64)
    ok = len(rows) == len(want_t) and np.array_equal(got_t, want_t) \
        and all(r["hostid"] >= fleet.n_hosts // 2
                and r["svcid"] in lst_of
                and r["nconns"] == lst["nconns"][lst_of[r["svcid"]]]
                for r in rows)
    num.add("top100_off", 0 if ok else 1, 0)

    # ---- hoststate / clusterstate
    rows = answers["hoststate"]["recs"]
    hs = hst[np.argsort(hst["host_id"])]
    n_off = abs(len(rows) - fleet.n_hosts) + sum(
        not (r["hostid"] == h["host_id"] and r["nproc"] == h["ntasks"]
             and r["nprocissue"] == h["ntasks_issue"]
             and r["nlisten"] == h["nlisten"]
             and r["nlistissue"] == h["nlisten_issue"]
             and bool(r["cpuissue"]) == bool(h["cpu_issue"])
             and bool(r["memissue"]) == bool(h["mem_issue"]))
        for r, h in zip(rows, hs))
    cs = answers["clusterstate"]["recs"][0]
    states = [r["state"] for r in rows]
    n_off += int(cs["nhosts"] != fleet.n_hosts) + sum(
        cs[k] != states.count(name) for k, name in (
            ("nidle", "Idle"), ("ngood", "Good"), ("nok", "OK"),
            ("nbad", "Bad"), ("nsevere", "Severe"), ("ndown", "Down")))
    num.add("host_rows_off", n_off, 0)

    # ---- dependency graph: per-service conn counts, bytes, callers
    rows = answers["dep"]["recs"]
    u, inv = np.unique(conn["svc"], return_inverse=True)
    w = conn["w"].astype(np.float64)
    n_conn = np.bincount(inv, weights=w)
    n_bytes = np.bincount(inv, weights=w * conn["bytes"])
    edge = np.unique(np.stack([conn["svc"], conn["cli_task"]]), axis=1)
    ue, n_call = np.unique(edge[0], return_counts=True)
    t_conn = dict(zip(hexid(u), n_conn))
    t_bytes = dict(zip(hexid(u), n_bytes))
    t_call = dict(zip(hexid(ue), n_call))
    n_edges = edge.shape[1]
    eload = n_edges / float(cfg["runtime"]["dep_edge_capacity"])
    allowed = math.ceil(4.0 * n_edges * eload ** 16 - 1e-9)
    offr = [r for r in rows if r["nconn"] != t_conn.get(r["serid"])
            or r["ncallers"] != t_call.get(r["serid"])]
    wrong = [r for r in offr if not (
        r["serid"] in t_conn and r["nconn"] <= t_conn[r["serid"]]
        and r["ncallers"] <= t_call[r["serid"]])]
    num.add("dep_svcs_short", len(offr), allowed,
            "services short of their recount (edge slab probe odds)")
    num.add("dep_svcs_wrong", len(wrong) + abs(len(rows) - len(u)), 0,
            f"{len(rows)} rows vs {len(u)} services")
    short = {r["serid"] for r in offr}
    rel = max((abs(r["bytes"] - t_bytes[r["serid"]]) / t_bytes[r["serid"]]
               for r in rows
               if r["serid"] in t_bytes and r["serid"] not in short),
              default=1.0)
    num.add("dep_bytes_rel", rel, _f32_sum_tol(int(n_conn.max())))
    rows = answers["dep100"]["recs"]
    key = np.stack([conn["svc"], conn["cli_task"]])
    _eu, einv = np.unique(key, axis=1, return_inverse=True)
    ecnt = np.bincount(np.asarray(einv).reshape(-1), weights=w)
    want_e = np.sort(ecnt)[::-1][:100]
    got_e = np.array([r["nconn"] for r in rows], np.float64)
    num.add("dep_top100_off",
            0 if np.array_equal(got_e, want_e) else 1, 0)

    # ---- heavy hitters: weighted error of the 32 heaviest flows
    rows = [r for r in answers["topk"]["recs"] if r["metric"] == "bytes"]
    u, inv = np.unique(conn["flow"], return_inverse=True)
    tot = np.bincount(inv, weights=w * conn["bytes"])
    top = np.argsort(tot)[::-1][:32]
    got = {r["id"]: r["value"] for r in rows}
    num.add("topk_err",
            sum(abs(got.get(format(int(u[i]), "016x"), 0.0) - tot[i])
                for i in top) / tot[top].sum(),
            stated["topk_weighted_err"])

    # ---- serverstatus
    ss = answers["serverstatus"]["recs"][0]
    num.add("serverstatus_off",
            int(ss["nsvc"] != fleet.n_svcs + probe - len(absent))
            + int(ss["nhosts"] != fleet.n_hosts)
            + int(ss["connevents"] != built["conn_events"])
            + int(ss["respevents"] != built["resp_events"]), 0)


# ------------------------------------------- answers given in the window
def window_answers(num: Numbers, fleet, dash_log: list, polls: list,
                   markers: list) -> None:
    """Every dashboard answer of the window, by what it says: rows sorted
    as asked, every row inside its filter and carrying a gauge its
    service was sent in some sweep of the pool. Every freshness answer:
    the probe gauge never runs ahead of the markers sent by then, and
    never backwards."""
    sent = {}
    for b in fleet.bufs:
        for lst in b["listener"]:
            for i, v in zip(hexid(lst["glob_id"]), lst["nconns"]):
                sent.setdefault(i, set()).add(int(v))
    bad = 0
    for d in dash_log:
        a = d["answer"]
        if a is None:
            continue
        rows = a.get("recs", [])
        if d["name"] == "top100":
            vals = [r["nconns"] for r in rows]
            bad += int(len(rows) > 100 or vals != sorted(vals, reverse=True)
                       or any(r["nconns"] <= 45
                              or r["hostid"] < fleet.n_hosts // 2
                              or int(r["nconns"]) not in sent.get(
                                  r["svcid"], ()) for r in rows))
        elif d["name"] == "hoststate":
            hid = [r["hostid"] for r in rows]
            bad += int(hid != sorted(hid) or len(set(hid)) != len(hid)
                       or len(rows) > fleet.n_hosts)
        elif d["name"] == "clusterstate":
            cs = rows[0] if rows else {}
            bad += int(not rows or sum(cs.get(k, 0) for k in (
                "nidle", "ngood", "nok", "nbad", "nsevere", "ndown"))
                != cs.get("nhosts"))
        elif d["name"] == "dep100":
            vals = [r["nconn"] for r in rows]
            bad += int(len(rows) > 100
                       or vals != sorted(vals, reverse=True))
        elif d["name"] == "topk":
            bad += int(not rows)
    num.add("window_answers_bad", bad, 0,
            f"of {len(dash_log)} dashboard answers")
    # freshness answers: causal and monotone
    t_sent = dict(markers)
    order_bad = 0
    last_tick = last_gauge = -1
    for tick, gauge, t_recv in polls:
        if gauge > 0 and (gauge not in t_sent or t_sent[gauge] > t_recv):
            order_bad += 1
        if tick < last_tick or gauge < last_gauge:
            order_bad += 1
        last_tick, last_gauge = max(last_tick, tick), max(last_gauge, gauge)
    num.add("probe_order_bad", order_bad, 0,
            f"of {len(polls)} freshness answers")
