#!/usr/bin/env python3
"""The builder's chip calls, as commands that can be read and run again.

Not part of a benchmark run: the driver runs ``benchmarks/run.py`` alone.
Run from the root of a checkout on the machine with the chip, as
``chiprun -- python3 benchmarks/tools/chip.py <sub-command> ...``; every
run's output goes to ``<out>/<tag>.out`` / ``.err`` and one summary line
per run to standard output.

``runs``   a list of seeds of one cell, one after the other (the last one
           traced with ``--trace-last``); ``--fault`` plants a fault of
           ``lib/faults.py``; ``--set group.key=value`` runs the cell with
           its configuration file changed for these runs (a control: the
           program with a sketch narrower than the configuration states),
           and puts the file back.
``sweep``  the cell at each rate of ``--rates`` (events/s, highest first)
           until one is flat — ingest within 1.5 % of the offered rate,
           ``fresh_lag_ms`` under 1 s, ``query_p90_ms`` under 0.5 s — then
           the remaining seeds at that rate. The rate is written into the
           workload file with the per-socket message sizes that keep one
           message of each kind per socket per second.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def run_one(cell, seed, seconds, trace, out_dir, tag, extra=()) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(int(trace)), *extra]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    with open(os.path.join(out_dir, tag + ".out"), "w") as f:
        f.write(p.stdout)
    with open(os.path.join(out_dir, tag + ".err"), "w") as f:
        f.write(p.stderr)
    res = {}
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        res = json.loads(lines[-1])
    e2e = {}
    for ln in p.stderr.splitlines():
        if "] e2e {" in ln:
            e2e = json.loads(ln.split("] e2e ", 1)[1])
    bad = {k: v for k, v in res.get("checks", {}).items() if v[0] > v[1]}
    summary = {
        "tag": tag, "rc": p.returncode, "wall_s": round(
            time.monotonic() - t0, 1), "correct": res.get("correct"),
        "e2e": e2e, "metrics": {k: v["value"] for k, v in
                                res.get("metrics", {}).items()},
        "device": res.get("device"), "failed_checks": bad}
    print(json.dumps(summary), flush=True)
    return summary


def set_rate(cell: str, rate: int, n_sockets: int) -> None:
    """``rate`` events/s as one conn and one resp message per socket per
    second (conn : resp = 1 : 2)."""
    path = os.path.join(BENCH, "workloads", cell + ".json")
    with open(path) as f:
        wl = json.load(f)
    per = rate // n_sockets
    conn = per // 3
    wl["round"] = {"conn_per_socket": conn, "resp_per_socket": per - conn}
    wl["rate_events_per_s"] = per * n_sockets
    with open(path, "w") as f:
        json.dump(wl, f, indent=1)


def flat(s: dict, rate: float) -> bool:
    e = s["e2e"]
    return bool(s["correct"]) \
        and e.get("ingest_events_per_s", 0) >= 0.985 * rate \
        and e.get("fresh_lag_ms", 1e9) <= 1000.0 \
        and e.get("query_p90_ms", 1e9) <= 500.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("runs", "sweep"))
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    ap.add_argument("--tag", default="run")
    ap.add_argument("--trace-last", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="runs: group.key=value in the configuration file")
    ap.add_argument("--rates", default="", help="sweep: comma-separated")
    ap.add_argument("--sockets", type=int, default=512)
    args, rest = ap.parse_known_args()   # the rest goes to run.py as it is
    seeds = [int(s) for s in args.seeds.split(",")]
    extra = (["--fault", args.fault] if args.fault else []) + rest
    tag = lambda seed: f"{args.cell}_{args.tag}_{seed}"      # noqa: E731

    if args.mode == "sweep":
        rates = [int(r) for r in args.rates.split(",")]
        chosen = None
        while rates and seeds and chosen is None:
            rate, seed = rates.pop(0), seeds.pop(0)
            set_rate(args.cell, rate, args.sockets)
            s = run_one(args.cell, seed, args.seconds, False, args.out,
                        f"{args.cell}_{args.tag}_r{rate}_{seed}", rest)
            if flat(s, rate):
                chosen = rate
        print(json.dumps({"flat_rate": chosen}), flush=True)
        with open(os.path.join(BENCH, "workloads", args.cell + ".json")) as f:
            wl_text = f.read()
        with open(os.path.join(args.out, args.cell + ".workload.json"),
                  "w") as f:
            f.write(wl_text)
        if chosen is None:
            return 1

    cfg_path = saved = None
    if args.set:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            b = json.load(f)
        cname = {w["name"]: w["config"] for w in b["workloads"]}[args.cell]
        cfg_path = os.path.join(ROOT, {c["name"]: c["file"]
                                       for c in b["configs"]}[cname])
        with open(cfg_path) as f:
            saved = f.read()
        cfg = json.loads(saved)
        for kv in args.set:
            key, val = kv.split("=", 1)
            group, name = key.split(".", 1)
            cfg[group][name] = json.loads(val)
        with open(cfg_path, "w") as f:
            json.dump(cfg, f, indent=1)
    try:
        for i, seed in enumerate(seeds):
            run_one(args.cell, seed, args.seconds,
                    args.trace_last and i == len(seeds) - 1, args.out,
                    tag(seed), extra)
    finally:
        if saved is not None:
            with open(cfg_path, "w") as f:
                f.write(saved)
    return 0


if __name__ == "__main__":
    sys.exit(main())
