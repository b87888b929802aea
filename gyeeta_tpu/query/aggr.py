"""Aggregation queries: groupby + sum/avg/min/max/count/pNN.

The reference builds aggregated SQL per subsystem (``get_select_aggr_query``
+ custom groupby, ``common/gy_query_common.cc:736-754``; per-subsystem
``web_db_aggr_*`` handlers, ``server/gy_mnodehandle.cc:1083``). Here one
aggregation engine serves both execution paths:

- **live**: the filtered columnar snapshot is grouped host-side (numpy per
  group) — the live path is already one device readback, aggregation is
  arithmetic on its columns;
- **historical**: exact-translatable queries push SUM/AVG/MIN/MAX/COUNT +
  GROUP BY down into partition SQL; percentile ops or inexact filters fall
  back to fetching the filtered rows and running the *same* numpy
  aggregator — one semantics, two speeds (the dual-execution discipline of
  ``common/gy_query_criteria.h`` extended to aggregation).

Spec syntax (JSON): ``{"aggr": ["avg(qps5s)", "p95(p95resp5s) as p",
"count(*)"], "groupby": ["hostid"], "step": 300}`` — ``step`` (historical
only) buckets time into N-second groups, the reference's downsampling
interval.
"""

from __future__ import annotations

import collections
import re
from typing import NamedTuple, Optional

import numpy as np

from gyeeta_tpu.query import fieldmaps

_SPEC_RE = re.compile(
    r"^\s*(sum|avg|min|max|count|p(\d{1,2}(?:\.\d+)?))"
    r"\(\s*(\*|\w+)\s*\)"
    r"(?:\s+as\s+(\w+))?\s*$", re.IGNORECASE)

# ops with a direct sqlite form (percentiles are numpy-only)
_SQL_OPS = {"sum": "SUM", "avg": "AVG", "min": "MIN", "max": "MAX",
            "count": "COUNT"}


class AggrSpec(NamedTuple):
    op: str                  # sum|avg|min|max|count|pNN
    field: str               # json field name, or "*" (count only)
    alias: str               # output column name
    pct: Optional[float] = None


def parse_aggr(spec: str, subsys: str) -> AggrSpec:
    m = _SPEC_RE.match(spec)
    if not m:
        raise ValueError(
            f"bad aggregation {spec!r}; want op(field) [as alias] with op "
            f"in sum/avg/min/max/count/pNN")
    op, pct, field, alias = m.groups()
    op = op.lower()
    fmap = fieldmaps.field_map(subsys)
    if field == "*":
        if not op.startswith("count"):
            raise ValueError(f"{spec!r}: only count(*) may use '*'")
    else:
        fd = fmap.get(field)
        if fd is None:
            raise ValueError(f"unknown field {field!r} in {spec!r}")
        if op != "count" and fd.kind not in ("num", "bool"):
            raise ValueError(
                f"{spec!r}: cannot {op} over non-numeric field {field!r}")
    pctv = float(pct) if pct else None
    if pctv is not None:
        op = "pct"
    return AggrSpec(op=op, field=field,
                    alias=alias or spec.strip().replace(" ", ""),
                    pct=pctv)


def parse_groupby(groupby, subsys: str) -> tuple:
    fmap = fieldmaps.field_map(subsys)
    out = []
    for g in groupby or ():
        if g == "time":          # historical step-bucket pseudo-column
            out.append(g)
            continue
        if g not in fmap:
            raise ValueError(f"unknown groupby field {g!r}")
        out.append(g)
    return tuple(out)


def _apply(spec: AggrSpec, vals: np.ndarray) -> float:
    if spec.op == "count":
        return float(len(vals))
    if len(vals) == 0:
        return 0.0
    v = vals.astype(np.float64)
    if spec.op == "sum":
        return float(np.sum(v))
    if spec.op == "avg":
        return float(np.mean(v))
    if spec.op == "min":
        return float(np.min(v))
    if spec.op == "max":
        return float(np.max(v))
    if spec.op == "pct":
        return float(np.percentile(v, spec.pct))
    raise AssertionError(spec.op)


def aggregate_rows(rows: list, specs: list, groupby: tuple) -> list:
    """Group + aggregate row dicts (shared by live & history fallback).

    ``rows`` values are presentation-domain (enum strings etc.); groupby
    labels pass through as-is, aggregated fields must be numeric.
    """
    groups = collections.defaultdict(list)
    for r in rows:
        key = tuple(r.get(g) for g in groupby)
        groups[key].append(r)
    if not groups and not groupby:
        # global aggregate over zero rows still yields one row (SQL
        # aggregate-without-GROUP-BY semantics; _apply gives the zeros)
        groups[()] = []
    out = []
    for key, members in groups.items():
        rec = dict(zip(groupby, key))
        for s in specs:
            if s.field == "*":
                rec[s.alias] = float(len(members))
                continue
            vals = np.array([m[s.field] for m in members
                             if m.get(s.field) is not None], np.float64)
            rec[s.alias] = _apply(s, vals)
        out.append(rec)
    return out


# np.sum adds pairwise from this many addends on and left to right
# below it (np.add.reduceat does neither: first + sum of the rest)
_PAIRWISE_MIN = 8


def _segments(keycols: list, n: int):
    """Group ``n`` rows by their key columns → (order, starts, rank):
    ``order`` sorts the rows group by group, each group's rows in their
    given order; ``starts`` are the groups' first positions in it;
    ``rank`` lists the groups by first occurrence, the order a dict of
    key tuples would give. Numeric keys sort (one lexsort); anything
    else (names, enums rendered as text) is grouped by that dict."""
    if all(k.dtype.kind in "biuf" for k in keycols):
        order = np.lexsort(tuple(reversed(keycols)))
        new = np.zeros(n, bool)
        new[:1] = True
        for k in keycols:
            ks = k[order]
            new[1:] |= ks[1:] != ks[:-1]
        starts = np.flatnonzero(new)
        # the sort is stable: a group's first sorted row is its first row
        return order, starts, np.argsort(order[starts], kind="stable")
    seen: dict = {}
    gid = np.fromiter(
        (seen.setdefault(k, len(seen))
         for k in zip(*[k.tolist() for k in keycols])), np.int64, n)
    order = np.argsort(gid, kind="stable")
    starts = np.searchsorted(gid[order], np.arange(len(seen)))
    return order, starts, np.arange(len(seen))


def _reduce(spec: AggrSpec, v, starts, ends) -> np.ndarray:
    """One aggregation over every segment of ``v`` → float64 per
    segment, bit-equal to :func:`_apply` on each segment."""
    count = (ends - starts).astype(np.float64)
    if spec.op == "count":
        return count
    if spec.op in ("sum", "avg"):
        # np.sum's own order of additions: the k-th addend of every
        # short segment in one pass, long segments one by one
        out = v[starts]
        for k in range(1, _PAIRWISE_MIN - 1):
            at = np.flatnonzero((count > k) & (count < _PAIRWISE_MIN))
            if not len(at):
                break
            out[at] += v[starts[at] + k]
        for j in np.flatnonzero(count >= _PAIRWISE_MIN):
            out[j] = np.sum(v[starts[j]:ends[j]])
        return out if spec.op == "sum" else out / count
    if spec.op == "min":
        return np.minimum.reduceat(v, starts)
    if spec.op == "max":
        return np.maximum.reduceat(v, starts)
    return np.array([_apply(spec, v[a:b]) for a, b in zip(starts, ends)],
                    np.float64)


def aggregate_columns(cols: dict, idx: np.ndarray, specs: list,
                      groupby: tuple, fmap: dict, sortcol=None,
                      sortdesc: bool = True, maxrecs=None) -> tuple:
    """Columnar group-aggregate over selected row indices (live path)
    → (the first ``maxrecs`` group rows in ``sortcol`` order, the number
    of groups). Groups come in first-occurrence order and the sort is
    stable, as a dict of key tuples and ``list.sort`` would give them.
    A lazy string column that names its key words (``LazyCols.keys_of``)
    is grouped on those, and its label rendered for the groups returned
    only (unless the sort itself is on that label)."""
    from gyeeta_tpu.query.lazycols import rows_of

    n = len(idx)
    keys_of = getattr(cols, "keys_of", {})
    if groupby:
        keycols = [np.asarray(cols[c])[idx] for g in groupby
                   for c in keys_of.get(fmap[g].col, (fmap[g].col,))]
        order, starts, rank = _segments(keycols, n)
    else:
        # a global aggregate over zero matches still yields one (zero)
        # row — the SQL path and aggregate_rows agree on this shape
        order, starts = np.arange(n), np.zeros(1, np.int64)
        rank = np.zeros(1, np.int64)
    ends = np.append(starts[1:], n)[:len(starts)]
    sel = idx[order]
    vals = {}
    for s in specs:
        if s.field == "*":
            vals[s.alias] = (ends - starts).astype(np.float64)[rank]
        elif n == 0:
            vals[s.alias] = np.zeros(len(rank))
        else:
            v = np.asarray(cols[fmap[s.field].col])[sel]
            vals[s.alias] = _reduce(s, v.astype(np.float64), starts,
                                    ends)[rank]
    first = sel[starts[rank]] if n else np.zeros(0, np.int64)

    def labels(g, rows):
        fd = fmap[g]
        got = rows_of(cols, [fd.col], rows)[fd.col].tolist()
        return [fd.to_json(kv) for kv in got] if fd.to_json else got

    pick = np.arange(len(rank))
    if sortcol in vals:
        key = vals[sortcol]
        pick = np.argsort(-key if sortdesc else key, kind="stable")
    elif sortcol:
        lab = labels(sortcol, first)
        pick = np.asarray(sorted(pick, key=lab.__getitem__,
                                 reverse=sortdesc), np.int64)
    pick = pick[:maxrecs]
    out = [{} for _ in pick]
    for g in groupby:
        for rec, lab in zip(out, labels(g, first[pick])):
            rec[g] = lab
    for alias, v in vals.items():
        for rec, x in zip(out, v[pick].tolist()):
            rec[alias] = x
    return out, len(rank)


def sql_pushdown(specs: list, groupby: tuple, step: Optional[float],
                 bucket_expr: Optional[str] = None):
    """(select_exprs, group_exprs) for the exact-SQL fast path, or None
    when any op needs numpy (percentiles). ``bucket_expr`` is the
    backend's floor-division time-bucket SQL (CAST truncates in sqlite
    but ROUNDS in Postgres — each store supplies the form that floors,
    matching the numpy path's ``time // step * step``)."""
    sel, grp = [], []
    for g in groupby:
        if g == "time":
            if not step:
                raise ValueError("groupby 'time' needs a 'step' seconds")
            expr = (bucket_expr or
                    "CAST(time/{step} AS INTEGER)*{step}").format(
                step=float(step))
            sel.append(f"{expr} AS time")
            grp.append(expr)
        else:
            sel.append(g)
            grp.append(g)
    for s in specs:
        if s.op not in _SQL_OPS:
            return None
        arg = "*" if s.field == "*" else s.field
        sel.append(f"{_SQL_OPS[s.op]}({arg}) AS \"{s.alias}\"")
    return sel, grp
