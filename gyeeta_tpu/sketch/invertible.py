"""Invertible heavy-flow sketch: recover heavy keys FROM device state.

The aggregation sketches (CMS / top-K) answer "how much did key k
move?" but cannot enumerate the heavy keys themselves — the top-K table
only knows keys that survived its per-dispatch admission. This module
is the invertible tier of the heavy-hitter subsystem (PAPERS.md:
*A Fast and Compact Invertible Sketch for Network-Wide Heavy Flow
Detection*, arXiv 1910.10441; priority-aware admission per *PSketch*,
arXiv 2509.07338): a ``(depth, width)`` bucket array where each bucket
remembers ONE candidate key — the key with the highest CMS-estimate
priority that ever hashed there — so per-tick decoding recovers the
heavy keys directly from the sketch, no candidate list.

Bucket contents (struct-of-arrays, all ``(depth, width)``):

- ``prio``    — the candidate's priority at its last write (its CMS
  upper-bound estimate; the PSketch angle: hot flows hold buckets,
  cold flows share them). Priorities only grow, so each bucket
  converges to the heaviest-by-estimate key among its colliders.
- ``enc_hi``/``enc_lo`` — the candidate key halves, XOR-folded with a
  fingerprint-derived mask (see :func:`encode_key`): decoding XORs the
  mask back and a corrupted/torn bucket fails the fingerprint check
  instead of yielding a plausible-looking garbage key.
- ``fp``      — the candidate's 32-bit key fingerprint (independent
  hash stream), verified at decode together with the bucket position
  re-hash (a decoded key must hash INTO its own bucket).

Update is pure scatter-max / masked scatter-set — it rides the fused
``fold_all`` dispatch with zero extra dispatches. Bucket mass totals are deliberately NOT tracked: the
CMS next door already accounts every lane's mass, so a per-bucket
vsum would duplicate the most expensive scatter in the fold for a
signal the error bounds never read. The candidate-replacement write resolves a
unique winner per bucket via lexicographic (priority, key_hi, key_lo)
scatter-max rounds, so the result is order-insensitive within a batch
and bit-identical between the fused and legacy fold paths.

Decode (:func:`decode` / :func:`decode_keys`) is a read-only jitted
pass: un-fold the keys, verify fingerprint + bucket position, and
point-query the CMS for each candidate — one dispatch, one small
readback per tick. Recovered counts are CMS upper bounds; the honest
per-key error term is :func:`cms_error_term` (≤ 2·N/width with
probability 1−2^−depth per key — Markov per row, min over rows).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.utils import hashing as H

# independent hash streams: per-row bucket salts and the fingerprint
# stream must not be correlated with the CMS rows (0xC035/0x51ED) or
# the flow-key mix — a shared stream would make CMS collisions and
# bucket collisions coincide, defeating the min-over-rows verification
_SALT_BUCKET = 0x1B5E12A7
_SALT_FP = 0x7F4A7C15
_MASK_HI = 0xA5A5A5A5
_MASK_LO = 0x5A5A5A5A


class InvSketch(NamedTuple):
    prio: jnp.ndarray     # (d, w) f32 candidate priority (CMS estimate)
    enc_hi: jnp.ndarray   # (d, w) uint32 XOR-folded key high half
    enc_lo: jnp.ndarray   # (d, w) uint32 XOR-folded key low half
    fp: jnp.ndarray       # (d, w) uint32 candidate fingerprint
    n_hot: jnp.ndarray    # () f32 lanes at/above the hot threshold


def init(depth: int = 2, width: int = 4096) -> InvSketch:
    return InvSketch(
        prio=jnp.zeros((depth, width), jnp.float32),
        enc_hi=jnp.zeros((depth, width), jnp.uint32),
        enc_lo=jnp.zeros((depth, width), jnp.uint32),
        fp=jnp.zeros((depth, width), jnp.uint32),
        n_hot=jnp.zeros((), jnp.float32),
    )


def fingerprint(key_hi, key_lo):
    """32-bit key fingerprint on its own hash stream (np + jnp)."""
    return H.mix64(key_hi, key_lo, _SALT_FP)


def buckets(key_hi, key_lo, depth: int, width: int) -> list:
    """Per-row bucket indices on the invertible tier's own salts."""
    return [H.bucket_index(key_hi, key_lo, _SALT_BUCKET + r, width)
            for r in range(depth)]


def encode_key(key_hi, key_lo, fp):
    """XOR-fold the key halves with fingerprint-derived masks. A bucket
    whose (enc, fp) fields ever disagree (corruption, torn write)
    decodes to a key whose fingerprint cannot match — decode drops it
    instead of surfacing garbage."""
    if isinstance(fp, np.ndarray):
        with np.errstate(over="ignore"):
            return (key_hi ^ H.fmix32(fp ^ np.uint32(_MASK_HI)),
                    key_lo ^ H.fmix32(fp ^ np.uint32(_MASK_LO)))
    return (key_hi ^ H.fmix32(fp ^ jnp.uint32(_MASK_HI)),
            key_lo ^ H.fmix32(fp ^ jnp.uint32(_MASK_LO)))


def decode_key(enc_hi, enc_lo, fp):
    """Inverse of :func:`encode_key` (XOR is its own inverse)."""
    return encode_key(enc_hi, enc_lo, fp)


def update(sk: InvSketch, key_hi, key_lo, prio, valid,
           hot=None, budget: int = 0) -> InvSketch:
    """Fold a batch of key lanes with per-lane ``prio``.

    ``prio`` is the lane's admission priority — the CMS upper-bound
    estimate of its flow's cumulative mass (``countmin.upper_bound``
    issued after the batch's CMS fold), so a bucket's candidate is
    always the estimated-heaviest collider, not the last writer.
    ``hot``: optional bool mask counting lanes at/above the hot
    admission threshold (pure accounting — surfaced as a health gauge).

    ``budget``: sketch-assisted candidate compaction (the same trick
    as ``topk.update``): only the ``budget`` highest-priority lanes
    enter the candidate-write scatters — a lane can only WIN a bucket
    while its estimate ranks high, and duplicate lanes of one flow
    share its flow-level estimate, so the selection is flow-wise. Hot
    counting always sees every lane. 0 = every lane competes.

    All ops are scatters over the flattened (d·w) buffers; candidate
    replacement resolves one unique winner per bucket per batch via
    lexicographic (prio, key_hi, key_lo) scatter-max rounds — ties
    between duplicate lanes of ONE key write identical values, so the
    result never depends on scatter application order.
    """
    import jax

    d, w = sk.prio.shape
    key_hi = key_hi.astype(jnp.uint32)
    key_lo = key_lo.astype(jnp.uint32)
    pr = jnp.where(valid, prio.astype(jnp.float32), 0.0)
    n = key_hi.shape[0]
    n_hot = sk.n_hot
    if hot is not None:
        # full-batch accounting — counted BEFORE candidate compaction
        n_hot = n_hot + jnp.sum(valid & hot).astype(jnp.float32)
    if 0 < budget < n:
        score = jnp.where(valid, pr, -1.0)
        _, sel = jax.lax.top_k(score, budget)
        key_hi, key_lo = key_hi[sel], key_lo[sel]
        pr = jnp.where(score[sel] >= 0, pr[sel], 0.0)
        valid = valid[sel] & (score[sel] >= 0)
    bks = buckets(key_hi, key_lo, d, w)
    flat_idx = jnp.concatenate([b + r * w for r, b in enumerate(bks)])
    prio_new = sk.prio.reshape(-1).at[flat_idx].max(
        jnp.tile(pr, d)).reshape(d, w)

    fp_l = fingerprint(key_hi, key_lo)
    e_hi, e_lo = encode_key(key_hi, key_lo, fp_l)
    enc_hi, enc_lo, fps = sk.enc_hi, sk.enc_lo, sk.fp
    rows_ehi, rows_elo, rows_fp = [], [], []
    for r, b in enumerate(bks):
        # winners: lanes that achieved the bucket's NEW max priority
        # AND strictly raised it (an unchallenged incumbent stays put)
        win = valid & (pr == prio_new[r, b]) & (pr > sk.prio[r, b])
        # lexicographic tie-break between distinct keys at equal
        # priority: scatter-max key_hi among winners, then key_lo —
        # surviving winner lanes of one bucket all carry the SAME key
        mh = jnp.zeros((w,), jnp.uint32).at[b].max(
            jnp.where(win, key_hi, jnp.uint32(0)))
        win = win & (key_hi == mh[b])
        ml = jnp.zeros((w,), jnp.uint32).at[b].max(
            jnp.where(win, key_lo, jnp.uint32(0)))
        win = win & (key_lo == ml[b])
        lanes = jnp.where(win, b, w)          # w = dropped lane
        rows_ehi.append(enc_hi[r].at[lanes].set(e_hi, mode="drop"))
        rows_elo.append(enc_lo[r].at[lanes].set(e_lo, mode="drop"))
        rows_fp.append(fps[r].at[lanes].set(fp_l, mode="drop"))
    return InvSketch(
        prio=prio_new, enc_hi=jnp.stack(rows_ehi),
        enc_lo=jnp.stack(rows_elo), fp=jnp.stack(rows_fp),
        n_hot=n_hot)


def decode_keys(sk: InvSketch):
    """Un-fold every bucket's candidate → (khi, klo, ok), all (d, w).

    ``ok`` is the invertibility verification: the bucket is occupied,
    its decoded key's fingerprint matches the stored one, and the key
    re-hashes INTO its own bucket position on that row's hash stream —
    a corrupted bucket can pass neither check by accident (~2^-44).
    """
    d, w = sk.prio.shape
    khi, klo = decode_key(sk.enc_hi, sk.enc_lo, sk.fp)
    ok = (sk.prio > 0) & (fingerprint(khi, klo) == sk.fp)
    pos = jnp.arange(w, dtype=jnp.int32)
    for r in range(d):
        ok = ok.at[r].set(
            ok[r] & (H.bucket_index(khi[r], klo[r], _SALT_BUCKET + r, w)
                     == pos))
    return khi, klo, ok


def decode(sk: InvSketch, cms):
    """Full recovery pass: decoded candidates + their CMS point
    estimates, flattened to (d·w,) host-ready arrays. One jitted
    dispatch; the caller reads back four small arrays per tick."""
    from gyeeta_tpu.sketch import countmin

    khi, klo, ok = decode_keys(sk)
    hi_f, lo_f = khi.reshape(-1), klo.reshape(-1)
    est = countmin.query(cms, hi_f, lo_f).astype(jnp.float32)
    est = jnp.where(ok.reshape(-1), est, 0.0)
    return {"hh_hi": hi_f, "hh_lo": lo_f, "hh_ok": ok.reshape(-1),
            "hh_est": est}


def merge(a: InvSketch, b: InvSketch) -> InvSketch:
    """Bucket-wise merge: the higher-priority candidate wins each
    bucket (same rule as the streaming update); n_hot adds."""
    take_b = b.prio > a.prio
    return InvSketch(
        prio=jnp.maximum(a.prio, b.prio),
        enc_hi=jnp.where(take_b, b.enc_hi, a.enc_hi),
        enc_lo=jnp.where(take_b, b.enc_lo, a.enc_lo),
        fp=jnp.where(take_b, b.fp, a.fp),
        n_hot=a.n_hot + b.n_hot)


def cms_error_term(total_mass, width: int):
    """Per-key CMS overestimate bound: err ≤ 2·N/width w.p. 1−2^−depth
    (Markov per row at the halving point, min over rows). This is the
    "invertible-array error term" every recovered topk row carries —
    recovered counts are upper bounds; exact top-K lanes carry the
    ``evicted`` undercount bound instead."""
    return 2.0 * total_mass / max(int(width), 1)


def merge_recovered_np(rec: dict, err_term: float,
                       hot_thresh: float = 0.0):
    """Host half of per-tick recovery: merge the exact top-K lanes with
    the decoded candidates → the heavy-flow view every query edge
    serves.

    ``rec``: the numpy readback of :func:`gyeeta_tpu.engine.step.
    heavy_recover` (topk_hi/lo/counts/est + hh_hi/lo/ok/est). Every
    row's value is an UPPER bound on the key's true total (it never
    undercounts, w.p. 1−2^−depth), with the overcount bounded by the
    row's own ``errbound``:

    - exact lanes: truth ∈ [count, est] — value = max(count, est) with
      errbound = value − count. The exact counter's job is TIGHTENING
      the bound: the longer a key stays admitted, the closer count
      tracks est and the smaller its error bar.
    - recovered-only candidates: value = est with errbound =
      ``err_term`` (the invertible-array term, :func:`cms_error_term`).

    Returns ``(flow_rows, recovered_ids, hot_ids)``: rows as
    ``(id_hex, value, errbound, source)`` heaviest-first (value desc,
    id asc on ties — deterministic across runs), the recovered key-id
    set, and the recovered ids at/above ``hot_thresh`` (the promotion
    candidates).
    """
    t_hi = np.asarray(rec["topk_hi"], np.uint64)
    t_lo = np.asarray(rec["topk_lo"], np.uint64)
    t_cnt = np.asarray(rec["topk_counts"], np.float64)
    t_est = np.asarray(rec["topk_est"], np.float64)
    m = t_cnt > 0
    exact_ids = (t_hi[m] << np.uint64(32)) | t_lo[m]
    rows = []
    for k, cnt, est in zip(exact_ids.tolist(), t_cnt[m].tolist(),
                           t_est[m].tolist()):
        val = max(cnt, est)
        rows.append((format(int(k), "016x"), float(val),
                     float(val - cnt), "exact"))
    exact_set = set(exact_ids.tolist())

    c_ok = np.asarray(rec["hh_ok"], bool)
    c_hi = np.asarray(rec["hh_hi"], np.uint64)[c_ok]
    c_lo = np.asarray(rec["hh_lo"], np.uint64)[c_ok]
    c_est = np.asarray(rec["hh_est"], np.float64)[c_ok]
    cand = {}
    for k, v in zip(((c_hi << np.uint64(32)) | c_lo).tolist(),
                    c_est.tolist()):
        if v > 0 and k not in exact_set:
            cand[k] = max(cand.get(k, 0.0), v)
    recovered_ids = set(cand)
    hot_ids = {k for k, v in cand.items() if v >= hot_thresh} \
        if hot_thresh > 0 else set(recovered_ids)
    rows.extend((format(k, "016x"), float(v), float(err_term),
                 "recovered") for k, v in cand.items())
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows, recovered_ids, hot_ids


# ---------------------------------------------------------------- numpy ref
def np_update(prio, enc_hi, enc_lo, fp, key_hi, key_lo, prios):
    """Host reference of one batch fold (tests): per bucket, the
    lexicographic-max (prio, key_hi, key_lo) lane wins, and replaces
    the incumbent only when it strictly raises the stored priority —
    the batch-level rule the vectorized scatters implement."""
    d, w = prio.shape
    key_hi = np.asarray(key_hi, np.uint32)
    key_lo = np.asarray(key_lo, np.uint32)
    bks = buckets(key_hi, key_lo, d, w)
    with np.errstate(over="ignore"):
        fps = np.asarray(fingerprint(key_hi, key_lo))
        e_hi, e_lo = encode_key(key_hi, key_lo, fps)
    for r in range(d):
        b = np.asarray(bks[r])
        per_bucket: dict = {}
        for i in range(len(key_hi)):
            j = int(b[i])
            cand = (float(prios[i]), int(key_hi[i]), int(key_lo[i]), i)
            if j not in per_bucket or cand[:3] > per_bucket[j][:3]:
                per_bucket[j] = cand
        for j, (p, _hi, _lo, i) in per_bucket.items():
            if p > prio[r, j]:
                prio[r, j] = p
                enc_hi[r, j] = e_hi[i]
                enc_lo[r, j] = e_lo[i]
                fp[r, j] = fps[i]
    return prio, enc_hi, enc_lo, fp
