"""Sketch accuracy vs exact CPU references (mirrors test_histogram.cc /
test_quantiles.cc fixtures, SURVEY §4 item 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gyeeta_tpu.sketch import countmin, exact, hyperloglog as hll, loghist, tdigest, topk
from gyeeta_tpu.utils import hashing as H


def _keys(rng, n, distinct=None):
    if distinct is None:
        distinct = n
    pool_hi = rng.integers(0, 2**32, distinct, dtype=np.uint32)
    pool_lo = rng.integers(0, 2**32, distinct, dtype=np.uint32)
    idx = rng.integers(0, distinct, n)
    return pool_hi[idx], pool_lo[idx]


# ------------------------------------------------------------------- CMS
def test_cms_point_estimates_upper_bound(rng):
    n, d = 50_000, 2000
    hi, lo = _keys(rng, n, distinct=d)
    vals = rng.exponential(100.0, n).astype(np.float32)
    sk = countmin.init(depth=4, width=1 << 14)
    upd = jax.jit(countmin.update)
    sk = upd(sk, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(vals))
    truth = exact.key_totals(hi, lo, vals)
    uh = np.unique((hi.astype(np.uint64) << np.uint64(32)) | lo)
    q_hi = (uh >> np.uint64(32)).astype(np.uint32)
    q_lo = (uh & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    est = np.asarray(countmin.query(sk, jnp.asarray(q_hi), jnp.asarray(q_lo)))
    true_v = np.array([truth[int(k)] for k in uh])
    # CMS never underestimates
    assert (est >= true_v - 1e-3).all()
    # average overestimate small vs total mass
    overshoot = (est - true_v).mean()
    assert overshoot < vals.sum() * 2.0 / (1 << 14) + 1.0
    # total preserved
    assert np.isclose(float(countmin.total(sk)), vals.sum(), rtol=1e-5)


def test_cms_merge_is_psum(rng):
    hi1, lo1 = _keys(rng, 1000)
    hi2, lo2 = _keys(rng, 1000)
    v1 = np.ones(1000, np.float32)
    v2 = np.full(1000, 2.0, np.float32)
    a = countmin.update(countmin.init(2, 1 << 10), jnp.asarray(hi1),
                        jnp.asarray(lo1), jnp.asarray(v1))
    b = countmin.update(countmin.init(2, 1 << 10), jnp.asarray(hi2),
                        jnp.asarray(lo2), jnp.asarray(v2))
    m = countmin.merge(a, b)
    both = countmin.update(a, jnp.asarray(hi2), jnp.asarray(lo2),
                           jnp.asarray(v2))
    np.testing.assert_allclose(np.asarray(m.counts), np.asarray(both.counts),
                               rtol=1e-6)


def test_cms_valid_mask(rng):
    hi, lo = _keys(rng, 64)
    vals = np.ones(64, np.float32)
    valid = np.zeros(64, bool)
    valid[:10] = True
    sk = countmin.update(countmin.init(2, 256), jnp.asarray(hi),
                         jnp.asarray(lo), jnp.asarray(vals),
                         valid=jnp.asarray(valid))
    assert float(countmin.total(sk)) == 10.0


# ------------------------------------------------------------------- HLL
@pytest.mark.parametrize("true_n", [100, 5_000, 200_000])
def test_hll_estimate_error(rng, true_n):
    hi = rng.integers(0, 2**32, true_n, dtype=np.uint32)
    lo = rng.integers(0, 2**32, true_n, dtype=np.uint32)
    # repeat keys: duplicates must not change the estimate
    rep = np.concatenate([np.arange(true_n), rng.integers(0, true_n, true_n)])
    sk = hll.init(p=14)
    upd = jax.jit(hll.update)
    sk = upd(sk, jnp.asarray(hi[rep]), jnp.asarray(lo[rep]))
    est = float(hll.estimate(sk))
    err = abs(est - true_n) / true_n
    assert err < 0.05, f"HLL err {err:.3f} at n={true_n}"


def test_hll_merge_equals_union(rng):
    hi, lo = _keys(rng, 20_000)
    a = hll.update(hll.init(p=12), jnp.asarray(hi[:10_000]),
                   jnp.asarray(lo[:10_000]))
    b = hll.update(hll.init(p=12), jnp.asarray(hi[10_000:]),
                   jnp.asarray(lo[10_000:]))
    merged = hll.merge(a, b)
    full = hll.update(hll.init(p=12), jnp.asarray(hi), jnp.asarray(lo))
    np.testing.assert_array_equal(np.asarray(merged.regs),
                                  np.asarray(full.regs))


def test_hll_per_entity(rng):
    n_ent, per = 8, 3000
    sk = hll.init(p=12, entities=(n_ent,))
    for e in range(n_ent):
        hi = rng.integers(0, 2**32, per * (e + 1), dtype=np.uint32)
        lo = rng.integers(0, 2**32, per * (e + 1), dtype=np.uint32)
        rows = np.full(hi.shape, e, np.int32)
        sk = hll.update_entities(sk, 12, jnp.asarray(rows),
                                 jnp.asarray(hi), jnp.asarray(lo))
    est = np.asarray(hll.estimate(hll.rows(sk, 12)))
    for e in range(n_ent):
        true_n = per * (e + 1)
        assert abs(est[e] - true_n) / true_n < 0.1


# --------------------------------------------------------------- loghist
def test_loghist_quantile_error_bound(rng):
    spec = loghist.RESP_TIME_SPEC
    vals = rng.lognormal(mean=-4.0, sigma=1.5, size=100_000).astype(np.float32)
    vals = np.clip(vals, spec.vmin, spec.vmax * 0.99)
    hist = loghist.init(spec)
    hist = jax.jit(
        lambda h, v: loghist.update(h, spec, v)
    )(hist, jnp.asarray(vals))
    qs = np.array([0.25, 0.5, 0.95, 0.99], np.float32)
    est = np.asarray(loghist.quantiles(hist, spec, jnp.asarray(qs)))
    truth = exact.quantiles(vals, qs)
    rel = np.abs(est - truth) / truth
    assert rel.max() < 2 * spec.rel_error + 0.01, f"rel err {rel}"
    assert spec.rel_error < 0.02  # the <2% north-star bound


def test_loghist_per_entity_scatter(rng):
    spec = loghist.LogHistSpec(1e-4, 10.0, 256)
    n_ent = 16
    hist = loghist.init(spec, entities=(n_ent,))
    rows = rng.integers(0, n_ent, 50_000).astype(np.int32)
    vals = rng.lognormal(-2.0, 1.0, 50_000).astype(np.float32)
    hist = jax.jit(
        lambda h, r, v: loghist.update_entities(h, spec, r, v)
    )(hist, jnp.asarray(rows), jnp.asarray(vals))
    est = np.asarray(loghist.quantiles(hist, spec, jnp.asarray([0.5, 0.99])))
    for e in range(n_ent):
        sel = vals[rows == e]
        truth = exact.quantiles(np.clip(sel, spec.vmin, spec.vmax), [0.5, 0.99])
        rel = np.abs(est[e] - truth) / truth
        assert rel.max() < 2 * spec.rel_error + 0.02
    # counts preserved per entity
    np.testing.assert_allclose(
        np.asarray(loghist.counts_total(hist)),
        np.bincount(rows, minlength=n_ent).astype(np.float32), rtol=1e-6)


def test_loghist_merge_additive(rng):
    spec = loghist.RATE_SPEC
    v1 = rng.exponential(100, 10_000).astype(np.float32)
    v2 = rng.exponential(1000, 10_000).astype(np.float32)
    h1 = loghist.update(loghist.init(spec), spec, jnp.asarray(v1))
    h2 = loghist.update(loghist.init(spec), spec, jnp.asarray(v2))
    hm = loghist.merge(h1, h2)
    hfull = loghist.update(h1, spec, jnp.asarray(v2))
    np.testing.assert_allclose(np.asarray(hm), np.asarray(hfull), rtol=1e-6)


# --------------------------------------------------------------- t-digest
def test_tdigest_quantiles_vs_exact(rng):
    vals = rng.lognormal(0.0, 2.0, 200_000).astype(np.float32)
    sk = tdigest.init(capacity=128)
    upd = jax.jit(tdigest.update)
    for chunk in np.array_split(vals, 20):
        sk = upd(sk, jnp.asarray(chunk))
    qs = np.array([0.01, 0.25, 0.5, 0.75, 0.95, 0.99], np.float32)
    est = np.asarray(tdigest.quantiles(sk, jnp.asarray(qs)))
    truth = exact.quantiles(vals, qs)
    rel = np.abs(est - truth) / truth
    assert rel.max() < 0.02, f"t-digest rel err {rel}"
    assert np.isclose(float(tdigest.count(sk)), len(vals), rtol=1e-6)


def test_tdigest_merge(rng):
    v1 = rng.normal(10.0, 2.0, 50_000).astype(np.float32)
    v2 = rng.normal(20.0, 2.0, 50_000).astype(np.float32)
    a = tdigest.update(tdigest.init(128), jnp.asarray(v1))
    b = tdigest.update(tdigest.init(128), jnp.asarray(v2))
    m = tdigest.merge(a, b)
    both = np.concatenate([v1, v2])
    qs = np.array([0.1, 0.5, 0.9], np.float32)
    est = np.asarray(tdigest.quantiles(m, jnp.asarray(qs)))
    truth = exact.quantiles(both, qs)
    rel = np.abs(est - truth) / np.abs(truth)
    assert rel.max() < 0.03, f"merged digest rel err {rel}"


# ------------------------------------------------------------------ top-K
def test_topk_heavy_hitters(rng):
    # zipf-ish: key i has frequency ∝ 1/(i+1)
    n_keys, n = 5000, 200_000
    p = 1.0 / np.arange(1, n_keys + 1)
    p /= p.sum()
    draws = rng.choice(n_keys, size=n, p=p)
    pool_hi = rng.integers(0, 2**32, n_keys, dtype=np.uint32)
    pool_lo = rng.integers(0, 2**32, n_keys, dtype=np.uint32)
    hi, lo = pool_hi[draws], pool_lo[draws]
    vals = np.ones(n, np.float32)
    sk = topk.init(capacity=256)
    upd = jax.jit(topk.update)
    for s in range(0, n, 20_000):
        sk = upd(sk, jnp.asarray(hi[s:s + 20_000]),
                 jnp.asarray(lo[s:s + 20_000]),
                 jnp.asarray(vals[s:s + 20_000]))
    got_hi, got_lo, got_v = topk.query(sk, 10)
    got_keys = (np.asarray(got_hi).astype(np.uint64) << np.uint64(32)) | \
        np.asarray(got_lo).astype(np.uint64)
    truth = exact.topk(hi, lo, vals, 10)
    true_keys = {int(k) for k, _ in truth}
    # at least 9 of the true top-10 present
    assert len(true_keys & {int(k) for k in got_keys}) >= 9
    # counts of recovered keys close to truth
    tmap = exact.key_totals(hi, lo, vals)
    for k, v in zip(got_keys[:5], np.asarray(got_v)[:5]):
        assert abs(v - tmap[int(k)]) / tmap[int(k)] < 0.15


def test_topk_merge(rng):
    hi, lo = _keys(rng, 10_000, distinct=100)
    vals = np.ones(10_000, np.float32)
    a = topk.update(topk.init(128), jnp.asarray(hi[:5000]),
                    jnp.asarray(lo[:5000]), jnp.asarray(vals[:5000]))
    b = topk.update(topk.init(128), jnp.asarray(hi[5000:]),
                    jnp.asarray(lo[5000:]), jnp.asarray(vals[5000:]))
    m = topk.merge(a, b)
    # 100 distinct keys all fit in capacity 128 → totals exact
    tmap = exact.key_totals(hi, lo, vals)
    gh, gl, gv = topk.query(m, 100)
    for khi, klo, v in zip(np.asarray(gh), np.asarray(gl), np.asarray(gv)):
        k = (int(khi) << 32) | int(klo)
        assert k in tmap and abs(v - tmap[k]) < 1e-3


def test_dense_topk():
    stats = jnp.asarray(np.array([5.0, 1.0, 9.0, 7.0, 3.0], np.float32))
    v, i = topk.dense_topk(stats, 3)
    np.testing.assert_array_equal(np.asarray(i), [2, 3, 0])
