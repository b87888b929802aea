"""Device entity-table tests (ref: RCU_HASH_TABLE ``common/gy_rcu_inc.h:1664``;
delete flow ``server/gy_mconnhdlr.cc:11195``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gyeeta_tpu.engine import table


def keys_of(rng, n, lo=1, hi=2**31):
    return (rng.integers(lo, hi, n).astype(np.uint32),
            rng.integers(lo, hi, n).astype(np.uint32))


@pytest.fixture(scope="module")
def jitted():
    cap = 64
    return {
        "cap": cap,
        "upsert": jax.jit(table.upsert),
        "lookup": jax.jit(table.lookup),
        "delete": jax.jit(table.delete),
    }


def test_upsert_then_lookup(rng, jitted):
    tbl = table.init(jitted["cap"])
    khi, klo = keys_of(rng, 40)
    tbl, rows = jitted["upsert"](tbl, jnp.asarray(khi), jnp.asarray(klo))
    rows = np.asarray(rows)
    assert (rows >= 0).all()
    assert int(tbl.n_live) == 40
    # same keys resolve to the same rows
    found = np.asarray(jitted["lookup"](tbl, jnp.asarray(khi),
                                        jnp.asarray(klo)))
    assert np.array_equal(found, rows)
    # unknown keys miss
    uhi, ulo = keys_of(rng, 8)
    miss = np.asarray(jitted["lookup"](tbl, jnp.asarray(uhi),
                                       jnp.asarray(ulo)))
    assert (miss == -1).all()


def test_intra_batch_duplicates_one_row(rng, jitted):
    tbl = table.init(jitted["cap"])
    khi = np.full(16, 77, np.uint32)
    klo = np.full(16, 99, np.uint32)
    tbl, rows = jitted["upsert"](tbl, jnp.asarray(khi), jnp.asarray(klo))
    rows = np.asarray(rows)
    assert (rows == rows[0]).all() and rows[0] >= 0
    assert int(tbl.n_live) == 1


def test_delete_and_reinsert(rng, jitted):
    tbl = table.init(jitted["cap"])
    khi, klo = keys_of(rng, 20)
    tbl, rows = jitted["upsert"](tbl, jnp.asarray(khi), jnp.asarray(klo))
    tbl, drows = jitted["delete"](tbl, jnp.asarray(khi[:5]),
                                  jnp.asarray(klo[:5]))
    assert int(tbl.n_live) == 15
    assert int(tbl.n_tomb) == 5
    gone = np.asarray(jitted["lookup"](tbl, jnp.asarray(khi[:5]),
                                       jnp.asarray(klo[:5])))
    assert (gone == -1).all()
    kept = np.asarray(jitted["lookup"](tbl, jnp.asarray(khi[5:]),
                                       jnp.asarray(klo[5:])))
    assert (kept >= 0).all()
    # reinsert reclaims tombstones
    tbl, rrows = jitted["upsert"](tbl, jnp.asarray(khi[:5]),
                                  jnp.asarray(klo[:5]))
    assert int(tbl.n_live) == 20
    assert (np.asarray(rrows) >= 0).all()


def test_delete_duplicate_lanes_count_once(rng, jitted):
    """Duplicate lanes deleting one key must not drive n_live negative."""
    tbl = table.init(jitted["cap"])
    tbl, _ = jitted["upsert"](tbl, jnp.asarray(np.array([7], np.uint32)),
                              jnp.asarray(np.array([9], np.uint32)))
    tbl, _ = jitted["delete"](tbl,
                              jnp.asarray(np.full(3, 7, np.uint32)),
                              jnp.asarray(np.full(3, 9, np.uint32)))
    assert int(tbl.n_live) == 0
    assert int(tbl.n_tomb) == 1


def test_compact_permutes_state(rng, jitted):
    cap = jitted["cap"]
    tbl = table.init(cap)
    khi, klo = keys_of(rng, 30)
    tbl, rows = jitted["upsert"](tbl, jnp.asarray(khi), jnp.asarray(klo))
    rows = np.asarray(rows)
    state = jnp.zeros((cap,), jnp.float32).at[rows].set(
        jnp.arange(30, dtype=jnp.float32))
    tbl, _ = jitted["delete"](tbl, jnp.asarray(khi[:10]),
                              jnp.asarray(klo[:10]))
    new_tbl, (new_state,) = jax.jit(table.compact)(tbl, (state,))
    assert int(new_tbl.n_tomb) == 0
    assert int(new_tbl.n_live) == 20
    new_rows = np.asarray(table.lookup(new_tbl, jnp.asarray(khi[10:]),
                                       jnp.asarray(klo[10:])))
    assert (new_rows >= 0).all()
    # surviving keys carried their state value through the permutation
    assert np.allclose(np.asarray(new_state)[new_rows],
                       np.arange(10, 30, dtype=np.float32))


def test_churn_storm(rng, jitted):
    """Create/delete storms: the table never corrupts surviving keys."""
    cap = jitted["cap"]
    tbl = table.init(cap)
    live = {}
    for step_i in range(6):
        khi, klo = keys_of(rng, 24)
        tbl, rows = jitted["upsert"](tbl, jnp.asarray(khi),
                                     jnp.asarray(klo))
        rows = np.asarray(rows)
        for i in range(24):
            if rows[i] >= 0:
                live[(int(khi[i]), int(klo[i]))] = rows[i]
        # delete a random half of live keys
        keys = list(live)
        drop = [keys[i] for i in
                rng.choice(len(keys), len(keys) // 2, replace=False)]
        dh = np.array([k[0] for k in drop], np.uint32)
        dl = np.array([k[1] for k in drop], np.uint32)
        tbl, _ = jitted["delete"](tbl, jnp.asarray(dh), jnp.asarray(dl))
        for k in drop:
            del live[k]
        if int(tbl.n_tomb) > cap // 2:
            tbl, _ = jax.jit(table.compact)(tbl, (jnp.zeros((cap,)),))
            live = {k: None for k in live}  # rows changed; re-resolve below
        # every surviving key still resolves
        sh = np.array([k[0] for k in live], np.uint32)
        sl = np.array([k[1] for k in live], np.uint32)
        if len(sh):
            got = np.asarray(table.lookup(tbl, jnp.asarray(sh),
                                          jnp.asarray(sl)))
            assert (got >= 0).all()
    assert int(tbl.n_live) == len(live)


# ----------------------------------------------------------- staged probe
def _loaded(rng, cap, load, batch=1024):
    """A slab filled to ``load`` through the insert path, with a few of
    its keys tombstoned → (tbl, live keys, tombstoned keys)."""
    n = int(cap * load)
    khi, klo = keys_of(rng, n)
    tbl = table.init(cap)
    up = jax.jit(table.upsert)
    for i in range(0, n, batch):
        tbl, _ = up(tbl, jnp.asarray(khi[i:i + batch]),
                    jnp.asarray(klo[i:i + batch]))
    dead = slice(0, 16)
    tbl, _ = table.delete(tbl, jnp.asarray(khi[dead]), jnp.asarray(klo[dead]))
    return tbl, (khi[16:], klo[16:]), (khi[dead], klo[dead])


def _probe_lanes(rng, live, dead, B, n_absent):
    """B lanes: live keys drawn WITH repeats, then absent keys, the
    tombstoned keys, both sentinel keys, and invalid lanes."""
    pick = rng.integers(0, len(live[0]), B)
    khi, klo = live[0][pick].copy(), live[1][pick].copy()
    valid = np.ones(B, bool)
    a = slice(0, n_absent)
    khi[a], klo[a] = keys_of(rng, n_absent, lo=2**31, hi=2**32 - 8)
    d = slice(n_absent, n_absent + 16)
    khi[d], klo[d] = dead
    khi[-4:-2], klo[-4:-2] = table.EMPTY, table.EMPTY
    khi[-2:], klo[-2:] = table.TOMB, table.TOMB
    valid[rng.integers(0, B, B // 8)] = False
    return jnp.asarray(khi), jnp.asarray(klo), jnp.asarray(valid)


@pytest.mark.parametrize("B", [256, 4096])
@pytest.mark.parametrize("jit", [True, False])
@pytest.mark.parametrize("load", [0.25, 0.5, 0.7, 0.85])
def test_staged_lookup_matches_full_probe(rng, load, jit, B):
    """``lookup`` (4 slots for every lane, 12 for the residue, or the
    overflow's full width) returns exactly the 16-slot probe's rows."""
    assert 256 < table.STAGED_MIN_LANES <= 4096
    tbl, live, dead = _loaded(rng, 4096, load)
    khi, klo, valid = _probe_lanes(rng, live, dead, B, n_absent=B // 64)
    staged, full = table.lookup, table._lookup_full
    if jit:
        staged, full = jax.jit(staged), jax.jit(full)
    want = np.asarray(full(tbl, khi, klo, valid))
    assert np.array_equal(np.asarray(staged(tbl, khi, klo, valid)), want)
    v = np.asarray(valid)
    assert (want[~v] == -1).all() and (want[v] >= 0).sum() > B // 2
    # absent and tombstoned keys miss
    assert (want[:B // 64 + 16] == -1).all()


def test_staged_lookup_overflow_counted(rng):
    """More absent keys than the residue holds: the overflow branch
    answers (same rows) and is counted."""
    B = 4096
    tbl, live, dead = _loaded(rng, 4096, 0.5)
    khi, klo, valid = _probe_lanes(rng, live, dead, B,
                                   n_absent=B // table.RESIDUE_DIV + 64)
    rows, probe = jax.jit(table.lookup_counted)(tbl, khi, klo, valid)
    assert np.array_equal(np.asarray(rows), np.asarray(
        table._lookup_full(tbl, khi, klo, valid)))
    probe = np.asarray(probe)
    assert probe[1] == 1 and probe[0] > B // table.RESIDUE_DIV


def test_staged_lookup_counters_at_half_load(rng):
    """All-hit lanes at the service slab's 50 % load: about 1.2 % of
    them need stage 2 and none overflows; a small batch counts nothing."""
    B = 8192
    tbl, live, _ = _loaded(rng, 16384, 0.5, batch=4096)
    pick = rng.integers(0, len(live[0]), B)
    khi, klo = jnp.asarray(live[0][pick]), jnp.asarray(live[1][pick])
    rows, probe = jax.jit(table.lookup_counted)(tbl, khi, klo)
    assert (np.asarray(rows) >= 0).all()
    probe = np.asarray(probe)
    assert probe[1] == 0
    assert 0.005 * B <= probe[0] <= 0.025 * B
    _, small = table.lookup_counted(tbl, khi[:256], klo[:256])
    assert np.asarray(small).tolist() == [0, 0]
