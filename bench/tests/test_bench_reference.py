"""The benchmark's generator and plain reference, checked against the
program's own primitives at small sizes (the reference imports nothing
of the program; these tests do, to show the two agree)."""

import numpy as np
import pytest

import tinybench  # noqa: F401  (puts bench/ and src/ on the path)
from harness import lake as lk
from harness import reference

CFG = {"tables": 512, "rows_per_table": 128, "key_zipf": 1.5,
       "n_keys_min": 20, "n_keys_max": 300, "shifted_share": 0.35,
       "categorical_every": 3}


def test_lake_is_a_function_of_the_seed():
    a, b = lk.Lake(CFG, 2**31 + 9), lk.Lake(CFG, 2**31 + 9)
    ca, cb = a.chunk(0, 64), b.chunk(0, 64)
    for key in ca:
        np.testing.assert_array_equal(ca[key], cb[key])
    other = lk.Lake(CFG, 12)
    # Another seed: the same multisets of key counts and strengths and
    # as many shifted tables, reordered.
    for f in ("n_keys", "alpha"):
        np.testing.assert_array_equal(np.sort(getattr(a, f)),
                                      np.sort(getattr(other, f)))
    assert not np.array_equal(a.n_keys, other.n_keys)
    assert (a.shift > 0).sum() == (other.shift > 0).sum() \
        == round(0.35 * CFG["tables"])
    assert a.discrete.sum() == -(-CFG["tables"] // 3)


def test_keys_follow_the_sources_zipf_draws_modulo_the_key_count():
    lake = lk.Lake(dict(CFG, rows_per_table=4096), 5)
    z = lake.draw_keys(lake._k_rows, np.arange(64), np.full(64, 250),
                       np.zeros(64, int)).ravel()
    want = np.random.default_rng(0).zipf(1.5, size=z.size) % 250
    got_p = np.bincount(z, minlength=250) / z.size
    want_p = np.bincount(want, minlength=250) / z.size
    # The head keys carry the law's weight: key 1 about 0.38, key 2 0.14.
    np.testing.assert_allclose(got_p[:8], want_p[:8], atol=0.01)
    assert abs(got_p[8:].sum() - want_p[8:].sum()) < 0.01


def test_key_hashes_are_distinct_and_never_the_fence():
    z = np.arange(1 << 16)
    h = lk.key_hash(z)
    assert len(np.unique(h)) == len(h)
    assert not np.any(h == np.uint32(0xFFFFFFFF))
    assert lk.key_hash(lk._FENCE_CODE) != np.uint32(0xFFFFFFFF)
    assert lk._fmix_int(lk._unfmix_int(0x12345678)) == 0x12345678


def test_murmur3_matches_the_program():
    from repro.core.hashing import murmur3_32_np
    rng = np.random.default_rng(0)
    w = rng.integers(0, 2**32, size=1000, dtype=np.uint64).astype(np.uint32)
    s = rng.integers(0, 2**32, size=1000, dtype=np.uint64).astype(np.uint32)
    np.testing.assert_array_equal(reference.murmur3_word(w, s),
                                  murmur3_32_np(w, seed=s))


def _lake_with_rows():
    lake = lk.Lake(CFG, 77)
    lake.keep_rows()
    lake.chunk(0, CFG["tables"])
    return lake


def test_sketches_match_the_program():
    from repro.core.sketch import build_sketch
    lake = _lake_with_rows()
    n = 32
    ls = reference.LakeSketches(lake, n)
    for c in range(40):
        z = lake.z[c]
        sk = build_sketch(lk.key_hash(z), lake.values(np.full(len(z), c), z),
                          n=n, method="tupsk", side="cand")
        want = set(sk.key_hashes[sk.mask].tolist())
        held = ls.joined(np.arange(lake.universe))[c]
        got = set(lk.key_hash(np.flatnonzero(held)).tolist())
        assert got == want
    q = lk.Query(lake, 5, 250, 0, 0.5, discrete=False)
    sel, _ = reference.train_rows(q, n)
    sk = build_sketch(q.keys, q.y, n=n, method="tupsk", side="train",
                      value_is_discrete=False)
    assert sorted(zip(lk.key_hash(q.z[sel]).tolist(), q.y[sel].tolist())) == \
        sorted(zip(sk.key_hashes[sk.mask].tolist(),
                   sk.values[sk.mask].tolist()))


@pytest.mark.parametrize("kind", ["mle", "mixed", "dc_x", "dc_y"])
def test_estimators_match_the_program(kind):
    import jax.numpy as jnp
    from repro.core import estimators as est
    rng = np.random.default_rng(3)
    L, P = 6, 32
    xf = np.round(rng.normal(size=(L, P)), 1).astype(np.float32)
    yf = np.round(rng.normal(size=P), 1).astype(np.float32)
    xc = rng.integers(0, 4, size=(L, P))
    yc = rng.integers(0, 3, size=P)
    mask = rng.random((L, P)) < 0.8
    x = xc if kind in ("mle", "dc_x") else xf
    y = yc if kind in ("mle", "dc_y") else yf
    got = reference.mi_lanes(x, y, mask, kind=kind, k=3)
    for i in range(L):
        m = jnp.asarray(mask[i])
        if kind == "mle":
            want = est.mle_mi(jnp.asarray(xc[i], jnp.uint32),
                              jnp.asarray(yc, jnp.uint32), m)
        elif kind == "mixed":
            want = est.mixed_ksg_mi(jnp.asarray(xf[i]), jnp.asarray(yf), m,
                                    k=3, impl="materialized")
        elif kind == "dc_x":
            want = est.dc_ksg_mi(est.dense_rank(jnp.asarray(xc[i]), m),
                                 jnp.asarray(yf), m, k=3, impl="materialized")
        else:
            want = est.dc_ksg_mi(est.dense_rank(jnp.asarray(yc), m),
                                 jnp.asarray(xf[i]), m, k=3,
                                 impl="materialized")
        np.testing.assert_allclose(got[i], float(want), atol=2e-5, rtol=1e-5)
