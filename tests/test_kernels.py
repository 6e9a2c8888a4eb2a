"""Per-kernel validation: both join paths and the segment sums vs. jnp
oracles.

Sweeps shapes and dtypes, the dense-or-sorted crossover of
``join_path``, and 64-bit frequencies on both paths; hypothesis property
tests check the engine-level invariants the kernels must uphold.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests skip without hypothesis; kernel tests always run
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

from repro.core.stats import limb_split
from repro.kernels import ops, ref
from repro.kernels.ops import (DENSE_DOMAIN_CAP, DENSE_FLOOR, DENSE_RATIO,
                               join_path)

jax.config.update("jax_platform_name", "cpu")


def _rand_tables(rng, np_, nc, key_range, kdt, fdt):
    pk = jnp.asarray(rng.integers(0, key_range, np_), kdt)
    ck = jnp.asarray(rng.integers(0, key_range, nc), kdt)
    pf = jnp.asarray(rng.integers(0, 4, np_), fdt)
    cf = jnp.asarray(rng.integers(0, 4, nc), fdt)
    return pk, pf, ck, cf


SHAPES = [(1024, 1024), (1000, 37), (2048, 4096), (8, 8), (4096, 1000)]
DTYPES = [(jnp.int32, jnp.int32), (jnp.int32, jnp.float32)]
# the declared domain that sends a join of keys below `key_range` down
# each path: the key range itself is dense, an undeclared one sorts
PATHS = ["sorted", "dense"]


def _domain(path, key_range):
    return key_range if path == "dense" else None


def _ran(fn, *args):
    """The path a kernel call took, read off its jaxpr: the dense path
    scatter-adds, the sorted one sorts."""
    text = str(jax.make_jaxpr(fn)(*args))
    scatters, sorts = "scatter-add" in text, " sort[" in text
    assert scatters != sorts, text
    return "dense" if scatters else "sorted"


@pytest.mark.parametrize("np_,nc", SHAPES)
@pytest.mark.parametrize("kdt,fdt", DTYPES)
@pytest.mark.parametrize("path", PATHS)
def test_freq_join_matches_oracle(np_, nc, kdt, fdt, path):
    rng = np.random.default_rng(np_ * 7919 + nc)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=50, kdt=kdt, fdt=fdt)
    domain = _domain(path, 50)
    assert join_path(domain, nc) == path
    got = ops.freq_join(pk, pf, ck, cf, mode="sum", domain=domain)
    want = ref.freq_join_ref(pk, pf, ck, cf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("np_,nc", SHAPES)
@pytest.mark.parametrize("path", PATHS)
def test_semi_join_matches_oracle(np_, nc, path):
    rng = np.random.default_rng(nc * 31 + np_)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=30,
                                  kdt=jnp.int32, fdt=jnp.int32)
    domain = _domain(path, 30)
    assert join_path(domain, nc) == path
    got = ops.semi_join(pk, pf, ck, cf, domain=domain)
    want = ref.semi_join_ref(pk, pf, ck, cf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _per_key(keys, sums, mask):
    out = {}
    for k, s, m in zip(np.asarray(keys), np.asarray(sums), np.asarray(mask)):
        if m:
            out[int(k)] = out.get(int(k), 0) + s
    return out


@pytest.mark.parametrize("n", [1024, 1000, 4096, 17, 2048])
@pytest.mark.parametrize("vdt", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("op", ["segment_sum_sorted", "group_by_sum"])
def test_segment_sum_matches_oracle(n, vdt, op):
    """The sorted segment sum over sorted keys, and ``group_by_sum`` over
    the same rows shuffled, against the oracle's per-key totals."""
    rng = np.random.default_rng(n)
    keys = jnp.sort(jnp.asarray(rng.integers(0, max(2, n // 8), n), jnp.int32))
    vals = jnp.asarray(rng.integers(-3, 5, n), vdt)
    if op == "segment_sum_sorted":
        gkeys = keys
        got, gvalid = ops.segment_sum_sorted(keys, vals)
    else:
        perm = jnp.asarray(rng.permutation(n))
        gkeys, got, gvalid = ops.group_by_sum(keys[perm], vals[perm])
        np.testing.assert_array_equal(np.asarray(gkeys), np.asarray(keys))
    want, _wfirst = ref.segment_sum_ref(keys, vals)
    # Emission rows differ (ref: first-of-run; kernel: last-of-run), so
    # compare per-key totals, which is the semantic contract.
    want_first = np.concatenate([[True], np.asarray(keys)[1:] != np.asarray(keys)[:-1]])
    assert _per_key(gkeys, got, gvalid) == _per_key(keys, want, want_first)
    # totals preserved
    np.testing.assert_allclose(np.asarray(jnp.sum(got)),
                               np.asarray(jnp.sum(vals)), rtol=1e-5)


@pytest.mark.parametrize("n", [64, 1000])
@pytest.mark.parametrize("wdt", [jnp.int32, jnp.int64])
def test_weighted_percentile_matches_oracle(n, wdt):
    """int64 weights, some past 2^32, are MEDIAN's in a 64-bit program:
    they accumulate in float64 there, as the oracle does over float64
    values."""
    rng = np.random.default_rng(n)
    wide = wdt == jnp.int64
    with jax.enable_x64(wide):
        vals = jnp.asarray(rng.normal(size=n), jnp.float32)
        w = jnp.asarray(rng.integers(0, 5, n), wdt)
        if wide:
            w = w << 33
        for q in (0.1, 0.5, 0.9):
            got = ops.weighted_percentile(vals, w, q)
            want = ref.weighted_percentile_ref(
                vals.astype(jnp.float64) if wide else vals, w, q)
            np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_weighted_percentile_expansion_equivalence():
    """Median over frequencies == median over the expanded bag (paper §4.2)."""
    vals = jnp.asarray([5.0, 1.0, 3.0, 9.0], jnp.float32)
    w = jnp.asarray([1, 3, 2, 0], jnp.int32)
    expanded = np.repeat(np.asarray(vals), np.asarray(w))
    got = float(ops.weighted_percentile(vals, w, 0.5))
    # lower-interpolation median of [1,1,1,3,3,5]
    want = float(np.sort(expanded)[max(0, int(np.ceil(0.5 * len(expanded))) - 1)])
    assert got == want


@pytest.mark.parametrize("distinct", [1, 3, 37])
def test_group_by_sum_runs_longer_than_a_block(distinct):
    """Runs of one key over thousands of shuffled rows: after the sort,
    each run's total sits at its last row, against the oracle run by
    run."""
    n = 3 * 1024 + 77
    rng = np.random.default_rng(distinct)
    keys = jnp.sort(jnp.asarray(rng.integers(0, distinct, n), jnp.int32))
    vals = jnp.asarray(rng.integers(-3, 5, n), jnp.int32)
    perm = jnp.asarray(rng.permutation(n))
    ks, got, valid = ops.group_by_sum(keys[perm], vals[perm])
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(keys))
    assert int(jnp.sum(valid)) == len(np.unique(np.asarray(keys)))
    want, first = ref.segment_sum_ref(keys, vals)
    want, first = np.asarray(want), np.asarray(first)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(valid)],
                                  want[first])


# ---------------------------------------------------------------------------
# The crossover, through the kernels: at DENSE_FLOOR a join goes dense
# whatever its child's size, one slot past it these children sort
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("domain, path", [(DENSE_FLOOR, "dense"),
                                          (DENSE_FLOOR + 1, "sorted")])
@pytest.mark.parametrize("np_,nc", [(1000, 37), (2048, 1024), (8, 8)])
@pytest.mark.parametrize("kernel", ["freq_join", "semi_join"])
def test_crossover_through_the_kernels(domain, path, np_, nc, kernel):
    rng = np.random.default_rng(np_ * 13 + nc)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=50,
                                  kdt=jnp.int32, fdt=jnp.int32)
    assert join_path(domain, nc) == path
    fn = getattr(ops, kernel)
    call = lambda *a: fn(*a, domain=domain)           # noqa: E731
    assert _ran(call, pk, pf, ck, cf) == path
    oracle = ref.freq_join_ref if kernel == "freq_join" else ref.semi_join_ref
    np.testing.assert_array_equal(np.asarray(call(pk, pf, ck, cf)),
                                  np.asarray(oracle(pk, pf, ck, cf)))


# ---------------------------------------------------------------------------
# 64-bit frequencies, some past 2^32: the dense path in limb_split's
# uint32 limbs, the sorted path in s64
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("np_,nc", SHAPES)
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("kernel", ["freq_join", "semi_join"])
def test_64_bit_frequencies_match_oracle(np_, nc, path, kernel):
    rng = np.random.default_rng(np_ * 31 + nc)
    with jax.enable_x64(True):
        pk, pf, ck, _ = _rand_tables(rng, np_, nc, key_range=50,
                                     kdt=jnp.int32, fdt=jnp.int64)
        cf = jnp.asarray(rng.integers(0, 1 << 34, nc), jnp.int64)
        cf = jnp.where(jnp.arange(nc) % 5 == 0, 0, cf)   # some dead rows
        assert int(cf.max()) >= 1 << 32
        domain = _domain(path, 50)
        assert join_path(domain, nc) == path
        if kernel == "freq_join":
            mult = int(np.bincount(np.asarray(ck), minlength=50).max())
            limbs = limb_split(mult, int(cf.max()))
            got = ops.freq_join(pk, pf, ck, cf, domain=domain, limbs=limbs)
            want = ref.freq_join_ref(pk, pf, ck, cf)
        else:
            got = ops.semi_join(pk, pf, ck, cf, domain=domain)
            want = ref.semi_join_ref(pk, pf, ck, cf)
        assert got.dtype == jnp.int64
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Dense-domain dispatch boundary
# ---------------------------------------------------------------------------
def test_dense_ok_boundary_and_cap():
    assert join_path(DENSE_FLOOR, 8) == "dense"           # at the floor
    assert join_path(DENSE_FLOOR + 1, 8) == "sorted"      # just past it
    big = DENSE_FLOOR                                     # ratio binds
    assert join_path(DENSE_RATIO * big, big) == "dense"   # at ratio*nc
    assert join_path(DENSE_RATIO * big + 1, big) == "sorted"
    assert join_path(None, 100) == "sorted"               # undeclared
    # the int32 accumulator cap binds whatever the ratio admits
    huge = DENSE_DOMAIN_CAP // DENSE_RATIO
    assert join_path(DENSE_DOMAIN_CAP - 1, huge) == "dense"
    assert join_path(DENSE_DOMAIN_CAP, huge) == "sorted"


def test_dense_domain_cap_falls_back_to_sort():
    """domain == 2^31 with a child long enough for the ratio to admit it
    sorts (no 2 GiB accumulator is traced), one slot less scatters; and
    at a runnable size the capped domain still matches the oracle."""
    huge = jax.ShapeDtypeStruct((DENSE_DOMAIN_CAP // DENSE_RATIO,),
                                jnp.int32)
    for domain, path in ((DENSE_DOMAIN_CAP, "sorted"),
                         (DENSE_DOMAIN_CAP - 1, "dense")):
        assert _ran(lambda *a: ops.freq_join(*a, domain=domain),
                    huge, huge, huge, huge) == path
    rng = np.random.default_rng(7)
    pk, pf, ck, cf = _rand_tables(rng, 64, 64, key_range=40,
                                  kdt=jnp.int32, fdt=jnp.int32)
    got = ops.freq_join(pk, pf, ck, cf, domain=DENSE_DOMAIN_CAP)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.freq_join_ref(pk, pf,
                                                               ck, cf)))


@pytest.mark.parametrize("mode", ["sum", "any"])
def test_dense_path_masks_negative_and_oob_child_keys(mode):
    """Regression: ``.at[].add(mode="drop")`` wraps NEGATIVE indices
    (NumPy semantics) even though it drops too-large ones — a dead child
    tuple marked with key -1 must contribute nothing, not corrupt
    ``acc[domain-1]``.  Dense and sort dispatch must agree bitwise."""
    dom = 64
    pk = jnp.asarray([0, 5, dom - 1, 63, 12], jnp.int32)
    pf = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    # child keys: valid, -1 (dead), dom (OOB-high), valid dup of dom-1
    ck = jnp.asarray([5, -1, dom, dom - 1, -1, 12], jnp.int32)
    cf = jnp.asarray([7, 9, 11, 2, 100, 1], jnp.int32)
    assert join_path(dom, ck.shape[0]) == "dense"
    dense = ops.freq_join(pk, pf, ck, cf, mode=mode, domain=dom)
    sort = ops.freq_join(pk, pf, ck, cf, mode=mode, domain=None)
    want = ref.freq_join_ref(pk, pf, ck, cf) if mode == "sum" \
        else ref.semi_join_ref(pk, pf, ck, cf)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sort))


# ---------------------------------------------------------------------------
# Property tests (hypothesis) — system invariants
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    small_ints = st.lists(st.integers(0, 12), min_size=1, max_size=40)

    @settings(max_examples=30, deadline=None)
    @given(pk=small_ints, ck1=small_ints, ck2=small_ints)
    def test_freq_join_distributes_over_child_union(pk, ck1, ck2):
        """mult(R, S1 ⊎ S2) == mult(R,S1) + mult(R,S2): the additive-semiring
        law that makes the distributed ring execution exact."""
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        c1 = jnp.asarray(ck1, jnp.int32)
        c2 = jnp.asarray(ck2, jnp.int32)
        f1 = jnp.ones_like(c1)
        f2 = jnp.ones_like(c2)
        whole = ops.freq_join(pk, pf, jnp.concatenate([c1, c2]),
                              jnp.concatenate([f1, f2]))
        parts = (ops.freq_join(pk, pf, c1, f1)
                 + ops.freq_join(pk, pf, c2, f2))
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(parts))

    @settings(max_examples=30, deadline=None)
    @given(pk=small_ints, ck=small_ints)
    def test_semi_join_idempotent(pk, ck):
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        ck = jnp.asarray(ck, jnp.int32)
        cf = jnp.ones_like(ck)
        once = ops.semi_join(pk, pf, ck, cf)
        twice = ops.semi_join(pk, once, ck, cf)
        np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))

    @settings(max_examples=30, deadline=None)
    @given(keys=small_ints)
    def test_segment_sum_mass_conservation(keys):
        ks = jnp.sort(jnp.asarray(keys, jnp.int32))
        vals = jnp.ones_like(ks)
        sums, valid = ops.segment_sum_sorted(ks, vals)
        assert int(jnp.sum(sums)) == len(keys)
        # one emission per distinct key
        assert int(jnp.sum(valid)) == len(set(keys))

    @settings(max_examples=30, deadline=None)
    @given(pk=small_ints, ck=small_ints, cf=small_ints)
    def test_freq_join_dense_equals_sorted(pk, ck, cf):
        n = min(len(ck), len(cf))
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        ck = jnp.asarray(ck[:n], jnp.int32)
        cf = jnp.asarray(cf[:n], jnp.int32)
        dense = ops.freq_join(pk, pf, ck, cf, domain=13)
        sort = ops.freq_join(pk, pf, ck, cf, domain=None)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(sort))

    @settings(max_examples=30, deadline=None)
    @given(pk=small_ints, ck=small_ints, cf=small_ints)
    def test_semi_join_dense_equals_sorted(pk, ck, cf):
        n = min(len(ck), len(cf))
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        ck = jnp.asarray(ck[:n], jnp.int32)
        cf = jnp.asarray(cf[:n], jnp.int32)        # zeros: dead rows
        dense = ops.semi_join(pk, pf, ck, cf, domain=13)
        sort = ops.semi_join(pk, pf, ck, cf, domain=None)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(sort))
else:
    def test_property_invariants_need_hypothesis():
        """Visible skip so a missing dependency is not silent."""
        pytest.importorskip("hypothesis")
