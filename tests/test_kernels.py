"""Per-kernel validation: Pallas (interpret) and XLA twins vs. jnp oracles.

Sweeps shapes (incl. non-block-multiples) and dtypes; hypothesis property
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

from repro.kernels import ops, ref
from repro.kernels.autotune import DENSE_DOMAIN_CAP, KernelConfig

jax.config.update("jax_platform_name", "cpu")


def _rand_tables(rng, np_, nc, key_range, kdt, fdt):
    pk = jnp.asarray(rng.integers(0, key_range, np_), kdt)
    ck = jnp.asarray(rng.integers(0, key_range, nc), kdt)
    pf = jnp.asarray(rng.integers(0, 4, np_), fdt)
    cf = jnp.asarray(rng.integers(0, 4, nc), fdt)
    return pk, pf, ck, cf


SHAPES = [(1024, 1024), (1000, 37), (2048, 4096), (8, 8), (4096, 1000)]
DTYPES = [(jnp.int32, jnp.int32), (jnp.int32, jnp.float32)]


@pytest.mark.parametrize("np_,nc", SHAPES)
@pytest.mark.parametrize("kdt,fdt", DTYPES)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_freq_join_matches_oracle(np_, nc, kdt, fdt, backend):
    rng = np.random.default_rng(np_ * 7919 + nc)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=50, kdt=kdt, fdt=fdt)
    got = ops.freq_join(pk, pf, ck, cf, mode="sum", backend=backend)
    want = ref.freq_join_ref(pk, pf, ck, cf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("np_,nc", SHAPES)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_semi_join_matches_oracle(np_, nc, backend):
    rng = np.random.default_rng(nc * 31 + np_)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=30,
                                  kdt=jnp.int32, fdt=jnp.int32)
    got = ops.semi_join(pk, pf, ck, cf, backend=backend)
    want = ref.semi_join_ref(pk, pf, ck, cf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1024, 1000, 4096, 17, 2048])
@pytest.mark.parametrize("vdt", [jnp.int32, jnp.float32])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_segment_sum_matches_oracle(n, vdt, backend):
    rng = np.random.default_rng(n)
    keys = jnp.sort(jnp.asarray(rng.integers(0, max(2, n // 8), n), jnp.int32))
    vals = jnp.asarray(rng.integers(-3, 5, n), vdt)
    got, gvalid = ops.segment_sum_sorted(keys, vals, backend=backend)
    want, _wfirst = ref.segment_sum_ref(keys, vals)
    # Emission rows differ (ref: first-of-run; kernel: last-of-run), so
    # compare per-key totals, which is the semantic contract.
    def per_key(sums, mask):
        out = {}
        for k, s, m in zip(np.asarray(keys), np.asarray(sums), np.asarray(mask)):
            if m:
                out[int(k)] = out.get(int(k), 0) + s
        return out

    want_first = np.concatenate([[True], np.asarray(keys)[1:] != np.asarray(keys)[:-1]])
    assert per_key(got, gvalid) == per_key(want, want_first)
    # totals preserved
    np.testing.assert_allclose(np.asarray(jnp.sum(got)),
                               np.asarray(jnp.sum(vals)), rtol=1e-5)


@pytest.mark.parametrize("n", [64, 1000])
def test_weighted_percentile_matches_oracle(n):
    rng = np.random.default_rng(n)
    vals = jnp.asarray(rng.normal(size=n), jnp.float32)
    w = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
    for q in (0.1, 0.5, 0.9):
        got = ops.weighted_percentile(vals, w, q)
        want = ref.weighted_percentile_ref(vals, w, q)
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


# ---------------------------------------------------------------------------
# Config-space parametrisation (kernels/autotune.py): every point the
# tuner may pick must match the oracles bitwise, including on shapes that
# don't divide the configured blocks (padding correctness per config).
# ---------------------------------------------------------------------------
JOIN_CONFIGS = [
    KernelConfig(),
    KernelConfig(parent_block_rows=16, child_block_rows=8),
    KernelConfig(parent_block_rows=8, child_block_rows=16),
    KernelConfig(parent_block_rows=32, child_block_rows=32),
    KernelConfig(dense_ratio=0),          # sort/searchsorted always
    KernelConfig(dense_ratio=256),        # dense scatter-add eagerly
]
_JIDS = ["default", "pb16cb8", "pb8cb16", "pb32cb32", "sort", "dense"]


@pytest.mark.parametrize("config", JOIN_CONFIGS, ids=_JIDS)
@pytest.mark.parametrize("np_,nc", [(1000, 37), (2048, 1024), (8, 8)])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_freq_join_config_space_matches_oracle(config, np_, nc, backend):
    rng = np.random.default_rng(np_ * 13 + nc)
    pk, pf, ck, cf = _rand_tables(rng, np_, nc, key_range=50,
                                  kdt=jnp.int32, fdt=jnp.int32)
    got = ops.freq_join(pk, pf, ck, cf, mode="sum", backend=backend,
                        domain=50, config=config)
    want = ref.freq_join_ref(pk, pf, ck, cf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_semi = ops.semi_join(pk, pf, ck, cf, backend=backend,
                             domain=50, config=config)
    want_semi = ref.semi_join_ref(pk, pf, ck, cf)
    np.testing.assert_array_equal(np.asarray(got_semi),
                                  np.asarray(want_semi))


@pytest.mark.parametrize("lanes", [4096, 1024, 2048])
@pytest.mark.parametrize("n", [1000, 17, 4096])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_segment_sum_config_space_matches_default(lanes, n, backend):
    """Any lane width produces bitwise the default's output — the tuner's
    gate invariant, checked directly (incl. non-divisible lengths)."""
    rng = np.random.default_rng(n * 3 + lanes)
    keys = jnp.sort(jnp.asarray(rng.integers(0, max(2, n // 8), n),
                                jnp.int32))
    vals = jnp.asarray(rng.integers(-3, 5, n), jnp.int32)
    base = ops.segment_sum_sorted(keys, vals, backend=backend)
    got = ops.segment_sum_sorted(keys, vals, backend=backend,
                                 config=KernelConfig(lanes_wide=lanes))
    for b, g in zip(base, got):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(g))


@pytest.mark.parametrize("distinct", [1, 3, 37])
def test_pallas_segment_sum_runs_across_rows_and_blocks(distinct):
    """Runs longer than a 128-lane row and a (8, 128) block: the row and
    block carries of the kernel, against the oracle row by row."""
    n = 3 * 1024 + 77
    rng = np.random.default_rng(distinct)
    keys = jnp.sort(jnp.asarray(rng.integers(0, distinct, n), jnp.int32))
    vals = jnp.asarray(rng.integers(-3, 5, n), jnp.int32)
    got, valid = ops.segment_sum_sorted(keys, vals, backend="pallas")
    xla, xla_valid = ops.segment_sum_sorted(keys, vals, backend="xla")
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(xla_valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(xla))
    want, first = ref.segment_sum_ref(keys, vals)
    want, first = np.asarray(want), np.asarray(first)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(valid)],
                                  want[first])


@pytest.mark.parametrize("platform,interpret",
                         [("tpu", False), ("cpu", True), ("gpu", True)])
def test_interpret_follows_platform(monkeypatch, platform, interpret):
    """Pallas kernels compile on a TPU and run through the interpreter
    anywhere else, with no option to say otherwise: every kernel entry
    point hands its jitted implementation the platform's answer."""
    k = jnp.arange(8, dtype=jnp.int32)
    seen = []

    def spy(*args, interpret, **kwargs):
        seen.append(interpret)

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(ops, "_freq_join_impl", spy)
    monkeypatch.setattr(ops, "_segment_sum_impl", spy)
    assert ops.interpret_mode() is interpret
    ops.freq_join(k, k, k, k, backend="pallas")
    ops.semi_join(k, k, k, k, backend="pallas")
    ops.segment_sum_sorted(k, k, backend="pallas")
    assert seen == [interpret] * 3


# ---------------------------------------------------------------------------
# Dense-domain dispatch boundary
# ---------------------------------------------------------------------------
def test_dense_ok_boundary_and_cap():
    cfg = KernelConfig(dense_ratio=4, dense_floor=1 << 10)
    assert cfg.dense_ok(1 << 10, 8)            # at the floor: dense
    assert not cfg.dense_ok((1 << 10) + 1, 8)  # just past: sort
    low_floor = KernelConfig(dense_ratio=4, dense_floor=1)
    assert low_floor.dense_ok(4 * 100, 100)    # at ratio*nc: dense
    assert not low_floor.dense_ok(4 * 100 + 1, 100)
    assert not cfg.dense_ok(None, 100)         # unknown domain: sort
    assert not KernelConfig(dense_ratio=0).dense_ok(16, 100)  # disabled
    # the structural int32 accumulator cap binds whatever the ratio says
    eager = KernelConfig(dense_ratio=1 << 30, dense_floor=1 << 30)
    assert not eager.dense_ok(DENSE_DOMAIN_CAP, 100)
    assert eager.dense_ok(DENSE_DOMAIN_CAP - 1, 100) is True


def test_dense_domain_cap_falls_back_to_sort():
    """domain == 2^31 with a dense-eager config must quietly use the sort
    path (no 2 GiB accumulator) and still match the oracle."""
    rng = np.random.default_rng(7)
    pk, pf, ck, cf = _rand_tables(rng, 64, 64, key_range=40,
                                  kdt=jnp.int32, fdt=jnp.int32)
    cfg = KernelConfig(dense_ratio=1 << 30, dense_floor=1 << 30)
    got = ops.freq_join(pk, pf, ck, cf, backend="xla",
                        domain=DENSE_DOMAIN_CAP, config=cfg)
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
    dense = ops.freq_join(pk, pf, ck, cf, mode=mode, backend="xla",
                          domain=dom,
                          config=KernelConfig(dense_ratio=1 << 20))
    sort = ops.freq_join(pk, pf, ck, cf, mode=mode, backend="xla",
                         domain=dom, config=KernelConfig(dense_ratio=0))
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
                              jnp.concatenate([f1, f2]), backend="xla")
        parts = (ops.freq_join(pk, pf, c1, f1, backend="xla")
                 + ops.freq_join(pk, pf, c2, f2, backend="xla"))
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(parts))

    @settings(max_examples=30, deadline=None)
    @given(pk=small_ints, ck=small_ints)
    def test_semi_join_idempotent(pk, ck):
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        ck = jnp.asarray(ck, jnp.int32)
        cf = jnp.ones_like(ck)
        once = ops.semi_join(pk, pf, ck, cf, backend="xla")
        twice = ops.semi_join(pk, once, ck, cf, backend="xla")
        np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))

    @settings(max_examples=30, deadline=None)
    @given(keys=small_ints)
    def test_segment_sum_mass_conservation(keys):
        ks = jnp.sort(jnp.asarray(keys, jnp.int32))
        vals = jnp.ones_like(ks)
        sums, valid = ops.segment_sum_sorted(ks, vals, backend="xla")
        assert int(jnp.sum(sums)) == len(keys)
        # one emission per distinct key
        assert int(jnp.sum(valid)) == len(set(keys))

    @settings(max_examples=20, deadline=None)
    @given(pk=small_ints, ck=small_ints)
    def test_pallas_equals_xla(pk, ck):
        pk = jnp.asarray(pk, jnp.int32)
        pf = jnp.ones_like(pk)
        ck = jnp.asarray(ck, jnp.int32)
        cf = jnp.ones_like(ck)
        a = ops.freq_join(pk, pf, ck, cf, backend="xla")
        b = ops.freq_join(pk, pf, ck, cf, backend="pallas")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
else:
    def test_property_invariants_need_hypothesis():
        """Visible skip so a missing dependency is not silent."""
        pytest.importorskip("hypothesis")
