"""The edge list of the Graph500 Kronecker generator (Benchmark
Specification v3.0.0, section 3), and the plain numpy reference of the
configuration's walk and star counts over it.

``generate`` runs the specification's generator in one jitted call on the
default device and copies the two columns to the host:

* M = edgefactor · 2^SCALE edges, each drawn by SCALE levels of the
  2×2 initiator [[A, B], [C, D]]: at level i the edge picks the quadrant
  (row bit, column bit) — row 1 with probability C + D, then column 1 with
  probability B / (A + B) on row 0 and D / (C + D) on row 1 — and adds
  2^i times those bits to its start and end vertex;
* the vertex labels are then permuted at random, and the edge list
  shuffled, both from the seed.

Edges are directed (start, end) tuples as the generator emits them;
self-loops and duplicate edges are kept, since the specification's
kernel-1 input holds them and the counts are of homomorphisms.

The reference counts the homomorphisms of each query's pattern from the
degree vectors and one pass over the edges, in numpy int64.  It imports
nothing of the engine.
"""

from __future__ import annotations

import functools

import numpy as np

# every count the comparison reads has to stay exact in float64 there
EXACT_BELOW = 2 ** 52


def generate(spec: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    import jax

    scale, edgefactor = int(spec["scale"]), int(spec["edgefactor"])
    if spec["rows"]["edge"] != edgefactor << scale:
        raise ValueError(f"rows {spec['rows']['edge']} != edgefactor · "
                         f"2^scale = {edgefactor << scale}")
    key = jax.random.key(int(np.random.default_rng([seed, 0])
                             .integers(0, 2 ** 31)))
    src, dst = kronecker(key, scale, edgefactor,
                         tuple(spec["initiator"]), True)
    return {"edge": {"src": np.asarray(src), "dst": np.asarray(dst)}}


def kronecker(key, scale: int, edgefactor: int,
              initiator: tuple[float, float, float, float],
              permute: bool = True):
    """The specification's edge list as two int32 device arrays.  With
    ``permute`` False the labels and the order are as the levels drew
    them, so bit i of a vertex is level i's quadrant."""
    return _kronecker_fn(scale, edgefactor, tuple(initiator), permute)(key)


@functools.cache
def _kronecker_fn(scale, edgefactor, initiator, permute):
    import jax
    import jax.numpy as jnp

    a, b, c, _d = initiator
    m = edgefactor << scale
    ab = a + b
    c_norm, a_norm = c / (1 - ab), a / ab

    def build(key):
        k_bits, k_label, k_order = jax.random.split(key, 3)

        def level(i, ij):
            src, dst = ij
            u = jax.random.uniform(jax.random.fold_in(k_bits, i), (2, m))
            ii = u[0] > ab
            jj = u[1] > jnp.where(ii, c_norm, a_norm)
            return (src | (ii.astype(jnp.int32) << i),
                    dst | (jj.astype(jnp.int32) << i))

        zero = jnp.zeros((m,), jnp.int32)
        src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
        if permute:
            label = jax.random.permutation(k_label, 1 << scale) \
                .astype(jnp.int32)
            order = jax.random.permutation(k_order, m)
            src, dst = label[src][order], label[dst][order]
        return src, dst

    return jax.jit(build)


def _counts(data, dtype) -> dict[str, dict[str, np.generic]]:
    e = data["edge"]
    src, dst = e["src"].astype(np.int64), e["dst"].astype(np.int64)
    n = int(max(src.max(), dst.max())) + 1
    out = np.bincount(src, minlength=n).astype(dtype)
    inn = np.bincount(dst, minlength=n).astype(dtype)
    counts = {
        # e0.dst = e1.src: a middle vertex, an edge in and an edge out
        "walk2": np.sum(inn * out, dtype=dtype),
        # e0.dst = e1.src ∧ e1.dst = e2.src: per middle edge (a, b), the
        # edges into a times the edges out of b
        "walk3": np.sum(inn[src] * out[dst], dtype=dtype),
        # e0.src = e1.src: two edges out of one vertex
        "star2": np.sum(out * out, dtype=dtype),
    }
    return {q: {"count(*)": v} for q, v in counts.items()}


def reference(spec: dict, data: dict) -> dict[str, dict[str, np.generic]]:
    """Each query's count, exact in int64.  Refuses data on which a count
    reaches 2^52, where the comparison's float64 would round it."""
    want = _counts(data, np.int64)
    for q, v in want.items():
        if v["count(*)"] >= EXACT_BELOW:
            raise ValueError(f"{q} counts {v['count(*)']}, not below 2^52")
    return want


def control(spec: dict, data: dict) -> dict[str, dict[str, np.generic]]:
    """The reference one width down: degrees, products and sums in int32,
    which wrap as the engine's counts did before they were widened.  It
    has to fail the comparison."""
    return _counts(data, np.int32)
