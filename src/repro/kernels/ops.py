"""The kernel ops: freq-join, semi-join, segment sum, weighted percentile.

Each op is an XLA formulation (scatter-add/gather, sort/searchsorted,
segment-sum), the same on every platform, and what the distributed
executor traces through `shard_map` (collectives compose with XLA ops).
All are tested against the O(N·M) oracles in ref.py.

A join takes one of two paths, and ``join_path(domain, n_child)`` alone
decides which: the dense path (one scatter-add into a domain-sized
accumulator, one gather) where the keys' declared domain is small enough
for the child, else the sorted path (argsort and two binary searches).
The kernel's dispatch asks it, and so does the executor's tally, so a
tally counts what runs.

The public entry points are NOT jitted: they call jitted
implementations that carry the join's mode, domain and limbs as static
arguments.  Under an outer ``jax.jit`` trace the wrappers inline like any
other Python, so compiled plans pay nothing for the indirection.  The
jitted implementations inline too (``inline=True``): a program that calls
a kernel from several sites with one signature would otherwise call one
shared function, whose instructions lose the call sites' scopes on their
way through XLA, so a device trace could not name the operator behind
them.

Each public entry point opens a ``jax.named_scope`` of its own name
(``freq_join``, ``semi_join``, ``segment_sum``, ``group_by_sum``,
``weighted_percentile``); the sort-based freq-join names its child
sort (``sort``) and its two binary searches (``search``), the dense one
its scatter-add (``scatter``) and its gather (``gather``).  Scopes are HLO
``op_name`` metadata only: they change neither fusion nor the code that
runs, and they let a profiler trace name the kernel behind every device
operation (``repro.core.scopes``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the dense path's crossover: a declared domain of at most
# max(DENSE_RATIO · child rows, DENSE_FLOOR) slots goes dense (on a TPU
# v5e, TPC-H SF10's 2^23 partsupp rows into 100,001 supplier slots take
# 56.4 ms dense against ~1.1 s sorted; PERF.md) ...
DENSE_RATIO = 4
DENSE_FLOOR = 1 << 20
# ... and never past what int32 packed keys can index
DENSE_DOMAIN_CAP = 1 << 31


# --------------------------------------------------------------------------
# FreqJoin
# --------------------------------------------------------------------------
def join_path(domain: int | None, n_child: int) -> str:
    """The path ``freq_join``/``semi_join`` take for a child of ``n_child``
    rows whose packed keys lie in ``[0, domain)`` (None: unknown):
    ``"dense"`` (one scatter-add, one gather) where the domain is declared,
    at most ``max(DENSE_RATIO · n_child, DENSE_FLOOR)`` and below
    ``DENSE_DOMAIN_CAP``; ``"sorted"`` (argsort and two binary searches)
    otherwise."""
    if (domain is not None
            and domain <= max(DENSE_RATIO * n_child, DENSE_FLOOR)
            and domain < DENSE_DOMAIN_CAP):
        return "dense"
    return "sorted"


def freq_join(parent_keys, parent_freq, child_keys, child_freq, *,
              mode: str = "sum", domain: int | None = None,
              limbs: tuple[int, int] | None = None):
    """R ⋉^freq S — returns updated parent frequencies (paper §5).

    mode="sum": ℕ-semiring (COUNT/SUM propagation);
    mode="any": Boolean semiring (semi-join).

    `domain`: the packed join keys' declared domain, None when unknown.
    Where ``join_path`` says dense, the sort+searchsorted pipeline
    collapses to one scatter-add into a domain-sized accumulator plus one
    gather — O(N) instead of O(N log N), and on TPU the exact memory
    pattern of an embedding-gradient update.  Keys outside the domain
    contribute nothing.  Otherwise it sorts.

    64-bit frequencies: XLA's s64 scatter-add and gather cost 11.9× and
    2.7× their s32 twins on a v5e (PERF.md), so the dense path
    scatters and gathers 32-bit words only.  The child's frequencies are
    split into ``limbs`` = (bits a limb, limbs), which the caller chooses
    from the statistics (``repro.core.stats.limb_split``); each limb is
    scatter-added and gathered as uint32, exact, and the limbs are
    recombined in s64.  64-bit frequencies on the dense path without
    ``limbs`` are refused.
    """
    with jax.named_scope("freq_join"):
        return _freq_join_impl(parent_keys, parent_freq, child_keys,
                               child_freq, mode=mode, domain=domain,
                               limbs=limbs)


def _scatter_gather(parent_keys, child_keys, values, domain: int):
    """``acc[parent_keys]`` where ``acc[k]`` sums ``values`` over the child
    rows of key k; keys outside ``[0, domain)`` contribute and receive
    nothing."""
    # scatter-add with EXPLICIT masking: ``mode="drop"`` alone drops
    # indices >= domain but follows NumPy semantics for negative ones
    # (wrapping them onto valid slots), which would corrupt acc[domain-1]
    # whenever dead/out-of-range child keys are negative — mask to zero
    # contribution instead
    with jax.named_scope("scatter"):
        live = (child_keys >= 0) & (child_keys < domain)
        acc = jnp.zeros((domain,), values.dtype)
        acc = acc.at[jnp.clip(child_keys, 0, domain - 1)].add(
            jnp.where(live, values, 0))
    with jax.named_scope("gather"):
        mult = acc[jnp.clip(parent_keys, 0, domain - 1)]
        return jnp.where((parent_keys >= 0) & (parent_keys < domain), mult,
                         0)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("mode", "domain", "limbs"))
def _freq_join_impl(parent_keys, parent_freq, child_keys, child_freq, *,
                    mode: str, domain: int | None,
                    limbs: tuple[int, int] | None = None):
    if join_path(domain, child_keys.shape[0]) == "dense":
        dt = parent_freq.dtype
        if mode == "any":
            # a count of live child rows, below 2^31 at any width
            mult = _scatter_gather(parent_keys, child_keys,
                                   (child_freq > 0).astype(jnp.int32),
                                   domain)
            return parent_freq * (mult > 0).astype(dt)
        if dt.itemsize <= 4:
            mult = _scatter_gather(parent_keys, child_keys, child_freq,
                                   domain)
            return parent_freq * mult.astype(dt)
        if limbs is None:
            raise ValueError(
                "64-bit frequencies on the dense path need their limb "
                "split (repro.core.stats.limb_split)")
        bits, n = limbs
        mask = (1 << bits) - 1
        mult = jnp.zeros(parent_freq.shape, dt)
        for i in range(n):
            limb = ((child_freq >> (bits * i)) & mask).astype(jnp.uint32)
            part = _scatter_gather(parent_keys, child_keys, limb, domain)
            mult = mult + (part.astype(dt) << (bits * i))
        return parent_freq * mult
    with jax.named_scope("sort"):
        order = jnp.argsort(child_keys)
        ck = child_keys[order]
        cf = child_freq[order]
    if mode == "any":
        cf = (cf > 0).astype(parent_freq.dtype)
    zero = jnp.zeros((1,), cf.dtype)
    prefix = jnp.concatenate([zero, jnp.cumsum(cf)])
    with jax.named_scope("search"):
        lo = jnp.searchsorted(ck, parent_keys, side="left")
        hi = jnp.searchsorted(ck, parent_keys, side="right")
    mult = (prefix[hi] - prefix[lo]).astype(parent_freq.dtype)
    if mode == "any":
        mult = (mult > 0).astype(parent_freq.dtype)
    return parent_freq * mult


def semi_join(parent_keys, parent_freq, child_keys, child_freq, *,
              domain: int | None = None):
    """R ⋉ S over live tuples (0MA sweep step, paper §4.1)."""
    with jax.named_scope("semi_join"):
        return _freq_join_impl(parent_keys, parent_freq, child_keys,
                               child_freq, mode="any", domain=domain)


# --------------------------------------------------------------------------
# Segment sum (sorted group-by-SUM)
# --------------------------------------------------------------------------
def segment_sum_sorted(sorted_keys, values):
    """GROUP BY key, SUM(value) over key-sorted input.

    Returns (sums, valid): run total at the LAST row of each run.
    """
    with jax.named_scope("segment_sum"):
        return _segment_sum_impl(sorted_keys, values)


@functools.partial(jax.jit, inline=True)
def _segment_sum_impl(sorted_keys, values):
    n = sorted_keys.shape[0]
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    is_last = jnp.concatenate(
        [sorted_keys[1:] != sorted_keys[:-1], jnp.ones((1,), bool)])
    run_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    sums = jax.ops.segment_sum(values, run_id, num_segments=n)
    out = jnp.where(is_last, jnp.take(sums, run_id), jnp.zeros((), values.dtype))
    return out, is_last


def group_by_sum(keys, values):
    """Unsorted group-by: sort once, then segment-sum.  Returns
    (sorted_keys, sums, valid) so downstream FreqJoins can reuse the sort."""
    with jax.named_scope("group_by_sum"):
        order = jnp.argsort(keys)
        ks = keys[order]
        vs = values[order]
        sums, valid = segment_sum_sorted(ks, vs)
        return ks, sums, valid


# --------------------------------------------------------------------------
# Weighted percentile (MEDIAN rewrite, paper §4.2)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, inline=True)
def weighted_percentile(values, weights, q):
    """PERCENTILE(q, A, freq) — lower-interpolation weighted percentile.

    Rows with weight 0 (dead tuples) are ignored: their values are moved to
    +inf before the sort so they never land below the target mass.  With
    no rows at all the answer is that same +inf, as if all were dead.
    """
    with jax.named_scope("weighted_percentile"):
        big = jnp.asarray(jnp.finfo(values.dtype).max if
                          jnp.issubdtype(values.dtype, jnp.floating)
                          else jnp.iinfo(values.dtype).max, values.dtype)
        if values.shape[0] == 0:
            return big
        v = jnp.where(weights > 0, values, big)
        order = jnp.argsort(v)
        vs = v[order]
        acc_dtype = jnp.float64 if jax.config.jax_enable_x64 \
            else jnp.float32
        ws = weights[order].astype(acc_dtype)
        cw = jnp.cumsum(ws)
        target = q * cw[-1]
        idx = jnp.clip(jnp.searchsorted(cw, target, side="left"), 0,
                       values.shape[0] - 1)
        return vs[idx]
