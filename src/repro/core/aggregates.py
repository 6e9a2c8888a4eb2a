"""Frequency-aware aggregate evaluation (paper §4.2 rewrites).

Once the bottom-up sweep finishes, the root relation carries frequencies
that encode the bag multiplicity of every answer tuple.  Standard aggregates
are rewritten to operate on (value, frequency) pairs:

    COUNT(*)  → SUM(c)                    COUNT(A)      → SUM(c·nonnull(A))
    SUM(A)    → SUM(A·c)                  AVG(A)        → SUM(A·c)/SUM(c)
    MEDIAN(A) → weighted-percentile(A,c)  MIN/MAX       → over live rows
    COUNT(DISTINCT A) / SUM(DISTINCT A)   → over distinct live values

`dedup=True` (0MA mode) aggregates with set semantics: weights become
live-row indicators.  GROUP BY is evaluated with one sort of the root
relation + segmented reductions — never by materialising groups.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.core.query import Agg
from repro.tables.table import pack_keys


def _acc_dtype(dt):
    """A sum's accumulator: floats keep their type; integers sum in the
    width of the counts, which ``dt`` (the frequencies' dtype, or a
    value's promoted with it) carries — int32, or int64 in a program with
    64-bit counts."""
    if jnp.issubdtype(dt, jnp.floating):
        return dt
    return jnp.promote_types(dt, jnp.int32)


def _big(dt):
    return jnp.asarray(
        jnp.finfo(dt).max if jnp.issubdtype(dt, jnp.floating)
        else jnp.iinfo(dt).max, dt)


def _small(dt):
    return jnp.asarray(
        jnp.finfo(dt).min if jnp.issubdtype(dt, jnp.floating)
        else jnp.iinfo(dt).min, dt)


def _distinct_mask(values, live):
    """Boolean mask (in sorted order) marking the first live occurrence of
    each distinct live value; returns (sorted_values, mask)."""
    v = jnp.where(live, values, _big(values.dtype))
    order = jnp.argsort(v)
    vs = v[order]
    ls = live[order]
    first = jnp.concatenate([jnp.ones((1,), bool), vs[1:] != vs[:-1]])
    return vs, first & ls


def scalar_aggregate(ag: Agg, cols: dict[str, jax.Array], freq: jax.Array,
                     dedup: bool) -> jax.Array:
    w = (freq > 0).astype(freq.dtype) if dedup else freq
    live = freq > 0
    if ag.func == "count" and ag.var is None:
        return jnp.sum(w.astype(_acc_dtype(w.dtype)))
    a = cols[ag.var] if ag.var is not None else None
    if ag.distinct:
        vs, mask = _distinct_mask(a, live)
        if ag.func == "count":
            return jnp.sum(mask.astype(jnp.int32))
        if ag.func == "sum":
            return jnp.sum(jnp.where(mask, vs, 0).astype(_acc_dtype(a.dtype)))
        if ag.func == "avg":
            s = jnp.sum(jnp.where(mask, vs, 0).astype(jnp.float32))
            n = jnp.sum(mask.astype(jnp.float32))
            return s / jnp.maximum(n, 1)
        # min/max distinct == min/max
    if ag.func == "count":
        return jnp.sum(w.astype(_acc_dtype(w.dtype)))  # nulls unsupported
    if ag.func == "sum":
        acc = _acc_dtype(jnp.promote_types(a.dtype, w.dtype))
        return jnp.sum(a.astype(acc) * w.astype(acc))
    if ag.func == "avg":
        s = jnp.sum(a.astype(jnp.float64 if jax.config.jax_enable_x64
                             else jnp.float32) * w)
        n = jnp.sum(w).astype(s.dtype)
        return s / jnp.maximum(n, 1)
    # no live rows — or none at all, after an eager join that matched
    # nothing — gives the identity, as a dead row would
    if ag.func == "min":
        return jnp.min(jnp.where(live, a, _big(a.dtype)),
                       initial=_big(a.dtype))
    if ag.func == "max":
        return jnp.max(jnp.where(live, a, _small(a.dtype)),
                       initial=_small(a.dtype))
    if ag.func == "median":
        return ops.weighted_percentile(a, w, 0.5)
    raise NotImplementedError(ag.func)


def grouped_aggregate(group_by: tuple[str, ...], aggregates: tuple[Agg, ...],
                      cols: dict[str, jax.Array], freq: jax.Array,
                      domains: dict[str, int | None], dedup: bool):
    """GROUP BY via one sort + segmented reductions.

    Returns (out_cols, out_valid): fixed capacity == input capacity; rows
    with out_valid=False are dead.  Group rows sit at the last row of each
    sorted run (segment-sum emission convention).
    """
    w = (freq > 0).astype(freq.dtype) if dedup else freq
    key = pack_keys([cols[g] for g in group_by],
                    [domains.get(g) for g in group_by])
    # dead rows sort last and never mark a group as live
    key = jnp.where(freq > 0, key, _big(key.dtype))
    order = jnp.argsort(key)
    ks = key[order]
    n = ks.shape[0]
    # [:n] keeps an empty root state empty
    is_last = jnp.concatenate([ks[1:] != ks[:-1], jnp.ones((1,), bool)])[:n]
    is_first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])[:n]
    run_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    live_s = (freq > 0)[order]
    w_s = w[order]

    def seg_sum(v):
        return jnp.take(jax.ops.segment_sum(v, run_id, num_segments=n), run_id)

    out_cols: dict[str, jax.Array] = {g: cols[g][order] for g in group_by}
    group_live = seg_sum(live_s.astype(jnp.int32)) > 0
    out_valid = is_last & group_live

    for ag in aggregates:
        a = cols[ag.var][order] if ag.var is not None else None
        if ag.distinct:
            raise NotImplementedError("DISTINCT inside GROUP BY")
        if ag.func == "count":
            out = seg_sum(w_s.astype(_acc_dtype(w_s.dtype)))
        elif ag.func == "sum":
            acc = _acc_dtype(jnp.promote_types(a.dtype, w_s.dtype))
            out = seg_sum(a.astype(acc) * w_s.astype(acc))
        elif ag.func == "avg":
            f = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            s = seg_sum(a.astype(f) * w_s.astype(f))
            c = seg_sum(w_s.astype(f))
            out = s / jnp.maximum(c, 1)
        elif ag.func == "min":
            v = jnp.where(live_s, a, _big(a.dtype))
            out = jnp.take(jax.ops.segment_min(v, run_id, num_segments=n),
                           run_id)
        elif ag.func == "max":
            v = jnp.where(live_s, a, _small(a.dtype))
            out = jnp.take(jax.ops.segment_max(v, run_id, num_segments=n),
                           run_id)
        elif ag.func == "median":
            out = _grouped_weighted_median(ks, a, w_s, live_s)
        else:
            raise NotImplementedError(f"{ag.func} with GROUP BY")
        out_cols[ag.name] = out
    return out_cols, out_valid


def _grouped_weighted_median(sorted_keys, values, weights, live):
    """Weighted median per group: one lexicographic sort by (group, value),
    then a segment-relative weighted-cumsum threshold — no group ever
    materialises (paper §4.2's PERCENTILE(0.5, A, c) generalised to
    GROUP BY)."""
    n = sorted_keys.shape[0]
    big = _big(values.dtype)
    v = jnp.where(live, values, big)
    # stable sort by value within already-key-sorted runs: sort (key, value)
    order = jnp.lexsort((v, sorted_keys))
    ks = sorted_keys[order]
    vs = v[order]
    ws = jnp.where(live[order], weights[order], 0).astype(
        jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    is_first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])[:n]
    run_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    cw = jnp.cumsum(ws)
    run_start_cw = jnp.take(
        jax.ops.segment_min(jnp.where(is_first, cw - ws, jnp.inf),
                            run_id, num_segments=n), run_id)
    rel_cw = cw - run_start_cw                        # within-group cumsum
    total = jnp.take(jax.ops.segment_max(rel_cw, run_id, num_segments=n),
                     run_id)
    # first row of each group whose cumulative weight reaches half
    reach = rel_cw >= 0.5 * total
    cand_v = jnp.where(reach, vs, big)
    med = jnp.take(jax.ops.segment_min(cand_v, run_id, num_segments=n),
                   run_id)
    # scatter medians back to the ORIGINAL (group-sorted) row order
    out = jnp.zeros(n, values.dtype).at[order].set(med.astype(values.dtype))
    return out
