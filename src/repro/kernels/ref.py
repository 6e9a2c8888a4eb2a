"""Pure-jnp oracles for every kernel in repro.kernels.

These are the semantic ground truth: O(N·M) / unvectorised-but-obvious
implementations that the kernels in ops.py are tested against.
"""

from __future__ import annotations

import jax.numpy as jnp


def freq_join_ref(parent_keys, parent_freq, child_keys, child_freq):
    """FreqJoin (paper §5), ℕ-semiring sum-product.

    For each parent row i:
        mult_i = Σ_j child_freq[j] · [child_keys[j] == parent_keys[i]]
        out_i  = parent_freq[i] · mult_i

    A dangling parent tuple (no join partner) gets out_i = 0, which is the
    static-shape analogue of the paper's "if r.c = 0 then delete".
    """
    eq = parent_keys[:, None] == child_keys[None, :]          # [Np, Nc]
    mult = jnp.sum(jnp.where(eq, child_freq[None, :], 0), axis=1)
    return parent_freq * mult.astype(parent_freq.dtype)


def semi_join_ref(parent_keys, parent_freq, child_keys, child_freq):
    """Semi-join (0MA sweep, §4.1): Boolean semiring specialisation.

    out_i = parent_freq[i] if parent_keys[i] has a live join partner else 0.
    """
    eq = parent_keys[:, None] == child_keys[None, :]
    live = eq & (child_freq[None, :] > 0)
    return jnp.where(jnp.any(live, axis=1), parent_freq, 0)


def segment_sum_ref(sorted_keys, values):
    """Group-by-SUM over a key-sorted array (paper §4.2 pre-grouping).

    Returns (out_values, out_valid):
      out_values[i] = Σ_j values[j] over the run of keys equal to
                      sorted_keys[i], emitted at the FIRST row of each run
                      (0 elsewhere);
      out_valid[i]  = True iff row i is the first row of its run.

    Dead rows (freq 0) are the caller's concern: they carry value 0 and thus
    do not perturb sums; a run consisting only of dead rows emits sum 0.
    """
    n = sorted_keys.shape[0]
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]]
    )
    # run id per row, then one-hot sum — O(N^2) oracle, clear and exact.
    run_id = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    eq = run_id[:, None] == run_id[None, :]                    # [N, N]
    run_sums = jnp.sum(jnp.where(eq, values[None, :], 0), axis=1)
    out = jnp.where(is_first, run_sums.astype(values.dtype), 0)
    return out, is_first


def weighted_percentile_ref(values, weights, q):
    """Weighted percentile with *lower* interpolation over live rows.

    Equivalent to Spark's PERCENTILE(q, A, freq) on the expanded bag:
    the smallest v such that cumweight(v) >= q * totalweight.
    Rows with weight 0 are ignored.  Oracle is a simple sort + scan.
    """
    order = jnp.argsort(values)
    v = values[order]
    w = weights[order].astype(jnp.float64 if values.dtype == jnp.float64 else jnp.float32)
    cw = jnp.cumsum(w)
    total = cw[-1]
    target = q * total
    idx = jnp.searchsorted(cw, target, side="left")
    idx = jnp.clip(idx, 0, values.shape[0] - 1)
    return v[idx]
