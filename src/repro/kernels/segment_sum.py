"""Sorted segmented-sum Pallas TPU kernel (paper §4.2/§4.3 pre-grouping).

The frequency-propagation rewrite repeatedly needs `GROUP BY key, SUM(val)`
over a key-sorted column pair — e.g. compressing a child relation to
(distinct key, total frequency) before a FreqJoin, and the final aggregate.
On TPU this is a single sequential-grid pass:

  * the column is laid out as ``(rows, 128)`` in flat row-major order and
    walked in ``(8·k, 128)`` VMEM blocks — the native 32-bit tile, so the
    block obeys the (8, 128) tiling rule at any block count;
  * run boundaries come from *shifted key columns* (prev/next) that the
    ops.py wrapper materialises once — no cross-block peeking inside the
    kernel;
  * within a block, the segmented cumulative sum is two log-step scans of
    shifted adds with masks (``pltpu.roll`` + select, both native VPU/XLU
    operations): first along the 128 lanes of every row, then along the
    rows over each row's tail value;
  * the TPU grid runs in order, so an SMEM scalar carries the running sum
    of a run that spans blocks.

Emission convention: the run total is written at the LAST row of each run
(valid=1 there, 0 elsewhere).  Consumers never care where a group's row
sits, only that each distinct key appears exactly once with its total —
rows with valid=0 carry value 0 and are dead by the engine's freq=0
convention.

This kernel is shared verbatim by the MoE layer (expert-load counting is a
guarded COUNT(*) GROUP BY expert — see DESIGN.md §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Default elements per block: (8, 128), one native tile.  The width is a
# static argument so the autotuner can search it per shape bucket (any
# multiple of 8·128); this constant is only the untuned default.
LANES_WIDE = 1024


def _shift(x, d: int, axis: int, fill):
    """``x`` moved ``d`` places toward higher indices along ``axis``, the
    first ``d`` places filled with ``fill``."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(pos >= d, pltpu.roll(x, d, axis), fill)


def _seg_scan(s, f, axis: int):
    """Inclusive segmented sum along ``axis`` (Hillis–Steele): ``f`` = 1
    where a run starts.  Returns (sums, any-start-so-far)."""
    zero = jnp.zeros((), s.dtype)
    d = 1
    while d < s.shape[axis]:
        s = jnp.where(f > 0, s, s + _shift(s, d, axis, zero))
        f = jnp.maximum(f, _shift(f, d, axis, 0))
        d *= 2
    return s, f


def _segment_sum_kernel(keys_ref, pkeys_ref, nkeys_ref, vals_ref,
                        out_ref, valid_ref, carry_ref, *, n_total: int):
    j = pl.program_id(0)
    rows = keys_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        carry_ref[0] = jnp.zeros((), carry_ref.dtype)

    keys = keys_ref[...]
    v = vals_ref[...]
    shape = keys.shape
    gpos = (j * (rows * LANES)
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    starts = ((keys != pkeys_ref[...]) | (gpos == 0)).astype(jnp.int32)
    is_last = (keys != nkeys_ref[...]) | (gpos == n_total - 1)

    # 1) runs within each row of 128 lanes
    seg, started = _seg_scan(v, starts, axis=1)
    # 2) each row's tail (lane 127) carries into the next row: scan the
    #    tails down the rows, then shift by one row for the exclusive carry
    tail = jnp.broadcast_to(seg[:, LANES - 1:], shape)
    tail_started = jnp.broadcast_to(started[:, LANES - 1:], shape)
    tail, tail_started = _seg_scan(tail, tail_started, axis=0)
    zero = jnp.zeros((), v.dtype)
    row_carry = _shift(tail, 1, 0, zero)
    row_started = _shift(tail_started, 1, 0, 0)
    # 3) rows with no run start before them also continue the run carried
    #    in from the previous block
    row_carry = row_carry + jnp.where(row_started > 0, zero, carry_ref[0])
    seg = seg + jnp.where(started > 0, zero, row_carry)
    carry_ref[0] = seg[rows - 1, LANES - 1]

    out_ref[...] = jnp.where(is_last, seg, zero)
    valid_ref[...] = is_last.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "lanes_wide"))
def segment_sum_pallas(sorted_keys, values, *, interpret: bool = False,
                       lanes_wide: int = LANES_WIDE):
    """Segmented sum over key-sorted arrays.

    Contract: ``lanes_wide`` is a multiple of 8·128; len % lanes_wide == 0;
    padded tail rows sort last (keys >= all real keys) and carry value 0.
    Returns (sums, valid) with run totals at the last row of each run.
    """
    n = sorted_keys.shape[0]
    if lanes_wide % (SUBLANES * LANES):
        raise ValueError(f"lanes_wide={lanes_wide} is not a multiple of "
                         f"{SUBLANES * LANES} (one (8, 128) tile)")
    if n % lanes_wide:
        raise ValueError(f"length {n} is not a multiple of "
                         f"lanes_wide={lanes_wide}")
    block_rows = lanes_wide // LANES

    pkeys = jnp.roll(sorted_keys, 1)
    nkeys = jnp.roll(sorted_keys, -1)

    def as2d(a):
        return a.reshape(n // LANES, LANES)

    spec = pl.BlockSpec((block_rows, LANES), lambda j: (j, 0))
    out, valid = pl.pallas_call(
        functools.partial(_segment_sum_kernel, n_total=n),
        grid=(n // lanes_wide,),
        in_specs=[spec] * 4,
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((n // LANES, LANES), values.dtype),
            jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), values.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(as2d(sorted_keys), as2d(pkeys), as2d(nkeys), as2d(values))
    return out.reshape(n), valid.reshape(n).astype(bool)
