"""The paper's physical operators as XLA kernels.

  ops.py — freq-join (paper §5) with its dense or sorted path chosen by
           ``join_path``, semi-join (0MA sweep, §4.1), sorted
           group-by-SUM (frequency pre-grouping, §4.2/§4.3), weighted
           percentile
  ref.py — pure-jnp O(N·M) oracles (ground truth for tests)
"""

from repro.kernels.ops import (
    freq_join,
    group_by_sum,
    segment_sum_sorted,
    semi_join,
    weighted_percentile,
)

__all__ = [
    "freq_join",
    "group_by_sum",
    "segment_sum_sorted",
    "semi_join",
    "weighted_percentile",
]
