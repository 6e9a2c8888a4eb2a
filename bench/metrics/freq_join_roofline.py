"""freq_join_roofline: kernels — the freq-join's share of its
roofline, in percent: the least time the window's freq-join kernel calls
could take per executed program, moving their least bytes at the device's
HBM bandwidth, over ``freq_join_device_ms``.  Memory bounds the join: it
does one multiply per parent row.

The least bytes of one call (``least_bytes``) are the int32 keys of the
child's and the parent's rows, read once; the child's frequencies read
and the parent's read and written, at the counts' width w (4 or 8 bytes);
and the dense path's accumulator of ``slots`` counts written and read.
The service adds, per executed program and by width, the calls' child
plus parent rows (``join_rows<bits>``), parent rows
(``join_parent_rows<bits>``) and slots (``join_slots<bits>``).  A service
without those counters, a window that ran no program, or a run without
``freq_join_device_ms`` reads nothing.
"""

from bench import common

# HBM bandwidth by ``device_kind`` (Google Cloud documentation, "TPU v5e":
# 819 GB/s)
PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}
WIDTHS = (32, 64)


def least_bytes(child: int, parent: int, slots: int, w: int) -> int:
    """The fewest bytes a freq-join call moves: keys, frequencies, and the
    dense accumulator, with w-byte counts."""
    return 4 * (child + parent) + w * (child + 2 * parent) + 2 * w * slots


def peak_bytes_per_s() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BYTES_PER_S:
        raise KeyError(f"no HBM bandwidth known for device kind {kind!r}")
    return PEAK_BYTES_PER_S[kind]


def read(run):
    if "programs_count32" not in run.counters_after:
        return None
    programs = sum(run.delta(f"programs_count{b}") for b in WIDTHS)
    if not programs:
        return None
    device_ms = common.metric_module("freq_join_device_ms").read(run)
    if not device_ms:
        return None
    total = 0
    for b in WIDTHS:
        parent = run.delta(f"join_parent_rows{b}")
        total += least_bytes(run.delta(f"join_rows{b}") - parent, parent,
                             run.delta(f"join_slots{b}"), b // 8)
    least_ms = total / programs / peak_bytes_per_s() * 1e3
    return 100.0 * least_ms / device_ms
