"""Fixed-shape columnar table substrate.

JAX requires static shapes, so relations never shrink or grow: a ``Table``
has a fixed ``capacity`` and carries a *frequency* column ``freq``.  A live
tuple has ``freq > 0``; selections and semi-joins zero frequencies instead of
deleting rows; the FreqJoin operator multiplies them.  This is exactly the
paper's K-relation view (semiring annotations) made static.

Columns are 1-D arrays of identical length.  Schema metadata (primary keys,
uniqueness, FK edges, domain sizes) drives the paper's §4.1 set-safety and
§4.3 FK/PK optimisations.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ColumnMeta:
    """Static metadata for one column of a relation."""

    name: str
    unique: bool = False          # declared UNIQUE / PK component
    domain: int | None = None     # values are ints in [0, domain) if known


@dataclasses.dataclass(frozen=True)
class ForeignKey:
    """FK edge: ``src.src_col`` references ``dst.dst_col`` (a PK/unique col)."""

    src: str
    src_col: str
    dst: str
    dst_col: str


@dataclasses.dataclass(frozen=True)
class RelSchema:
    """Schema of one relation."""

    name: str
    columns: tuple[ColumnMeta, ...]

    def column_names(self) -> tuple[str, ...]:
        return tuple(c.name for c in self.columns)

    def meta(self, name: str) -> ColumnMeta:
        for c in self.columns:
            if c.name == name:
                return c
        raise KeyError(f"{self.name} has no column {name!r}")

    def is_unique(self, cols: Sequence[str]) -> bool:
        """True if `cols` contains at least one declared-unique column.
        Unknown names raise (via ``meta``): a typo in FK/PK metadata must
        not silently flip a §4.3 pre-grouping decision."""
        return any(self.meta(c).unique for c in cols)


@dataclasses.dataclass(frozen=True)
class Schema:
    """Database schema: relations + FK edges."""

    relations: Mapping[str, RelSchema]
    foreign_keys: tuple[ForeignKey, ...] = ()

    def fk_edge(self, src: str, src_col: str, dst: str, dst_col: str) -> bool:
        """True if src.src_col → dst.dst_col is a declared FK into a unique col."""
        for fk in self.foreign_keys:
            if (fk.src, fk.src_col, fk.dst, fk.dst_col) == (src, src_col, dst, dst_col):
                return True
        return False

    def check_domains(self, name: str, table: "Table") -> None:
        """Raise ``ValueError`` if a live row of ``table``, the data of
        relation ``name``, holds a value outside a column's declared domain.
        Packed join keys and the dense freq-join both rely on every live
        value lying in ``[0, domain)``."""
        rel = self.relations.get(name)
        if rel is None:
            return
        declared = [c for c in rel.columns
                    if c.domain is not None and c.name in table.columns]
        if not declared:
            return
        live = np.asarray(table.freq) > 0
        every = bool(live.all())
        bad = []
        for c in declared:
            vals = np.asarray(table.columns[c.name])
            if not every:
                vals = vals[live]
            if vals.size and (vals.min() < 0 or vals.max() >= c.domain):
                n = int(((vals < 0) | (vals >= c.domain)).sum())
                bad.append(f"{c.name}: {n} live rows outside "
                           f"[0, {c.domain}), seen [{vals.min()}, "
                           f"{vals.max()}]")
        if bad:
            raise ValueError(f"table {name!r} breaks its declared domains: "
                             + "; ".join(bad))

    def without_domains(self) -> "Schema":
        """This schema with no column declaring a domain: every join then
        takes the sorted path, the control for dense-domain dispatch."""
        return dataclasses.replace(self, relations={
            name: dataclasses.replace(rel, columns=tuple(
                dataclasses.replace(c, domain=None) for c in rel.columns))
            for name, rel in self.relations.items()})


@jax.tree_util.register_pytree_node_class
class Table:
    """A fixed-capacity columnar relation with a frequency column.

    ``columns``: dict name → 1-D array, all of length ``capacity``.
    ``freq``:    1-D array of length ``capacity``; 0 marks dead/padded rows.
    """

    def __init__(self, columns: dict[str, jax.Array], freq: jax.Array):
        self.columns = dict(columns)
        self.freq = freq

    # ---- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.freq,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1])

    # ---- construction -----------------------------------------------------
    @classmethod
    def from_numpy(
        cls,
        data: Mapping[str, np.ndarray],
        freq_dtype: Any = jnp.int32,
        capacity: int | None = None,
    ) -> "Table":
        n = len(next(iter(data.values())))
        cap = capacity if capacity is not None else n
        if cap < n:
            raise ValueError(
                f"capacity {cap} below data length {n}; tables never "
                "shrink (drop rows by zeroing freq instead)")
        cols = {}
        for k, v in data.items():
            arr = np.asarray(v)
            if cap > n:
                pad = np.zeros((cap - n,) + arr.shape[1:], dtype=arr.dtype)
                arr = np.concatenate([arr, pad])
            cols[k] = jnp.asarray(arr)
        freq = jnp.concatenate(
            [jnp.ones((n,), freq_dtype), jnp.zeros((cap - n,), freq_dtype)]
        )
        return cls(cols, freq)

    # ---- basic properties ---------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.freq.shape[0])

    @property
    def column_names(self) -> tuple[str, ...]:
        return tuple(sorted(self.columns))

    def live_count(self) -> jax.Array:
        """Number of live tuples (rows with freq > 0) — the paper's
        'materialised tuples' metric for this relation."""
        return jnp.sum((self.freq > 0).astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32))

    def weight_total(self) -> jax.Array:
        """Sum of frequencies = bag cardinality this table represents."""
        return jnp.sum(self.freq)

    def content_token(self) -> str:
        """Cheap content hash of the table's data version: one sha256 over
        every column's bytes plus the frequency column.  The statistics
        layer keys per-table stats on this token, so a warm restart over
        identical data recognises its persisted stats without recomputing
        them, and any data change (new rows, zeroed frequencies, padding)
        invalidates every decision calibrated against the old version."""
        import hashlib
        h = hashlib.sha256()
        for name in self.column_names:
            arr = np.asarray(self.columns[name])
            h.update(name.encode())
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        f = np.asarray(self.freq)
        h.update(b"__freq__")
        h.update(str(f.dtype).encode())
        h.update(f.tobytes())
        return h.hexdigest()

    # ---- relational primitives (frequency-aware) -----------------------
    def select(self, pred: Callable[[dict[str, jax.Array]], jax.Array]) -> "Table":
        """σ: zero out frequencies of rows failing `pred` (no compaction)."""
        mask = pred(self.columns)
        return Table(self.columns, jnp.where(mask, self.freq, 0))

    def with_freq(self, freq: jax.Array) -> "Table":
        return Table(self.columns, freq)

    def project(self, names: Sequence[str]) -> "Table":
        """π (frequency-preserving; duplicates remain encoded by rows+freq)."""
        return Table({n: self.columns[n] for n in names}, self.freq)

    def pad_to(self, capacity: int) -> "Table":
        """Grow capacity to `capacity` by appending dead rows (freq = 0).

        Padding is semantically free: every operator in the engine masks by
        frequency, so zero-freq rows join, select, and aggregate to nothing.
        The serving tier pads tables to power-of-two buckets so that data
        growth inside a bucket keeps jitted executables' shapes — and hence
        their compiled programs — valid (zero recompiles)."""
        cap = self.capacity
        if capacity == cap:
            return self
        if capacity < cap:
            raise ValueError(
                f"pad_to({capacity}) below current capacity {cap}; tables "
                "never shrink (drop rows by zeroing freq instead)")
        extra = capacity - cap
        cols = {}
        for name, col in self.columns.items():
            pad = jnp.zeros((extra,) + col.shape[1:], col.dtype)
            cols[name] = jnp.concatenate([col, pad])
        freq = jnp.concatenate(
            [self.freq, jnp.zeros((extra,), self.freq.dtype)])
        return Table(cols, freq)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Table(cap={self.capacity}, cols={list(self.column_names)})"


def bucket_capacity(n: int, min_capacity: int = 8) -> int:
    """Smallest power of two ≥ max(n, min_capacity) — the shape bucket a
    table of n rows compiles against.  Bucketing trades ≤2× padded rows for
    XLA program reuse across data growth."""
    n = max(int(n), min_capacity, 1)
    return 1 << (n - 1).bit_length()


def sharded_bucket_capacity(n: int, n_shards: int,
                            min_capacity: int = 8) -> int:
    """Shape bucket for a table of n rows ROW-SHARDED over `n_shards`
    devices: each shard holds a power-of-two block of
    ``bucket_capacity(ceil(n / n_shards))`` rows, so the total is both
    divisible by the shard count (a shard_map requirement) and stable
    under per-shard growth — rows added anywhere inside the per-shard
    bucket never change the mesh program's shapes.

    For power-of-two shard counts this equals
    ``bucket_capacity(n, n_shards * min_capacity)`` (the per-shard
    rounding distributes over the product), which is what makes a mesh
    service's padded capacities reproducible on one device: a local
    service with ``min_bucket = n_shards * min_capacity`` pads every
    relation to exactly the mesh's global shapes."""
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    per_shard = -(-max(int(n), 1) // n_shards)   # ceil
    return n_shards * bucket_capacity(per_shard, min_capacity)


def pack_keys(
    cols: Sequence[jax.Array],
    domains: Sequence[int | None],
    dtype: Any = None,
) -> jax.Array:
    """Pack multi-attribute join keys into a single integer key.

    If all domains are known, packing is collision-free mixed-radix:
    ``key = ((c0 * d1 + c1) * d2 + c2) ...``.  Otherwise a 64/32-bit
    Fibonacci mixing hash combine is used (documented collision risk —
    exact engines should declare domains; our generators always do).
    """
    if dtype is None:
        dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    if len(cols) == 1:
        return cols[0].astype(dtype)
    if all(d is not None for d in domains):
        key = cols[0].astype(dtype)
        for c, d in zip(cols[1:], domains[1:]):
            key = key * jnp.asarray(d, dtype) + c.astype(dtype)
        return key
    # hash combine fallback
    phi = jnp.asarray(0x9E3779B9 if dtype == jnp.int32 else 0x9E3779B97F4A7C15, dtype)
    key = cols[0].astype(dtype)
    for c in cols[1:]:
        key = key ^ (c.astype(dtype) + phi + (key << 6) + (key >> 2))
    return key
