"""Kernel autotuner: measured block/dispatch search per shape bucket.

The three kernels (freq_join, semi_join, segment_sum) historically ran
fixed block shapes and a hard-coded dense-domain dispatch threshold
regardless of input size or backend.  This module closes the loop that
``benchmarks/roofline.py`` opened: it parametrises the kernels over a
small config space (``KernelConfig``), measures every candidate on
synthetic inputs shaped like the serving bucket, gates each candidate on
BITWISE equality with the untuned result, and keeps the winner in a
``TuneTable`` keyed by ``(kernel, shape bucket, backend)``.

Shape buckets are the SAME power-of-two buckets the plan cache uses
(``repro.tables.table.bucket_capacity`` semantics): a table growing
inside its bucket hits the same tune entry, so within-bucket growth
never retunes — matching the serving tier's never-recompile invariant.

The config space, per (kernel, backend):

* ``("freq_join"|"semi_join", "xla")``   — ``dense_ratio``/``dense_floor``:
  where the sort+searchsorted pipeline should hand over to the
  scatter-add dense-domain path (``kernels/ops.py``).  Candidates are
  measured over a grid of key-domain probes spanning the crossover, so
  the winning ratio is the one that dispatches best across the whole
  domain range the bucket may see, not at one lucky point.
* ``("freq_join"|"semi_join", "pallas")`` — ``parent_block_rows`` ×
  ``child_block_rows`` for the blocked broadcast-compare kernels.
* ``("segment_sum", "pallas")``          — ``lanes_wide``, elements per
  ``(lanes_wide // 128, 128)`` block (a multiple of one 8×128 tile).
* ``("segment_sum", "xla")``             — nothing to tune (one
  candidate); ``search`` returns the default without measuring.

Persistence lives one layer up (``repro.service.tune_store.TuneStore``,
same cache_dir and store discipline as the plan store); ``KernelTuner``
consults it table → store → measured search, so a warm-started service
re-measures nothing (``tune_searches == 0``).

Timing uses ``time.perf_counter`` directly — this is the kernel layer's
offline calibration path, not the serving tier (whose clock discipline
``scripts/lint.py`` enforces for ``src/repro/service/`` only).  Rows can
be forwarded to a ``benchmarks.recorder.Recorder`` by passing its
``row`` method as the sink.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

KERNELS = ("freq_join", "semi_join", "segment_sum")

# structural (non-tunable) bound on the dense-domain accumulator: int32
# packed keys cannot index past 2^31 regardless of measured preference
DENSE_DOMAIN_CAP = 1 << 31


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the kernel config space.  Frozen (hashable), so a
    config is a valid ``jax.jit`` static argument — ``kernels/ops.py``
    traces one program per (shapes, backend, config).

    The defaults reproduce the untuned behaviour exactly: 8×128 fp32
    native tiles for the blocked joins, (8, 128) blocks (``lanes_wide``
    = 1024 elements) for the segmented sum, and the historical
    ``max(4·nc, 2^20)`` dense-domain crossover.  ``dense_ratio <= 0``
    disables the dense path entirely.
    """

    parent_block_rows: int = 8
    child_block_rows: int = 8
    lanes_wide: int = 1024
    dense_ratio: int = 4
    dense_floor: int = 1 << 20

    def dense_ok(self, domain: int | None, n_child: int) -> bool:
        """Should the XLA freq-join dispatch to the scatter-add dense
        path for this (domain, child-size)?"""
        return (domain is not None and self.dense_ratio > 0
                and domain <= max(self.dense_ratio * n_child,
                                  self.dense_floor)
                and domain < DENSE_DOMAIN_CAP)


DEFAULT_CONFIG = KernelConfig()


def _pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def bucket_shape(*sizes: int) -> tuple[int, ...]:
    """Round each size up to a power of two — the tune-table key uses the
    same bucket boundaries as the serving tier's shape buckets, so a
    bucket-padded input always looks up the entry its bucket was tuned
    at."""
    return tuple(_pow2(s) for s in sizes)


def candidate_configs(kernel: str, backend: str) -> list[KernelConfig]:
    """The measured search space for one (kernel, backend).  Always
    includes ``DEFAULT_CONFIG`` (so the search can never do worse than
    untuned) and keeps irrelevant fields at their defaults (so configs
    stay comparable and the jit static-arg space stays small)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}")
    out = [DEFAULT_CONFIG]
    if kernel in ("freq_join", "semi_join"):
        if backend == "xla":
            for ratio in (0, 32, 256):
                out.append(dataclasses.replace(DEFAULT_CONFIG,
                                               dense_ratio=ratio))
        else:
            for pbr, cbr in ((16, 8), (8, 16), (16, 16), (32, 8)):
                out.append(dataclasses.replace(
                    DEFAULT_CONFIG, parent_block_rows=pbr,
                    child_block_rows=cbr))
    elif kernel == "segment_sum" and backend != "xla":
        for lw in (2048, 4096, 8192):
            out.append(dataclasses.replace(DEFAULT_CONFIG, lanes_wide=lw))
    return out


class TuneTable:
    """In-memory tuned-config table: (kernel, shape bucket, backend) →
    ``KernelConfig``.  Lookups bucket the raw sizes, so callers pass the
    concrete (already bucket-padded) array lengths they are about to run.
    Misses return None — ``kernels/ops.py`` treats that as
    ``DEFAULT_CONFIG``.  Thread-safe: the serving tier reads it from
    concurrent compile threads while ``autotune()`` installs entries."""

    def __init__(self):
        self._d: dict[tuple, KernelConfig] = {}
        self._lock = threading.Lock()

    @staticmethod
    def key(kernel: str, shape, backend: str) -> tuple:
        return (kernel, bucket_shape(*shape), backend)

    def lookup(self, kernel: str, shape, backend: str) -> KernelConfig | None:
        with self._lock:
            return self._d.get(self.key(kernel, shape, backend))

    def install(self, kernel: str, shape, backend: str,
                config: KernelConfig) -> None:
        with self._lock:
            self._d[self.key(kernel, shape, backend)] = config

    def entries(self) -> list[tuple[tuple, KernelConfig]]:
        with self._lock:
            return list(self._d.items())

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


# --------------------------------------------------------------------------
# synthetic inputs + measurement
# --------------------------------------------------------------------------
def _synth_join(shape: tuple[int, int], domain: int):
    """Deterministic join inputs for one bucket: keys uniform over
    ``domain`` (including a sprinkle of out-of-range/negative child keys,
    so the bitwise gate also covers the scatter path's masking), freqs
    small positive ints."""
    np_, nc = shape
    rng = np.random.default_rng((np_, nc, domain, 0xA11CE))
    pk = rng.integers(0, domain, np_, dtype=np.int64).astype(np.int32)
    ck = rng.integers(0, domain, nc, dtype=np.int64).astype(np.int32)
    # a few dead/OOB child keys exercise every candidate's masking
    oob = rng.random(nc) < 0.01
    ck = np.where(oob, np.where(rng.random(nc) < 0.5, -1, domain), ck)
    pf = rng.integers(1, 4, np_, dtype=np.int32)
    cf = rng.integers(0, 4, nc, dtype=np.int32)
    return (jnp.asarray(pk), jnp.asarray(pf),
            jnp.asarray(ck), jnp.asarray(cf))


def _synth_segment(shape: tuple[int, ...]):
    (n,) = shape
    rng = np.random.default_rng((n, 0x5E6))
    keys = np.sort(rng.integers(0, max(2, n // 4), n,
                                dtype=np.int64).astype(np.int32))
    vals = rng.integers(0, 100, n, dtype=np.int64).astype(np.int32)
    return jnp.asarray(keys), jnp.asarray(vals)


def _domain_probes(nc: int) -> list[int]:
    """Key-domain grid spanning the dense/sort crossover for a child
    bucket of ``nc`` rows — from comfortably-dense to clearly-sparse,
    capped below the structural 2^31 accumulator bound."""
    probes = []
    for mult in (1, 8, 16, 64):
        d = nc * mult
        if 2 <= d < DENSE_DOMAIN_CAP:
            probes.append(d)
    return probes or [max(2, nc)]


def measure(fn: Callable[[], Any], repeats: int = 3) -> float:
    """Best-of-``repeats`` wall-clock seconds for ``fn`` (one warmup call
    first, so compile/trace time never pollutes the comparison)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _bitwise_equal(a, b) -> bool:
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    if len(flat_a) != len(flat_b):
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(flat_a, flat_b))


class KernelTuner:
    """Measured config search with a store-backed warm path.

    Resolution order in ``ensure``: in-memory ``TuneTable`` → persistent
    ``TuneStore`` (when constructed with one) → measured ``search``.
    Only the last bumps ``tune_searches`` — a warm-started service whose
    store already holds every bucket reports ``tune_searches == 0``,
    mirroring the plan cache's ``plan_builds == 0`` invariant.

    ``row(name, us, derived)`` is an optional timing sink with the
    ``benchmarks.recorder.Recorder.row`` signature, so benchmark runs
    can record the full candidate trajectory without this package
    depending on ``benchmarks/``.
    """

    def __init__(self, store=None, *, backend: str = "xla", repeats: int = 3,
                 row: Callable[..., Any] | None = None):
        self.table = TuneTable()
        self.store = store
        self.backend = backend
        self.repeats = repeats
        self.row = row
        self._lock = threading.Lock()
        self.counters = {
            "tune_searches": 0,        # measured searches actually run
            "tune_candidates": 0,      # candidate configs measured
            "tune_gate_rejects": 0,    # candidates failing the bitwise gate
            "tune_store_hits": 0,      # configs loaded from the store
            "tune_installs": 0,        # entries installed into the table
        }

    # ---- resolution ------------------------------------------------------
    def load_persisted(self) -> int:
        """Install every valid store entry for this tuner's backend into
        the table (warm start).  Returns the number installed."""
        if self.store is None:
            return 0
        n = 0
        for (kernel, shape, backend), config in self.store.load_all():
            if backend != self.backend:
                continue
            self.table.install(kernel, shape, backend, config)
            n += 1
        if n:
            with self._lock:
                self.counters["tune_store_hits"] += n
                self.counters["tune_installs"] += n
        return n

    def ensure(self, kernel: str, shape) -> KernelConfig:
        """The tuned config for (kernel, bucket(shape)) — from the table,
        the store, or a fresh measured search (persisted on the way
        out)."""
        bshape = bucket_shape(*shape)
        cfg = self.table.lookup(kernel, bshape, self.backend)
        if cfg is not None:
            return cfg
        if self.store is not None:
            cfg = self.store.load(kernel, bshape, self.backend)
            if cfg is not None:
                self.table.install(kernel, bshape, self.backend, cfg)
                with self._lock:
                    self.counters["tune_store_hits"] += 1
                    self.counters["tune_installs"] += 1
                return cfg
        cfg, measurements = self.search(kernel, bshape)
        self.table.install(kernel, bshape, self.backend, cfg)
        with self._lock:
            self.counters["tune_installs"] += 1
        if self.store is not None:
            self.store.save(kernel, bshape, self.backend, cfg,
                            measurements=measurements)
        return cfg

    # ---- search ----------------------------------------------------------
    def search(self, kernel: str,
               shape) -> tuple[KernelConfig, dict[str, float]]:
        """Measure every candidate for (kernel, bucket(shape)); return
        (winner, per-candidate best seconds).  Every candidate's answer
        is bitwise-gated against ``DEFAULT_CONFIG``'s; a gate failure
        drops the candidate (counted), it can never win."""
        bshape = bucket_shape(*shape)
        cands = candidate_configs(kernel, self.backend)
        with self._lock:
            self.counters["tune_searches"] += 1
        if len(cands) == 1:
            return cands[0], {}

        scenarios = self._scenarios(kernel, bshape)
        baselines = [fn(DEFAULT_CONFIG) for _, fn in scenarios]
        best_cfg, best_t = DEFAULT_CONFIG, float("inf")
        measurements: dict[str, float] = {}
        for cfg in cands:
            with self._lock:
                self.counters["tune_candidates"] += 1
            total = 0.0
            ok = True
            for (label, fn), base in zip(scenarios, baselines):
                if not _bitwise_equal(fn(cfg), base):
                    ok = False
                    break
                total += measure(lambda: fn(cfg), self.repeats)
            tag = self._cfg_tag(kernel, cfg)
            if not ok:
                # zero-drift gate: a diverging candidate is dropped on
                # the spot — it can never win, however fast it measured
                with self._lock:
                    self.counters["tune_gate_rejects"] += 1
                continue
            measurements[tag] = total
            if self.row is not None:
                self.row(f"tune/{kernel}/{self.backend}/"
                         f"{'x'.join(map(str, bshape))}/{tag}",
                         total * 1e6,
                         {"candidates": len(cands)})
            if total < best_t:
                best_cfg, best_t = cfg, total
        return best_cfg, measurements

    def _scenarios(self, kernel: str, bshape: tuple[int, ...]):
        """(label, config → answer) closures the search measures.  Joins
        run one scenario per domain probe so dispatch-policy candidates
        are scored across the whole crossover range."""
        from repro.kernels import ops  # deferred: ops imports KernelConfig

        if kernel in ("freq_join", "semi_join"):
            mode = "any" if kernel == "semi_join" else "sum"
            out = []
            for dom in _domain_probes(bshape[1]):
                args = _synth_join(bshape, dom)

                def fn(cfg, args=args, dom=dom):
                    return ops.freq_join(
                        *args, mode=mode, backend=self.backend,
                        domain=dom, config=cfg)

                out.append((f"domain{dom}", fn))
            return out
        keys, vals = _synth_segment(bshape)

        def fn(cfg):
            return ops.segment_sum_sorted(keys, vals, backend=self.backend,
                                          config=cfg)

        return [("sorted", fn)]

    @staticmethod
    def _cfg_tag(kernel: str, cfg: KernelConfig) -> str:
        if kernel == "segment_sum":
            return f"lanes{cfg.lanes_wide}"
        return (f"pb{cfg.parent_block_rows}_cb{cfg.child_block_rows}"
                f"_ratio{cfg.dense_ratio}")

    # ---- observability ---------------------------------------------------
    def metrics(self) -> dict[str, int]:
        with self._lock:
            out = dict(self.counters)
        out["tune_entries"] = len(self.table)
        return out


TUNE_ZEROS = {
    "tune_searches": 0, "tune_candidates": 0, "tune_gate_rejects": 0,
    "tune_store_hits": 0, "tune_installs": 0, "tune_entries": 0,
}
