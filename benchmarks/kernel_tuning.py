"""Kernel autotuning gate: untuned vs tuned, bitwise-identical, warm.

Three checks, mirroring the serving benchmark's counter gates:

1. **Win gate** — run the measured search (``repro.kernels.autotune``)
   for each kernel at benchmark scale, then time the untuned
   ``DEFAULT_CONFIG`` against the winner over the SAME workload the
   search scored (the sum over the key-domain probe grid for joins).
   The full run asserts a strict speedup on >= 2 of the 3 kernels; the
   ``--smoke`` run prints the ratios but only gates correctness
   (timings at smoke scale are noise).

2. **Bitwise gate** — the tuned config's answers must be EXACTLY the
   untuned answers on every workload, re-checked here independently of
   the search's own per-candidate gate.

3. **Warm-restart gate** — a second ``KernelTuner`` over the same
   ``TuneStore`` directory must resolve every bucket from disk:
   ``tune_searches == 0``, mirroring the plan cache's
   ``plan_builds == 0`` invariant.

Usage::

    PYTHONPATH=src python benchmarks/kernel_tuning.py            # full
    PYTHONPATH=src python benchmarks/kernel_tuning.py --smoke
    PYTHONPATH=src python benchmarks/kernel_tuning.py --smoke \
        --record BENCH_tuning.json   # + schema-versioned trajectory

The timings are those of XLA's CPU backend and the Pallas interpreter,
so the script runs on the CPU only: it sets ``jax_platforms="cpu"``
before any backend starts (and exports ``JAX_PLATFORMS=cpu`` to any
child), so it never opens a chip.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

# run as `python benchmarks/kernel_tuning.py` (script dir on sys.path,
# repo root not) and as `python -m benchmarks.kernel_tuning`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.kernels.autotune import (  # noqa: E402
    DEFAULT_CONFIG,
    KernelTuner,
    _domain_probes,
    _synth_join,
    _synth_segment,
    bucket_shape,
    measure,
)
from repro.service.tune_store import TuneStore  # noqa: E402

# (kernel, backend, shape) per scale — the backends each kernel is
# actually tuned for: the XLA joins' dense/sort dispatch is what the CPU
# benchmarks time; the pallas segmented sum's block width is searched in
# interpret mode (same-lowering twin of the TPU path).
CASES = {
    "full": [
        ("freq_join", "xla", (1 << 17, 1 << 17)),
        ("semi_join", "xla", (1 << 17, 1 << 17)),
        ("segment_sum", "pallas", (1 << 15,)),
    ],
    "smoke": [
        ("freq_join", "xla", (1 << 12, 1 << 12)),
        ("semi_join", "xla", (1 << 12, 1 << 12)),
        ("segment_sum", "pallas", (1 << 13,)),
    ],
}


def workloads(kernel: str, backend: str, shape):
    """(label, config -> answer) closures — the comparison workload,
    built from public ops only.  Joins get one closure per key-domain
    probe (dispatch-policy wins must hold across the crossover range)."""
    bshape = bucket_shape(*shape)
    if kernel in ("freq_join", "semi_join"):
        mode = "any" if kernel == "semi_join" else "sum"
        out = []
        for dom in _domain_probes(bshape[1]):
            args = _synth_join(bshape, dom)

            def fn(cfg, args=args, dom=dom):
                return ops.freq_join(*args, mode=mode, backend=backend,
                                     domain=dom, config=cfg)

            out.append((f"domain{dom}", fn))
        return out
    keys, vals = _synth_segment(bshape)

    def fn(cfg):
        return ops.segment_sum_sorted(keys, vals, backend=backend,
                                      config=cfg)

    return [("sorted", fn)]


def run_case(tuner: KernelTuner, kernel: str, shape, rec) -> dict:
    """Tune one (kernel, bucket), then compare untuned vs tuned on the
    comparison workload.  Returns {kernel, tuned_is_default, untuned_s,
    tuned_s, speedup, bitwise}."""
    cfg = tuner.ensure(kernel, shape)
    wl = workloads(kernel, tuner.backend, shape)
    untuned_s = tuned_s = 0.0
    bitwise = True
    for label, fn in wl:
        base = fn(DEFAULT_CONFIG)
        got = fn(cfg)
        flat_b = [np.asarray(x) for x in
                  (base if isinstance(base, tuple) else (base,))]
        flat_g = [np.asarray(x) for x in
                  (got if isinstance(got, tuple) else (got,))]
        if not all(np.array_equal(b, g) for b, g in zip(flat_b, flat_g)):
            bitwise = False
        untuned_s += measure(lambda: fn(DEFAULT_CONFIG), tuner.repeats)
        tuned_s += measure(lambda: fn(cfg), tuner.repeats)
    speedup = untuned_s / tuned_s if tuned_s > 0 else float("inf")
    rec.row(f"{kernel}/untuned", untuned_s * 1e6, tuner.backend)
    rec.row(f"{kernel}/tuned", tuned_s * 1e6,
            f"{tuner.backend} speedup={speedup:.2f} cfg={cfg}")
    return {"kernel": kernel, "tuned_is_default": cfg == DEFAULT_CONFIG,
            "untuned_s": untuned_s, "tuned_s": tuned_s,
            "speedup": speedup, "bitwise": bitwise}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes; gates correctness + warm restart "
                         "only (timings advisory)")
    ap.add_argument("--record", nargs="?", const="BENCH_tuning.json",
                    default=None, metavar="PATH",
                    help="write the schema-versioned trajectory JSON")
    args = ap.parse_args(argv)
    scale = "smoke" if args.smoke else "full"
    cases = CASES[scale]

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    from benchmarks.recorder import Recorder
    rec = Recorder("tuning", path=args.record)
    rec.add_meta(scale=scale)

    failures: list[str] = []
    with tempfile.TemporaryDirectory(prefix="tune_bench_") as cache_dir:
        results = []
        for kernel, backend, shape in cases:
            rec.section(f"{kernel} ({backend}, "
                        f"{'x'.join(map(str, shape))})")
            store = TuneStore(cache_dir)
            tuner = KernelTuner(store, backend=backend,
                                repeats=2 if args.smoke else 3, row=rec.row)
            r = run_case(tuner, kernel, shape, rec)
            r["backend"] = backend
            r["shape"] = shape
            results.append(r)
            rec.add_metrics({f"{kernel}_{k}": v
                             for k, v in tuner.metrics().items()})
            print(f"# {kernel:12s} untuned {r['untuned_s'] * 1e3:8.1f} ms  "
                  f"tuned {r['tuned_s'] * 1e3:8.1f} ms  "
                  f"speedup {r['speedup']:.2f}x  "
                  f"bitwise={'OK' if r['bitwise'] else 'FAIL'}")
            if not r["bitwise"]:
                failures.append(f"{kernel}: tuned answers diverge bitwise")
            if tuner.counters["tune_searches"] != 1:
                failures.append(f"{kernel}: expected 1 cold search, got "
                                f"{tuner.counters['tune_searches']}")

        # warm-restart gate: a fresh tuner over the same cache dir must
        # resolve every bucket from disk — zero measured searches
        rec.section("warm restart")
        warm_total = {"searches": 0, "hits": 0}
        for kernel, backend, shape in cases:
            warm = KernelTuner(TuneStore(cache_dir), backend=backend)
            warm.load_persisted()
            warm.ensure(kernel, shape)
            warm_total["searches"] += warm.counters["tune_searches"]
            warm_total["hits"] += warm.counters["tune_store_hits"]
        rec.row("warm/tune_searches", float("nan"),
                str(warm_total["searches"]))
        print(f"# warm restart: tune_searches={warm_total['searches']} "
              f"store_hits={warm_total['hits']}")
        if warm_total["searches"] != 0:
            failures.append("warm restart re-searched "
                            f"{warm_total['searches']} bucket(s)")
        rec.add_metrics({"warm_tune_searches": warm_total["searches"],
                         "warm_tune_store_hits": warm_total["hits"]})

        wins = sum(1 for r in results
                   if not r["tuned_is_default"] and r["speedup"] > 1.0)
        print(f"# tuned wins: {wins}/{len(results)} kernels")
        rec.add_metrics({"tuned_wins": wins})
        if not args.smoke and wins < 2:
            failures.append(f"only {wins}/3 kernels improved at full scale")

    rec.finish()
    if failures:
        for fmsg in failures:
            print(f"FAIL: {fmsg}", file=sys.stderr)
        return 1
    print("kernel_tuning: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
