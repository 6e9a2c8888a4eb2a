"""Run one cell of the benchmark on a TPU and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Once it holds the chip, it builds the cell's data from the seed (on the
host the tables the reference reads, on the device those made there),
builds a ``QueryService`` with the options the configuration states, warms every
program the cell's traffic can use, and drives the traffic for
``--seconds``.  Then, once the window has closed and the device's peak
memory has been read, it compares every answer of the window with the
configuration's plain reference.  With ``--trace 1`` the window runs
under the profiler and the line carries the per-layer metrics instead of
the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), and last ``checks``, each number compared with its limit;
the same numbers are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits 2.  JAX's persistent compilation cache is kept in
``.bench_cache/jax`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".bench_cache" / "jax"
# service counters whose change over the window standard error reports
WINDOW_COUNTERS = ("batches", "dedup_saved", "fused_batches", "fused_queries",
                   "fusion_cost_rejects", "fusion_demotions")


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chip(chips: int) -> dict:
    """The device line of the run; raises ``NoChip`` off a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU; "
                     "the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _cache_setup() -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()


class _Compiles:
    """Counts XLA backend compiles and compile-cache hits and misses."""

    def __init__(self):
        self.backend = 0
        self.hits = 0
        self.misses = 0

    def event(self, name: str, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def duration(self, name: str, _secs: float, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1


def _memory_peak(n: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # the compile cache sits at a fixed path in the checkout, and the
    # environment's choice of another is overridden before JAX reads it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax
    import numpy as np

    from bench import check, common, devtrace, drive, traffic
    from bench.record import Run

    suite = common.benchmark()
    cell = common.workload(suite, args.workload)
    try:
        device = require_chip(int(cell["chips"]))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    # set-up counts from here: starting the TPU runtime, which no change to
    # the program can shorten, varies by seconds with the host's load
    t_chip = time.perf_counter()
    from bench import system      # the engine: its import counts as set-up
    _cache_setup()
    compiles = _Compiles()
    jax.monitoring.register_event_listener(compiles.event)
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)

    spec = common.config_spec(cell["config"])
    gen = common.config_module(cell["config"])
    mix = common.traffic_spec(cell["traffic"])
    names = list(spec["queries"])
    sql = spec["queries"]

    t = time.perf_counter()
    data = gen.generate(spec, args.seed)
    made = getattr(gen, "generate_on_device", None)
    on_device = made(spec, args.seed) if made is not None else {}
    db = system.load(spec, {**data, **on_device})
    jax.block_until_ready(db)
    t_data = time.perf_counter() - t
    t = time.perf_counter()
    svc = system.service(spec, db, system.schema(spec),
                         profile_annotations=bool(args.trace))
    t_service = time.perf_counter() - t
    sched = traffic.schedule(mix, names, args.seed, args.seconds)
    t = time.perf_counter()
    drive.warm(svc, sched.call, [[sql[n] for n in b]
                                 for b in sched.warm_batches])
    t_warm = time.perf_counter() - t
    before = svc.metrics()
    backend_before = compiles.backend

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    setup_s = time.perf_counter() - t_chip
    with (devtrace.capture(trace_dir) if args.trace
          else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            if sched.loop == "closed":
                window = drive.closed_loop(svc, sched.call, sql,
                                           sched.client_requests,
                                           args.seconds)
            else:
                window = drive.open_loop(svc, sql, sched.arrivals,
                                         args.seconds)
    after = svc.metrics()
    window_backend = compiles.backend - backend_before
    device["memory_peak_bytes"] = _memory_peak(device["count"])

    run = Run(cell, spec, mix, window, setup_s, before, after)
    svc.close()
    del svc, db, on_device
    gc.collect()

    t = time.perf_counter()
    try:
        want = gen.reference(spec, data)
        ref_error = None
    except ValueError as e:
        want, ref_error = None, e
    t_ref = time.perf_counter() - t
    answers = []
    for r in window.requests:
        for i, q in enumerate(r.queries):
            res = r.results[i] if i < len(r.results) else None
            if res is None or isinstance(res, BaseException) \
                    or res.error is not None:
                answers.append((q, res if isinstance(res, BaseException)
                                else getattr(res, "error", None)))
            else:
                answers.append((q, {k: np.asarray(v)
                                    for k, v in res.values.items()}))
    if want is None:
        numbers = {"reference_failed": {"value": 1, "limit": 0}}
        failed = len(answers)
    else:
        numbers, failed = check.compare(spec["checks"], want, answers)
    correct = check.passed(numbers)

    out = {"correct": correct, "attempted": len(answers), "failed": failed}
    t = time.perf_counter()
    if args.trace:
        run.trace = devtrace.load(trace_dir, devices=device["count"])
        device["busy_s"] = devtrace.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in common.cell_metrics(suite, cell["name"], bool(args.trace)):
        value = common.metric_module(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    t_reduce = time.perf_counter() - t
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = devtrace.breakdown(run.trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["checks"] = numbers

    lateness = [r.sent - r.due for r in window.requests if r.sent == r.sent]
    lat = [r.latency for r in window.answered]
    print(f"cell {cell['name']} seed {args.seed}: {len(window.requests)} "
          f"requests, {len(answers)} answers, window {window.close} s",
          file=sys.stderr)
    print(f"set-up: process start to chip held {t_chip - T_START} s, "
          f"data+load {t_data} s, service {t_service} s, warm {t_warm} s "
          f"({len(sched.warm_batches)} batches), "
          f"setup_s {setup_s} s; compile cache hits {compiles.hits} misses "
          f"{compiles.misses}", file=sys.stderr)
    print(f"window: backend compiles {window_backend}, service compiles "
          f"{after['compiles'] - before['compiles']}, generator late by at "
          f"most {max(lateness, default=0.0)} s, latency min "
          f"{min(lat, default=float('nan'))} max "
          f"{max(lat, default=float('nan'))} s", file=sys.stderr)
    moved = {k: after[k] - before[k] for k in WINDOW_COUNTERS}
    print(f"window counters: {moved}", file=sys.stderr)
    programs: dict[str, list[float]] = {}
    for span, queries in run.programs():
        programs.setdefault("+".join(sorted(set(queries))), []).append(
            span.duration_s)
    print("window programs (runs, mean run ms): " + ", ".join(
        f"{k} {len(v)} {1e3 * sum(v) / len(v)}"
        for k, v in sorted(programs.items())), file=sys.stderr)
    print(f"after the window: reference {t_ref} s, trace reduction "
          f"{t_reduce} s", file=sys.stderr)
    if ref_error is not None:
        print(f"reference refused the data: {ref_error}", file=sys.stderr)
    for line in check.lines(numbers):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
