"""The chip benchmark of the guarded-aggregate serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on a TPU.  Everything that belongs to
one configuration, traffic mix or metric lives in a file of its own and is
found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — sizes, schema, service options,
  queries and the limits of the comparison; ``bench/configs/<config>.py``
  beside it — the generator and the plain numpy reference;
* ``bench/traffic/<mix>.json`` — parameters of the one general generator
  (``bench/traffic.py``);
* ``bench/metrics/<metric>.py`` — one reader per metric.
"""
