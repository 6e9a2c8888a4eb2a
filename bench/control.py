"""The control of a configuration's comparison, at the configuration's
own size: the reference one precision step down, put in the program's
place.  Prints, per seed, the numbers the comparison reads for the control
against the reference, beside their limits; each seed has to fail.

    python bench/control.py --config <name> --seeds <n> [<n> ...]

The benchmark's own runs never run it; ``tests/bench`` keeps it as a test.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(config: str, seed: int) -> tuple[dict, bool]:
    from bench import check, common

    spec = common.config_spec(config)
    gen = common.config_module(config)
    data = gen.generate(spec, seed)
    numbers, _ = check.compare(spec["checks"], gen.reference(spec, data),
                               list(gen.control(spec, data).items()))
    return numbers, check.passed(numbers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    failed_all = True
    for seed in args.seeds:
        numbers, passed = readings(args.config, seed)
        failed_all &= not passed
        print(json.dumps({"config": args.config, "seed": seed,
                          "control_passed": passed, "checks": numbers}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
