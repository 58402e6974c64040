"""Record the small chip trace the trace-reduction tests read.

    python3 chipbench/tools/record_trace.py chipbench/tests/data/tiny_trace.xplane.pb

Runs the tiny test cell (``chipbench/tests/data``) through the harness
with ``--trace 1`` for one second and keeps its ``.xplane.pb``.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from chipbench import run as R  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out = argv[0]
    workload = argv[1] if len(argv) > 1 else "tiny.layup.m1"
    args = R.parse(["--workload", workload, "--seed", "7", "--seconds", "0.1",
                    "--trace", "1"])
    res = R.run(args, base=DATA, bench_path=os.path.join(DATA, "BENCHMARK.json"),
                keep_trace=out)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
