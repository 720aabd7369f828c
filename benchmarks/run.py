#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell of BENCHMARK.json in this process.
See benchmarks/README.md."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks.harness import main

    sys.exit(main())
