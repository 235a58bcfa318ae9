"""Run one benchmark cell on the chip(s) this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers that decided ``correct`` are printed beside their limits as the
last lines of standard error.  Without a TPU (or with fewer chips than
the cell asks for) it exits non-zero and prints no result.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
