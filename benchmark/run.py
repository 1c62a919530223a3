#!/usr/bin/env python3
"""One run of one benchmark cell on the chip:

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (`correct`, `attempted`,
`failed`, `metrics`, `device`, with --trace 1 `breakdown`, and last
`compared`: each number compared with the reference beside its limit).
Exits non-zero, printing no result, without the TPU chips the cell asks
for; there is no CPU fallback.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness.core import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
