"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started on:
set-up (data from the seed, one warm-up call of each shape, every compile
answered from the persistent cache after the first run in a checkout),
the measured window, the peak memory, then — with the program's state
freed — the plain reference and the comparison that decide ``correct``.
The last line of standard output is the result; the line before it holds
the set-up phases and the compile counts.
"""

import os
import sys
import time

_STARTED = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

if __name__ == "__main__":
    from benchmark import harness

    raise SystemExit(harness.main(sys.argv[1:], root=_ROOT, started=_STARTED))
