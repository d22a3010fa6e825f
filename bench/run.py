"""Run one benchmark cell on the chips of this machine (see
``bench/harness.py``)::

    python3 bench/run.py --workload qwen2.5-3b-l9.s256 --seed 7 \
        --seconds 10 --trace 0
"""

import time

CLOCK0 = time.monotonic()   # set-up time counts from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], CLOCK0, ROOT))
