"""CPU tests of the benchmark harness: ``pytest bench/tests``.

They run on the CPU whatever the machine holds, and put the harness
(``bench/``) and the program (``src/``) on the import path.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
