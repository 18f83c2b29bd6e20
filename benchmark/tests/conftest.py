"""benchmark/tests: the harness's own tests, on the CPU.

    python -m pytest benchmark/tests -q

Not part of the repository's tier-1 suite.  Runner tests rehearse the
control flow at a tiny size; no number they see is a measurement.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)
