"""The harness's own tests: ``python -m pytest perfbench/tests -q``.

They run on the CPU at toy widths (``perfbench/tests/root`` is a
benchmark made only of added files) and never look for a chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
