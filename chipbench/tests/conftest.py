"""Tests of the benchmark's own arithmetic; none needs a chip or starts a
daemon. Run from the root of the repo:

    python -m pytest chipbench/tests -q -p no:cacheprovider
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
