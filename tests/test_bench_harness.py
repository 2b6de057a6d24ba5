"""The benchmark's own tests (``bench/tests``), collected with the rest.

The benchmark lives outside the package (``bench/``, run as
``python3 bench/run.py``) and its tests import its modules by their bare
names, as ``bench/tests/conftest.py`` arranges.  This file puts ``bench/``
on ``sys.path`` the same way and imports those tests, so a plain
``python -m pytest`` runs the yardstick's trace arithmetic, work counts,
readers, controls and planted faults too.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for path in (os.path.join(BENCH, "tests"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_controls import *  # noqa: E402,F401,F403
from test_harness import *  # noqa: E402,F401,F403
