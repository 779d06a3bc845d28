"""The tests here shrink every configuration through ``tiny.CONFIG``. A PR
that adds a cell may add files and may not edit tiny.py, so a configuration
that came later brings its shrink in a file of its own and is entered into
the table here, before any test runs."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests import tiny, tiny_evabyte  # noqa: E402

tiny.CONFIG.setdefault("evabyte", tiny_evabyte.CONFIG)
