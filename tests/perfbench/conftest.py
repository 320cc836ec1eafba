"""The benchmark's own tests: ``perfbench/`` goes on the path so that its
modules import as the harness imports them (``pb.*``, ``readers.*``)."""

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERFBENCH = os.path.join(CHECKOUT, "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
