"""The benchmark's CPU tests: ``bench`` is imported from the checkout's
root, and the cells run at a small size (``SMOKE_*``)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
