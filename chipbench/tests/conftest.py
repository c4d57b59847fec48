"""Puts the checkout (for ``chipbench``) and ``src`` (for the program) on
the path; the tests run on the CPU at a size a test run can hold."""

import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
