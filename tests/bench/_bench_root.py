"""Puts the repository root (for ``bench``) and ``src`` (for ``repro``) on
``sys.path``.  A module of its own, not a ``conftest.py``: the suite's
other files import ``conftest`` by name, and a second one would shadow
``tests/conftest.py``."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
