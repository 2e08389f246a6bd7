"""The benchmark's CPU tests: the repository root on ``sys.path`` (for
``bench``) and ``src`` (for the program)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
