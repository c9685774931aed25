"""The benchmark's CPU tests import ``bench`` from the checkout root."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(pathlib.Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
