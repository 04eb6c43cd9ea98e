"""Shared test configuration.

``hypothesis`` is a required dev dependency (see requirements-dev.txt): the
property tests (test_kernels.py, test_properties.py,
test_broker_properties.py and the property cases of the broker test files)
belong to the tier-1 suite.

This conftest puts ``src/`` on ``sys.path`` so
``python -m pytest`` works from the repo root even without
``PYTHONPATH=src``.
"""
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
