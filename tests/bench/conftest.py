"""Fixtures for the benchmark's own tests. ``bench`` is imported as a
package from the repository root, which goes on the path here."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_helpers import make_tiny_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_tiny_root(tmp_path / "checkout")
