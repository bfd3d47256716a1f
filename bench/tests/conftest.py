"""Fixtures of the benchmark's own tests (see bench_tiny.py)."""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return bench_tiny.make_root(tmp_path_factory.mktemp("checkout"))
