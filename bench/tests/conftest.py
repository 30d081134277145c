"""Fixtures of the benchmark's own tests: they run on the CPU, with no
chip, at sizes a test run holds."""
from __future__ import annotations

import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = pathlib.Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench_testlib import make_tiny_root  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))
