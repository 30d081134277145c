"""Without a TPU the benchmark refuses: a non-zero exit and no result."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench_testlib import ROOT


@pytest.mark.parametrize("workload", ["epsilon-dagd", "not-a-cell"])
def test_no_tpu_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
