"""Helpers of the benchmark's own tests."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY = dict(n=2048, d=256, m=4, lam=1e-4, ref_iters=300)
TINY_ROUNDS = 60
TINY_THM2 = dict(instance_params=dict(d=16, lam=0.5, m=2),
                 kappa_range=[4.0, 16.0], rounds=300,
                 service=dict(max_batch=2, max_wait=0.05, cache_capacity=32))
TINY_RATE = 20.0


def load_run_module():
    """``bench/run.py`` as a module (it is a script, not a package)."""
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_tiny_root(root: pathlib.Path) -> pathlib.Path:
    """Fill ``root`` as a checkout holding the benchmark with its cells
    cut to a CPU size: ``BENCHMARK.json``'s cells pointed at ``tiny-*``
    configurations of the same recipes, with shorter solves."""
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    cells["epsilon-dagd"]["config"] = "tiny-logistic"
    cells["thm2-serve"]["config"] = "tiny-thm2"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    configs = root / "bench" / "configs"
    for name, base, changes in (
            ("tiny-logistic", "epsilon-logistic", dict(instance_params=TINY)),
            ("tiny-thm2", "thm2-certify", TINY_THM2)):
        cfg = json.loads((configs / f"{base}.json").read_text())
        cfg.update(changes, name=name)
        (configs / f"{name}.json").write_text(json.dumps(cfg))
        shutil.copy(configs / f"{base}.reference.py",
                    configs / f"{name}.reference.py")
    traffic = root / "bench" / "traffic"
    for mix_name, changes in (("solve-loop", dict(rounds=TINY_ROUNDS)),
                              ("serve-open-loop", dict(rate=TINY_RATE))):
        mix = json.loads((traffic / f"{mix_name}.json").read_text())
        mix.update(changes)
        (traffic / f"{mix_name}.json").write_text(json.dumps(mix))
    return root
