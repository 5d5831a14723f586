"""Shared set-up for the benchmark's own tests (run on the CPU).

    python3 -m pytest chipbench/tests

They are outside the repository's ``tests/``, so the tier-1 suite does not
collect them.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny-dense.tiny"


def make_root(dest: Path, limit: float = 0.05) -> Path:
    """A checkout-like root: BENCHMARK.json and the benchmark's directory,
    plus a toy configuration, a toy mix and a cell over them, all added as
    new files and new entries."""
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = dest / "chipbench"
    shutil.copy(DATA / "tiny-dense.json", bench / "configs" / "tiny-dense.json")
    shutil.copy(DATA / "tiny.json", bench / "traffic" / "tiny.json")
    (bench / "limits" / f"{TINY}.json").write_text(json.dumps({"max_logit_gap": limit}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dense", "source": "test",
                            "file": "chipbench/configs/tiny-dense.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": TINY, "config": "tiny-dense", "traffic": "tiny",
                              "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "checkout")


def tiny_run(root: Path, seed: int, *, trace: bool = False, control: bool = False,
             seconds: float = 2.0) -> dict:
    from chipbench import harness

    return harness.run_cell(TINY, seed, seconds, trace, t_start=harness.clock(), root=root,
                            require_chip=False, tune_device="tpu_v5e", control=control)
