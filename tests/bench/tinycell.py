"""A benchmark cell at a size the CPU runs in seconds, for the bench tests.

``tiny-<model>`` is the ogbn-mag schema at 0.2% of OGB's counts, batch 16,
fanouts 3,2 (``fixtures/configs``).  Its limits are the real cell's
(``bench/limits/<model>-mag.frozen.json``), or for a model with no cell
those of ``fixtures/limits``, unless a test gives others.
"""

import json
import time
from pathlib import Path

from bench import harness

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def bm(model: str, traffic: str = "frozen") -> dict:
    real = harness.benchmark()
    return {
        "configs": [{"name": f"tiny-{model}",
                     "file": f"tests/bench/fixtures/configs/tiny-{model}.json"}],
        "workloads": [{"name": f"tiny-{model}.{traffic}", "config": f"tiny-{model}",
                       "traffic": traffic, "chips": 1}],
        "end_to_end": real["end_to_end"],
        "per_layer": real["per_layer"],
    }


def root_with_limits(tmp_path: Path, model: str, traffic: str = "frozen",
                     limits: dict = None) -> Path:
    """A lookup root holding only the tiny cell's limits file (the rest of
    the cell's files come from ``bench/``)."""
    name = f"tiny-{model}.{traffic}"
    if limits is None:
        path = harness.BENCH / "limits" / f"{model}-mag.frozen.json"
        if not path.exists():
            path = FIXTURES / "limits" / f"tiny-{model}.frozen.json"
        limits = harness.load_json(path)["limits"]
    (tmp_path / "limits").mkdir(parents=True, exist_ok=True)
    (tmp_path / "limits" / f"{name}.json").write_text(json.dumps({"limits": limits}))
    return tmp_path


def run(tmp_path: Path, model: str, traffic: str = "frozen", seed: int = 5,
        trace: bool = False, limits: dict = None) -> dict:
    root = root_with_limits(tmp_path, model, traffic, limits)
    return harness.run(f"tiny-{model}.{traffic}", seed, 0.2, trace,
                       time.perf_counter(), require_tpu=False,
                       bm=bm(model, traffic), root=root)
