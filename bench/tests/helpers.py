"""Small cells for the CPU tests: the real configuration files at a map the
CPU can train in seconds, the real cell files and their limits."""
import json

from run_cell import BENCH, entry_of

# Cells whose files are kept for a later PR but that BENCHMARK.json does not
# list yet; their entries, as that PR will add them.
UNLISTED = [
    {"name": "coco608.train.2x2", "config": "yolov2-16.coco608",
     "traffic": "train.b64.xla.2x2", "chips": 4},
]


def small(cell_name: str, hw: int = 64, batch: int = 8):
    """The benchmark (with the unlisted entries), the cell and its
    configuration, at ``hw`` x ``hw`` and ``batch`` rows."""
    benchmark = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    listed = {w["name"] for w in benchmark["workloads"]}
    benchmark["workloads"] += [w for w in UNLISTED if w["name"] not in listed]
    entry = entry_of(benchmark, cell_name)
    cell = json.loads((BENCH / "cells" / f"{cell_name}.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{entry['config']}.json").read_text())
    cfg.update(width=hw, height=hw)
    cell.update(batch=batch)
    return benchmark, cell, cfg


def chips(benchmark: dict, cell_name: str) -> int:
    return entry_of(benchmark, cell_name)["chips"]
