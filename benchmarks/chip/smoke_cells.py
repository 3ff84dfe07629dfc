"""Smoke-size cells for the harness's CPU tests, built from data alone.

Each configuration file carries its CPU test sizes under ``"smoke"``,
which a run on the chip never reads: ``name`` (the smoke cell's),
``model`` (keys that replace the configuration's own), ``cache_len`` and
``max_prompt``. ``make_root(dst)`` copies the benchmark directory and
``BENCHMARK.json`` under ``dst`` and adds one smoke cell per configuration
listed there, a small copy of its first cell's configuration and traffic:
the way a later change adds a cell, with no edit to the harness.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def configs() -> List[Dict]:
    """Every configuration file listed in ``BENCHMARK.json``, read."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [json.loads((ROOT / c["file"]).read_text()) for c in bench["configs"]]


def model(conf: Dict, **keys) -> Dict:
    """The configuration's model at its smoke size, with ``keys`` over it."""
    return dict(conf["model"], **conf["smoke"]["model"], name=conf["smoke"]["name"], **keys)


def make_root(dst: Path) -> Path:
    rel = "benchmarks/chip"
    shutil.copytree(HERE, dst / rel, ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry, conf in zip(list(bench["configs"]), configs()):
        smoke = conf["smoke"]
        cell = smoke["name"]
        like = next(w for w in bench["workloads"] if w["config"] == entry["name"])
        conf = dict(conf, name=cell, model=model(conf),
                    serving=dict(conf["serving"], cache_len=smoke["cache_len"]))
        (dst / rel / "configs" / f"{cell}.json").write_text(json.dumps(conf))
        traffic = json.loads((HERE / "traffic" / f"{like['traffic']}.json").read_text())
        max_prompt = smoke["max_prompt"]
        traffic["prompt"].update(min=min(traffic["prompt"]["min"], max_prompt // 2), max=max_prompt)
        (dst / rel / "traffic" / f"{cell}.json").write_text(json.dumps(traffic))
        bench["configs"].append({"name": cell, "source": "smoke size", "reduced": [],
                                 "file": f"{rel}/configs/{cell}.json", "why": "CPU test"})
        bench["workloads"].append({"name": cell, "config": cell, "traffic": cell, "chips": 1,
                                   "why": "CPU test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like["name"] in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst
