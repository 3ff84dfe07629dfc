"""Smoke-size cells for the harness's CPU tests, built from data alone.

``make_root(dst)`` copies the benchmark directory and ``BENCHMARK.json``
under ``dst`` and adds two cells, one per reference family, whose
configuration and traffic files are small copies of the real ones: the
way a later change adds a cell, with no edit to the harness.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

SMALL = {
    "hymba-1.5b": dict(num_layers=3, d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                       vocab_size=256, head_dim=32, ssm_state=8, ssm_head_dim=32, ssm_chunk=16,
                       sliding_window=32, global_attn_layers=[0]),
    "granite-8b-18l": dict(num_layers=2, d_model=64, num_heads=8, num_kv_heads=2, d_ff=160,
                           vocab_size=256, head_dim=8),
}
CELLS = {"hymba-smoke": ("hymba-1.5b", "hymba-chat", "chat", 160, 100),
         "granite-smoke": ("granite-8b-18l", "granite-decode", "batch-gen", 320, 100)}


def make_root(dst: Path) -> Path:
    rel = "benchmarks/chip"
    shutil.copytree(HERE, dst / rel, ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell, (base, like, mix, cache_len, max_prompt) in CELLS.items():
        conf = json.loads((HERE / "configs" / f"{base}.json").read_text())
        conf["name"] = cell
        conf["model"].update(SMALL[base], name=cell)
        conf["serving"]["cache_len"] = cache_len
        (dst / rel / "configs" / f"{cell}.json").write_text(json.dumps(conf))
        traffic = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
        traffic["prompt"].update(min=min(traffic["prompt"]["min"], max_prompt // 2), max=max_prompt)
        (dst / rel / "traffic" / f"{cell}.json").write_text(json.dumps(traffic))
        bench["configs"].append({"name": cell, "source": "smoke size", "reduced": [],
                                 "file": f"{rel}/configs/{cell}.json", "why": "CPU test"})
        bench["workloads"].append({"name": cell, "config": cell, "traffic": cell, "chips": 1,
                                   "why": "CPU test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst
