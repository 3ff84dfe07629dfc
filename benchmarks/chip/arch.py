"""The code that stands for a configuration's architecture, found by name.

A configuration file may name, under a top-level ``"arch"`` entry (beside
``serving`` and ``check``; not in ``model``, whose keys are the program's),
its plain reference and its counts of operations and bytes, as paths
under the benchmark's directory:

    "arch": {"reference": "reference/model.py", "costs": "costs.py"}

A role the entry leaves out, or a file without the entry, takes the
default in ``DEFAULTS``. A new architecture is then added as new files and
new entries, with no edit to the harness.

What each module offers (``m`` is the configuration's ``model`` dict):

reference
    ``logits_at(m, seed, seqs, rows, numerics, shape, timings)``: float32
    logits ``[sum(len(r)), V]`` at positions ``rows[i]`` of ``seqs[i]``,
    with weights drawn from ``seed`` by ``weights.py`` and matrix products
    in ``numerics`` ("float32", or the control's lower precision); and
    ``block(h, key, l, window, m, numerics)``: layer ``l`` over float32
    activations ``h`` [sequences, positions, d_model], ``window`` its
    attention window or 0, which ``rehearse_tpu_compile.py`` compiles at
    the size of a check; see ``reference/model.py``.
costs
    ``param_count(m)``, ``param_bytes(m)``, ``prefill_flops(m, prompt_len)``,
    ``decode_flops(m, positions)``, ``decode_bytes(m, positions)`` and
    ``roofline_s(flops, nbytes, peak)``; see ``costs.py``, whose
    ``roofline_s`` and ``DTYPE_BYTES`` another costs module may import.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict

HERE = Path(__file__).resolve().parent
DEFAULTS = {"reference": "reference/model.py", "costs": "costs.py"}


def load_module(path: Path):
    """A module from its file, under a name made from its directory and stem."""
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def path(role: str, config: Dict, bench_dir: Path = HERE) -> Path:
    """The file that plays ``role`` for the configuration ``config``."""
    named = config.get("arch", {})
    if set(named) - set(DEFAULTS):
        # a misspelt role would be measured with the default's counts
        raise ValueError(f"unknown roles in 'arch': {sorted(set(named) - set(DEFAULTS))}")
    found = (bench_dir / named.get(role, DEFAULTS[role])).resolve()
    if not found.is_relative_to(bench_dir.resolve()):
        # the code that decides `correct` comes from the checkout under test only
        raise ValueError(f"'arch' {role} {named[role]!r} lies outside {bench_dir}")
    return found


def _load(role: str, cell_or_config):
    config = getattr(cell_or_config, "config", cell_or_config)
    return load_module(path(role, config, getattr(cell_or_config, "bench_dir", HERE)))


def reference(cell_or_config):
    """The plain reference of a cell (``bench.Cell``, resolved under its
    benchmark directory) or of a configuration file's contents."""
    return _load("reference", cell_or_config)


def costs(cell_or_config):
    """The counts of operations and bytes of a cell or a configuration."""
    return _load("costs", cell_or_config)
