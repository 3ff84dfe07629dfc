"""``chip_smoke.py``: refuses to run without a TPU, and its serving run and
both checks pass on the reduced hymba config on the CPU."""
import importlib.util
from pathlib import Path

import jax

from repro.configs import get_config, get_smoke

_PATH = Path(__file__).resolve().parent.parent / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


def test_serving_and_checks_pass_at_smoke_size(capsys):
    ok = chip_smoke.run(
        get_smoke(chip_smoke.ARCH), seed=0, slots=4, cache_len=96, requests=5,
        min_prompt=8, max_prompt=40, max_new=5,
        # one length inside the smoke config's 32-token window, one past it
        consistency_lens=(10, 60), f32_len=40)
    out = capsys.readouterr().out
    assert ok, out
    assert "served 5/5 requests, 25 tokens" in out
    assert "consistency PASS" in out and "float32_reference PASS" in out


def test_full_width_shape_matches_the_published_config():
    cfg = get_config(chip_smoke.ARCH)
    assert (cfg.num_layers, cfg.d_model) == (32, 1600)
    shape = chip_smoke.SHAPE
    assert (shape["slots"], shape["cache_len"], shape["requests"],
            shape["max_new"]) == (4, 2048, 8, 16)
    assert max(shape["consistency_lens"]) + 1 <= shape["cache_len"]
    assert min(shape["consistency_lens"]) < cfg.sliding_window < max(
        shape["consistency_lens"])
