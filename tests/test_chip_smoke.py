"""chip_smoke.py on this CPU: it refuses to run without a TPU, and its
phases pass on a tiny bfloat16 Granite-family model (the same code the
chip run drives at published widths)."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.models import init_params

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    cfg = dataclasses.replace(get_config(smoke.ARCH).reduced(),
                              dtype="bfloat16")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def _args(smoke, requests=0):
    args = smoke.serve_args(0, requests=requests)
    args.tokens, args.prompt_len = 6, 8
    return args


def test_single_stream_phase_passes(smoke, tiny):
    lines = []
    assert smoke.phase_single(*tiny, _args(smoke), lines.append)
    assert any("tokens == greedy_generate: True" in ln for ln in lines)


def test_serving_phase_passes(smoke, tiny):
    lines = []
    assert smoke.phase_serving(*tiny, _args(smoke, requests=3),
                               lines.append)
    assert any("solo greedy_generate: True" in ln for ln in lines)
