import sys

import jax
import pytest

from repro.models.config import ModelConfig

try:                                    # prefer the real hypothesis
    import hypothesis  # noqa: F401
except ModuleNotFoundError:             # container has none; use the shim
    from tests import _hypothesis_shim as _shim

    sys.modules["hypothesis"] = _shim
    sys.modules["hypothesis.strategies"] = _shim.strategies

jax.config.update("jax_platform_name", "cpu")

# Heavy cases of *computed* parametrizations (arch registries), marked
# here because their id lists are generated.  Literal parametrizations
# and whole modules carry explicit ``pytest.mark.slow`` instead.
_SLOW_NODES = (
    "test_archs_smoke.py::test_smoke_train_step[jamba-v0.1-52b]",
    "test_archs_smoke.py::test_smoke_decode_step[jamba-v0.1-52b]",
    "test_archs_smoke.py::test_smoke_train_step[llama3-8b]",
    "test_archs_smoke.py::test_smoke_decode_step[llama3-8b]",
    "test_archs_smoke.py::test_smoke_train_step[mamba2-2.7b]",
    "test_archs_smoke.py::test_smoke_train_step[chatglm3-6b]",
    "test_archs_smoke.py::test_smoke_train_step[qwen3-moe-30b-a3b]",
    "test_archs_smoke.py::test_smoke_train_step[internvl2-26b]",
    "test_archs_smoke.py::test_smoke_train_step[seamless-m4t-large-v2]",
    "test_archs_smoke.py::test_smoke_decode_step[seamless-m4t-large-v2]",
    "test_models.py::test_decode_matches_teacher_forcing[hybrid]",
    "test_models.py::test_decode_matches_teacher_forcing[audio-encdec]",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid.endswith(_SLOW_NODES):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True, scope="module")
def _release_jit_code():
    """The suite JITs thousands of small executables; without periodic
    release, LLVM's execution engine eventually fails to allocate JIT
    code pages ("Failed to materialize symbols") late in the run."""
    yield
    jax.clear_caches()


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def tiny_dense(**kw):
    base = dict(name="t-dense", family="dense", num_layers=3, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97)
    base.update(kw)
    return ModelConfig(**base)


def tiny_moe(**kw):
    base = dict(name="t-moe", family="moe", num_layers=4, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=0, d_expert=96,
                vocab_size=97, num_experts=8, top_k=2)
    base.update(kw)
    return ModelConfig(**base)


def tiny_ssm(**kw):
    base = dict(name="t-ssm", family="ssm", num_layers=2, d_model=64,
                num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=97,
                ssm_state=16, ssm_head_dim=16, ssm_chunk=4)
    base.update(kw)
    return ModelConfig(**base)


def tiny_hybrid(**kw):
    """One Granite-4.0-H period: Mamba-2 mixers with a NoPE attention
    layer at index 5, a routed FFN with a shared expert on every layer,
    the four Granite multipliers."""
    base = dict(name="t-hybrid", family="hybrid", num_layers=10,
                d_model=64, num_heads=4, num_kv_heads=2, d_ff=32,
                d_expert=32, d_shared=48, vocab_size=97, num_experts=8,
                top_k=3, ssm_state=16, ssm_head_dim=32, ssm_chunk=8,
                attn_every=10, attn_offset=5, rope_fraction=0.0,
                embedding_multiplier=12.0, attention_multiplier=0.0625,
                residual_multiplier=0.22, logits_scaling=16.0)
    base.update(kw)
    return ModelConfig(**base)
