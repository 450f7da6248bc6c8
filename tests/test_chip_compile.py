"""Ahead-of-time compiles of the grouped expert-FFN kernels for a TPU v5e.

Nothing runs: each test lowers a kernel at published widths for a chip
that is described, not attached, and asserts that the TPU compiler
accepts it (Mosaic tile shapes, scoped VMEM).  Interpret mode on CPU
cannot see either refusal.  Widths: Granite-MoE-3B-A800M (d=1536,
d_expert=512) and Mixtral-8x7B (d=4096, d_expert=14336).

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, and every
pytest-xdist worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_gemm import moe_ffn_kernel, moe_ffn_packed_kernel
from repro.kernels.moe_gemm import ops as moe_ops

WIDTHS = {"granite": (1536, 512), "mixtral": (4096, 14336)}
N_EXPERTS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _int8_parts(spec, d, f):
    up = (spec((N_EXPERTS, d, f), "int8"), spec((N_EXPERTS, 1, f), "float32"))
    down = (spec((N_EXPERTS, f, d), "int8"),
            spec((N_EXPERTS, 1, d), "float32"))
    return {"w_gate": up, "w_up": up, "w_down": down}


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("wdtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_moe_ffn_kernel_compiles(one_chip, width, wdtype, rows):
    d, f = WIDTHS[width]
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = moe_ffn_kernel.lower(
        spec((N_EXPERTS, rows, d), "float32"),
        spec((N_EXPERTS, d, f), wdtype), spec((N_EXPERTS, d, f), wdtype),
        spec((N_EXPERTS, f, d), wdtype)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_moe_ffn_kernel_ragged_f_compiles(one_chip):
    """A final F tile that overhangs F takes the in-kernel 2-D mask."""
    d, f = 1536, 640                       # 640 = 512 + a ragged 128
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = moe_ffn_kernel.lower(
        spec((N_EXPERTS, 8, d), "float32"),
        spec((N_EXPERTS, d, f), "bfloat16"),
        spec((N_EXPERTS, d, f), "bfloat16"),
        spec((N_EXPERTS, f, d), "bfloat16"), block_f=512).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_packed_int8_kernel_compiles(one_chip, width, rows):
    d, f = WIDTHS[width]
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = moe_ffn_packed_kernel.lower(
        spec((N_EXPERTS, rows, d), "float32"), _int8_parts(spec, d, f),
        scheme="int8").compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the "
    "overall array — the nf4 absmax block (1, d, Fb/64) is neither"))
def test_packed_nf4_kernel_compiles(one_chip):
    d, f = WIDTHS["mixtral"]
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    up = (spec((N_EXPERTS, d, f // 2), "uint8"),
          spec((N_EXPERTS, d, f // 64), "float32"))
    down = (spec((N_EXPERTS, f, d // 2), "uint8"),
            spec((N_EXPERTS, f, d // 64), "float32"))
    moe_ffn_packed_kernel.lower(
        spec((N_EXPERTS, 8, d), "float32"),
        {"w_gate": up, "w_up": up, "w_down": down}, scheme="nf4").compile()


@pytest.mark.parametrize("rows,n_stacked", [(1, 8), (4, 40)])
def test_grouped_contrib_step_takes_the_kernel(one_chip, monkeypatch, rows,
                                               n_stacked):
    """The jitted decode step the engine and the reference both call:
    a wave of 8 slot experts, and the reference's stack of all 40
    Granite experts (padded to 64 inside the trace).  The backend query
    sees this CPU, so the test steers it to the TPU branch."""
    monkeypatch.setattr(moe_ops, "_on_tpu", lambda: True)
    d, f = WIDTHS["granite"]
    k = 8
    spec = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    compiled = moe_ops._grouped_contrib.lower(
        spec((rows, d), "bfloat16"),
        spec((n_stacked, d, f), "bfloat16"),
        spec((n_stacked, d, f), "bfloat16"),
        spec((n_stacked, f, d), "bfloat16"),
        spec((rows, k), "int32"), spec((rows, k), "float32")).compile()
    assert "tpu_custom_call" in compiled.as_text()
