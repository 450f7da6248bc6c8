"""The Granite-MoE architecture plug-in (``arch/granitemoe.py``): its
reference agrees with the program's own float32 yardstick, its weights,
reference and operation counts are the ones the harness had before they
moved into the plug-in, and its counts check by hand for
Granite-3.0-3B-A800M."""
from __future__ import annotations

import hashlib

import jax
import numpy as np
import pytest

import chipbench_tiny
from chipbench import config, spec


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp("tiny")
    chipbench_tiny.build(base)
    cfg = config.load("tiny", base)
    return cfg, cfg.plugin, cfg.plugin.Arch.from_config(cfg.raw)


@pytest.fixture(scope="module")
def granite3b():
    return config.load("granite3-3b-a800m-8L").model


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_configurations_name_the_plugin():
    assert config.load("granite3-3b-a800m-8L").plugin is \
        spec.arch_module("granitemoe")


# Golden values: what the harness's own functions gave before they moved
# into the plug-in (``weights.make_params``, ``reference.forward``,
# ``flops.prompt_flops`` / ``decode_flops``), on the CPU.  The move has to
# keep every bit of the weights and reference outputs, and every count.
GOLDEN_WEIGHTS = {
    3: "33cc5b6e1acd30a0a2abb483dceb066db2fda62384600c47a8452f29925f7f6e",
    2**33 + 7:
        "5a32b290368b04577cc637b790cdc97c9e1e635a98513256743348a601cd10ae",
}
GOLDEN_REFERENCE = {
    "logits":
        "6a954330e1242bbdc4b4eaf4a636a99d44edf6a5300f160b1bac729cf0a96352",
    "router":
        "512bb046f5e6db5dcf7306b3eb2173028614f5703e25194e76fab347fe971026",
    "routing":
        "e2b0f34eb29dda474b353274f0ae6c8f207df86c29d88e02648af77dfb333b11",
    "forced_logits":
        "8e25651adba305cfb28be4ef2f2e8dceafd46b08ce0b6202c59300086889ebc9",
    "fp8_logits":
        "877a2684ab2408817154d621ef0404608f7d95f44dad07241d4de7d10048a02a",
    "fp8_router":
        "5262ec97c193e60dd519d600fe3a62796a1a0328ab4eac3600e29a5266cc0be6",
}
GOLDEN_FLOPS = {      # config -> ({prompt length: FLOPs}, {context: FLOPs})
    "tiny": ({1: 166_400, 12: 2_030_592, 24: 4_134_912},
             {1: 166_400, 17: 174_592, 40: 186_368}),
    "granite3-3b-a800m-8L": (
        {256: 143_604_842_496, 1024: 593_746_722_816,
         3072: 1_935_858_991_104},
        {32: 556_213_248, 640: 586_097_664, 1026: 605_070_336}),
    "granite3-1b-a400m-24L": (
        {256: 222_681_366_528, 1024: 929_380_171_776,
         3072: 3_097_378_160_640},
        {32: 860_362_752, 640: 920_131_584, 1026: 958_076_928}),
}


@pytest.mark.parametrize("seed", sorted(GOLDEN_WEIGHTS))
def test_weights_are_bit_identical_to_the_golden(tiny, seed):
    cfg, plugin, _ = tiny
    params = plugin.make_params(cfg.model, seed)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert _digest(a for _, a in leaves) == GOLDEN_WEIGHTS[seed]


def test_reference_is_bit_identical_to_the_golden(tiny):
    cfg, plugin, arch = tiny
    params = plugin.make_params(cfg.model, 3)
    tokens = np.random.default_rng(0).integers(0, 512, 20).astype(np.int32)
    pos = np.arange(10, 20)
    layers = range(arch.num_layers)
    ref = plugin.forward(arch, params, tokens, pos)
    forced = {li: (ref.routing[li] + 1) % arch.num_experts for li in layers}
    ctl = plugin.forward(arch, params, tokens, pos, mode="fp8")
    got = {
        "logits": _digest([ref.logits]),
        "router": _digest([ref.router[li] for li in layers]),
        "routing": _digest([ref.routing[li] for li in layers]),
        "forced_logits": _digest([plugin.forward(arch, params, tokens, pos,
                                                 forced=forced).logits]),
        "fp8_logits": _digest([ctl.logits]),
        "fp8_router": _digest([ctl.router[li] for li in layers]),
    }
    assert got == GOLDEN_REFERENCE


@pytest.mark.parametrize("name", sorted(GOLDEN_FLOPS))
def test_flops_equal_the_golden(tiny, name):
    cfg = tiny[0] if name == "tiny" else config.load(name)
    prompt, decode = GOLDEN_FLOPS[name]
    assert {n: cfg.plugin.prompt_flops(cfg.model, n) for n in prompt} \
        == prompt
    assert {c: cfg.plugin.decode_flops(cfg.model, c) for c in decode} \
        == decode


def test_reference_agrees_with_the_programs_float32_yardstick(tiny):
    import jax.numpy as jnp
    from repro.core.yardstick import float32_reference
    cfg, plugin, arch = tiny
    params = plugin.make_params(cfg.model, 3)
    tokens = np.random.default_rng(0).integers(0, 512, 20).astype(np.int32)
    pos = np.arange(20)
    ours = plugin.forward(arch, params, tokens, pos)
    theirs, routing = float32_reference(cfg.model, params,
                                        jnp.asarray(tokens)[None])
    np.testing.assert_allclose(ours.logits, theirs[0], rtol=2e-4, atol=2e-4)
    for li in range(arch.num_layers):
        assert [set(r) for r in ours.routing[li]] == \
            [set(r) for r in routing[li][0]]


def test_forced_routing_is_used_where_given(tiny):
    cfg, plugin, arch = tiny
    params = plugin.make_params(cfg.model, 4)
    tokens = np.arange(10, dtype=np.int32)
    pos = np.array([7, 8, 9])
    own = plugin.forward(arch, params, tokens, pos)
    forced = {li: (own.routing[li] + 1) % arch.num_experts
              for li in range(arch.num_layers)}
    out = plugin.forward(arch, params, tokens, pos, forced=forced)
    for li in range(arch.num_layers):
        np.testing.assert_array_equal(out.routing[li], forced[li])
    assert not np.allclose(out.logits, own.logits)


def test_seed_beyond_32_bits_makes_distinct_weights(tiny):
    cfg, plugin, _ = tiny
    a = plugin.make_params(cfg.model, 5)
    b = plugin.make_params(cfg.model, 5 + 2**32)
    c = plugin.make_params(cfg.model, 5)
    leaf = lambda p: np.asarray(p["layers"][0]["ff"]["w_gate"],  # noqa
                                np.float32)
    assert not np.array_equal(leaf(a), leaf(b))
    np.testing.assert_array_equal(leaf(a), leaf(c))
    assert jax.tree.structure(a) == jax.tree.structure(c)


def test_active_flops_by_hand(granite3b):
    # per layer: q (1536x1536) + k, v (1536x512 each) + o (1536x1536)
    # = 6,291,456 weights; router 1536x40 = 61,440; 8 experts x 3 x
    # 1536 x 512 = 18,874,368 -> 25,227,264 weights x 2 FLOPs
    plugin = spec.arch_module("granitemoe")
    per_layer = 2 * (6_291_456 + 61_440 + 18_874_368)
    head = 2 * 1536 * 49155
    assert plugin.active_matmul_flops(granite3b) == 8 * per_layer + head


def test_attention_and_prompt_flops_by_hand(granite3b):
    # 24 heads x 64 dims, QK and PV: 4 x 1536 FLOPs per key and layer
    plugin, cfg = spec.arch_module("granitemoe"), granite3b
    assert plugin.attention_flops(cfg, 100) == 8 * 4 * 1536 * 100
    n = 3
    assert plugin.prompt_flops(cfg, n) == (
        3 * plugin.active_matmul_flops(cfg) + 8 * 4 * 1536 * (1 + 2 + 3))
    assert plugin.decode_flops(cfg, 10) == (
        plugin.active_matmul_flops(cfg) + 8 * 4 * 1536 * 10)
