"""A wave's slot weights stacked in one jitted program.

``stack_shards`` replaces the eager per-expert ``jnp.stack`` that
``WorkerSlots.gather_stack`` and ``ODMoEEngine._compute_hosted`` ran:
these tests pin that it stacks the same bits in the same order, that it
compiles once per wave shape, and that a serving run compiles nothing
once its shapes are warm.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_moe
from repro.core import ExpertStore, ODMoEEngine, WorkerSlots
from repro.core.store import stack_shards
from repro.models import init_params
from repro.quant.transport import EXPERT_WEIGHT_NAMES
from repro.serve import Request, ServingLoop

CFG = tiny_moe(num_layers=2)
CFG_BF16 = tiny_moe(num_layers=2, dtype="bfloat16")


@pytest.fixture(scope="module")
def models():
    return {cfg.dtype: (cfg, init_params(cfg, jax.random.PRNGKey(0)))
            for cfg in (CFG, CFG_BF16)}


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


def _compiles_during(fn):
    compiles = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    return compiles


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_stack_shards_bit_equals_eager_stack(models, dtype, n):
    """The jitted stack holds the eager ``jnp.stack``'s bits, expert by
    expert in the order given."""
    cfg, params = models[dtype]
    store = ExpertStore(cfg, params)
    li = store.moe_layers[0]
    experts = [(3 * i + 1) % cfg.num_experts for i in range(n)]
    shards = [store.unpack_shard(li, e) for e in experts]
    got = stack_shards(shards)
    assert set(got) == set(EXPERT_WEIGHT_NAMES)
    for name in EXPERT_WEIGHT_NAMES:
        want = jnp.stack([s[name] for s in shards])
        assert _same_bits(got[name], want), (dtype, n, name)


def test_stack_shards_compiles_once_per_wave_shape(models):
    """New weights of a shape already stacked reuse its program."""
    cfg, params = models["bfloat16"]
    store = ExpertStore(cfg, params)
    li = store.moe_layers[0]
    stack_shards([store.unpack_shard(li, e) for e in (0, 1)])
    again = []
    assert _compiles_during(lambda: again.append(stack_shards(
        [store.unpack_shard(li, e) for e in (5, 2)]))) == []
    assert _same_bits(again[0]["w_up"], jnp.stack(
        [store.unpack_shard(li, e)["w_up"] for e in (5, 2)]))


def test_gather_stack_stacks_resident_slots_in_expert_order(models):
    """``gather_stack`` orders the wave by expert id, whatever worker
    holds each one, and stacks exactly the slots' contents."""
    cfg, params = models["float32"]
    store = ExpertStore(cfg, params, policy="int8")
    li = store.moe_layers[-1]
    slots = WorkerSlots(store, 3)
    wave = {6: 0, 2: 2, 4: 1}
    for e, w in wave.items():
        slots.load(0, li, e, worker=w, predicted=True)
    experts, stacked = slots.gather_stack(li, wave)
    assert experts == [2, 4, 6]
    for i, e in enumerate(experts):
        slot = slots.slot(wave[e], li, e)
        for name in EXPERT_WEIGHT_NAMES:
            assert _same_bits(stacked[name][i], slot[name]), (e, name)


def test_serving_compiles_nothing_after_warm_up(models):
    """Once every batch size has run, requests of different ages joining
    one composed step reuse the warmed programs."""
    cfg, params = models["bfloat16"]
    loop = ServingLoop(ODMoEEngine(cfg, params, n_workers=cfg.top_k),
                       max_batch=3)
    loop.start([], cache_len=32)
    rng = np.random.default_rng(0)
    rid = itertools.count()

    def request(new_tokens):
        return Request(rid=next(rid), max_new_tokens=new_tokens,
                       prompt=rng.integers(0, cfg.vocab_size, 6
                                           ).astype(np.int32),
                       arrival_s=0.0)

    def drain():
        while loop.has_work():
            loop.tick()

    for rows in (1, 2, 3):                  # warm every batch size
        for _ in range(rows):
            loop.add_request(request(3))
        drain()

    def staggered():
        for new_tokens in (8, 6, 4):        # each joins mid-decode
            loop.add_request(request(new_tokens))
            loop.tick()
            loop.tick()
        drain()
    assert _compiles_during(staggered) == []
