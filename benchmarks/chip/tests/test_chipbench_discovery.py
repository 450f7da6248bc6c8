"""A new configuration, traffic mix, per-layer metric and architecture are
new files, found by name: no file that is already there changes."""
from __future__ import annotations

import json
import shutil
import time

import numpy as np
import pytest

import chipbench_tiny
from chipbench import config, driver, spec, traffic


@pytest.fixture
def tree(tmp_path):
    spec_file = chipbench_tiny.build(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    return tmp_path, spec_file, before


def test_new_cell_is_found_by_name(tree):
    base, spec_file, before = tree
    cfg = dict(chipbench_tiny.CONFIG, name="tiny-wide", hidden_size=128)
    (base / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    mix = dict(chipbench_tiny.MIX, name="pairs", clients=2, max_batch=2)
    (base / "traffic" / "pairs.json").write_text(json.dumps(mix))
    (base / "metrics" / "mean_prompt.py").write_text(
        "def read(run):\n"
        "    n = [len(c.req.prompt) for c in run.clients]\n"
        "    return sum(n) / len(n) if n else None\n")
    raw = json.loads(spec_file.read_text())
    raw["workloads"].append({"name": "tiny-wide.pairs",
                             "config": "tiny-wide", "traffic": "pairs",
                             "chips": 1, "why": "a new cell"})
    raw["per_layer"].append(dict(chipbench_tiny.metric("mean_prompt",
                                                       "tokens"),
                                 workloads=["tiny-wide.pairs"]))
    spec_file.write_text(json.dumps(raw))

    cell = spec.load_cell("tiny-wide.pairs", spec_file)
    assert (cell.config, cell.traffic) == ("tiny-wide", "pairs")
    assert "mean_prompt" in [m.name for m in cell.per_layer]
    assert "mean_prompt" not in [m.name for m in
                                 spec.load_cell("tiny.solo",
                                                spec_file).per_layer]
    assert config.load("tiny-wide", base).model.d_model == 128
    m = traffic.Mix.from_dict(spec.load_json("traffic", "pairs", base))
    assert m.clients == 2
    fake = type("Run", (), {"clients": [type("C", (), {"req": r})()
                                        for r in traffic.make_requests(
                                            m, 512, 3)[:4]]})()
    new = [m for m in cell.per_layer if m.name == "mean_prompt"]
    got = spec.read_metrics(new, fake, base)
    assert got["mean_prompt"]["unit"] == "tokens"
    after = {p: p.read_bytes() for p in before}
    changed = [p.name for p in before
               if p != spec_file and after[p] != before[p]]
    assert changed == []


def test_reader_that_finds_nothing_leaves_the_metric_out(tree):
    base, spec_file, _ = tree
    cell = spec.load_cell("tiny.solo", spec_file)
    empty = type("Run", (), {"counters": {"loads": 0, "decoded_tokens": 0},
                             "records": [], "steps": []})()
    assert spec.read_metrics(list(cell.per_layer), empty, base) == {}


def test_unknown_names_are_errors(tree):
    base, spec_file, _ = tree
    with pytest.raises(KeyError):
        spec.load_cell("tiny.nothing", spec_file)
    with pytest.raises(FileNotFoundError):
        config.load("no-such-model", base)
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric", base)
    raw = dict(chipbench_tiny.CONFIG, name="alien", model_type="alien")
    (base / "configs" / "alien.json").write_text(json.dumps(raw))
    with pytest.raises(KeyError, match="'alien'; known: .*granitemoe"):
        config.load("alien", base)


def test_the_real_benchmark_files_resolve():
    for w in json.loads(spec.find_spec_file().read_text())["workloads"]:
        cell = spec.load_cell(w["name"])
        config.load(cell.config)
        traffic.Mix.from_dict(spec.load_json("traffic", cell.traffic))
        spec.load_json("limits", cell.name)
        for m in cell.per_layer:
            spec.metric_reader(m.name)
        assert "setup_s" in [m.name for m in cell.end_to_end]


def test_bench_dir_alone_is_enough(tmp_path):
    """The package finds everything under its own directory."""
    for d in ("configs", "arch"):
        shutil.copytree(spec.BENCH_DIR / d, tmp_path / d)
    assert config.load("granite3-3b-a800m-8L", tmp_path).model.num_layers \
        == 8


# A new architecture, as a later change would bring it: the Granite
# plug-in copied at tiny size, with its routed experts kept on the host as
# ``numpy`` leaves and brought to the device a layer at a time by its
# reference.  ``broken`` leaves the experts' output out of the reference.
TOY_EDITS = {
    "clean": [
        ("def make_params(cfg, seed: int) -> dict:\n"
         "    params = _maker(cfg)(seed_key(seed))\n"
         "    jax.block_until_ready(params)\n",
         "def make_params(cfg, seed: int) -> dict:\n"
         "    params = _maker(cfg)(seed_key(seed))\n"
         "    jax.block_until_ready(params)\n"
         "    ff = params[\"layers\"][0][\"ff\"]\n"
         "    for name in (\"w_gate\", \"w_up\", \"w_down\"):\n"
         "        ff[name] = np.asarray(ff[name])\n"),
        ("            layers, jnp.int32(li), x, positions,",
         "            jax.tree.map(lambda a: jnp.asarray(a[li:li + 1]), "
         "layers),\n            jnp.int32(0), x, positions,"),
    ],
}
TOY_EDITS["broken"] = TOY_EDITS["clean"] + [
    ("            x = x + y.astype(dt)\n", "")]


@pytest.mark.parametrize("variant,expect", [("clean", True),
                                            ("broken", False)])
def test_new_architecture_is_new_files(tree, variant, expect):
    base, spec_file, before = tree
    toy = (base / "arch" / "granitemoe.py").read_text()
    for old, new in TOY_EDITS[variant]:
        assert toy.count(old) == 1, old
        toy = toy.replace(old, new)
    (base / "arch" / "toy.py").write_text(toy)
    cfg = dict(chipbench_tiny.CONFIG, name="toy", model_type="toy")
    (base / "configs" / "toy.json").write_text(json.dumps(cfg))
    (base / "limits" / "toy.solo.json").write_text(
        json.dumps({"limits": chipbench_tiny.LIMITS}))
    raw = json.loads(spec_file.read_text())
    raw["configs"].append(dict(raw["configs"][0], name="toy",
                               file="configs/toy.json"))
    raw["workloads"].append({"name": "toy.solo", "config": "toy",
                             "traffic": "solo", "chips": 1,
                             "why": "a new architecture"})
    spec_file.write_text(json.dumps(raw))

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    out = driver.run("toy.solo", 2**33 + 7, 2.0, False, lambda m: None,
                     t_start=time.perf_counter(), require_chip=False,
                     base=base, spec_file=spec_file, compile_cache=False,
                     keep_served=True)
    res = out["result"]
    assert out["plugin"] is config.load("toy", base).plugin
    assert isinstance(out["params"]["layers"][0]["ff"]["w_gate"],
                      np.ndarray)
    assert res["checks"]["judged_steps"]["value"] > 0
    assert res["correct"] is expect, res["checks"]
    after = {p: p.read_bytes() for p in before}
    changed = [p.name for p in before
               if p != spec_file and after[p] != before[p]]
    assert changed == []
