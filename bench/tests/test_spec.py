"""A configuration, a mix, a metric and a cell added as new files (and
entries in ``BENCHMARK.json``) are found by name; no registry is edited."""
import dataclasses
import json
import shutil

import pytest

from bench import spec
from bench.tests.test_run import make_root


@pytest.fixture()
def root(tmp_path):
    return make_root(tmp_path)


def _add(root, sub, name, content):
    path = root / "bench" / sub / name
    path.write_text(content if isinstance(content, str)
                    else json.dumps(content))


def test_new_files_are_found_by_name(root):
    cfg = json.loads((root / "bench/configs/tiny_qwen.json").read_text())
    cfg["num_hidden_layers"] = 3
    _add(root, "configs", "tiny_qwen3.json", cfg)
    mix = json.loads((root / "bench/traffic/tiny_chat.json").read_text())
    mix["rate_rps"] = 5.0
    _add(root, "traffic", "bursty.json", mix)
    shutil.copy(root / "bench/workloads/tiny_qwen.chat.json",
                root / "bench/workloads/tiny_qwen3.bursty.json")
    _add(root, "metrics", "queue.depth_max.py",
         '"""A new reader."""\n\ndef read(run):\n    return 42.0\n')
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="tiny_qwen3",
                                 file="bench/configs/tiny_qwen3.json"))
    bench["workloads"].append({"name": "tiny_qwen3.bursty",
                               "config": "tiny_qwen3", "traffic": "bursty",
                               "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "queue.depth_max", "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": "serving scheduler and KV pool",
        "moves": "ttft_p95_s", "workloads": ["tiny_qwen3.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("tiny_qwen3.bursty", root)
    assert cell.traffic["rate_rps"] == 5.0
    assert spec.arch_config(cell).n_layers == 3
    assert [m.name for m in cell.per_layer] == ["queue.depth_max"]
    assert cell.per_layer[0].reader(None) == 42.0
    # The tails are listed for the chat cell alone.
    assert {m.name for m in cell.end_to_end} == {"output_tok_s", "setup_s"}
    assert cell.module("reference", cell.config["reference"]).shape(
        cell.config).n_layers == 3
    # The cells already there are untouched by the additions.
    old = spec.load_cell("tiny_qwen.chat", root)
    assert "queue.depth_max" not in [m.name for m in old.per_layer]


def test_a_suffixed_metric_falls_back_to_its_stem(root):
    r = spec._reader(root, "step_mfu.anything")
    assert r.__module__.endswith("step_mfu")


def test_missing_pieces_are_refused(root):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such.cell", root)
    (root / "bench/traffic/tiny_chat.json").unlink()
    with pytest.raises(spec.SpecError):
        spec.load_cell("tiny_qwen.chat", root)


def _act(cfg):
    cfg["hidden_act"] = "gelu_pytorch_tanh"


def _last_layer_windowed(cfg):
    cfg["layer_types"][-3] = "sliding_attention"


def _experts_on_a_dense_program(cfg):
    cfg.update(num_local_experts=4, num_experts_per_tok=2,
               moe_intermediate_size=64)


def _program_holds_other_experts(cfg):
    cfg["program"]["moe"]["n_experts"] = 8


@pytest.mark.parametrize("config,change", [
    ("tiny_qwen", _act), ("tiny_hybrid", _last_layer_windowed),
    ("tiny_qwen", _experts_on_a_dense_program),
    ("tiny_moe", _program_holds_other_experts)])
def test_a_config_that_disagrees_with_the_program_is_refused(root, config,
                                                             change):
    cfg = json.loads((root / f"bench/configs/{config}.json").read_text())
    change(cfg)
    _add(root, "configs", f"{config}.json", cfg)
    cell = {"tiny_qwen": "tiny_qwen.chat", "tiny_hybrid": "tiny_hybrid.batch",
            "tiny_moe": "tiny_moe.chat"}[config]
    with pytest.raises(spec.SpecError):
        spec.arch_config(spec.load_cell(cell, root))


def test_layer_kinds_and_experts_come_from_the_file(root):
    hybrid = spec.arch_config(spec.load_cell("tiny_hybrid.batch", root))
    assert hybrid.layer_kinds == ((("local", "moe"),) * 3
                                  + (("attn", "moe"),)) * 2 \
        + (("local", "moe"),) * 2
    assert (hybrid.hd, hybrid.window, hybrid.moe.capacity_factor) == \
        (32, 24, 2.0)
    moe = spec.arch_config(spec.load_cell("tiny_moe.chat", root))
    assert (moe.moe.n_experts, moe.moe.top_k, moe.moe.d_ff_expert) == \
        (4, 2, 64)


# What the program's registered config changes to for the repository's
# configuration files, recorded before configurations brought their own
# layers.
RECORDED = {"qwen15_4b": {"rope_theta": 5000000.0, "format_policy": "bf16"},
            "starcoder2_7b": {"n_layers": 16}}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_real_configurations_give_the_recorded_program_config(name):
    from repro.configs import get_config
    config = json.loads((spec.REPO / f"bench/configs/{name}.json").read_text())
    cell = spec.Cell(name=name, config_name=name, chips=1, config=config,
                     traffic={}, sizing={}, end_to_end=[], per_layer=[],
                     root=spec.REPO)
    assert spec.arch_config(cell) == dataclasses.replace(
        get_config(config["program"]["arch"]), **RECORDED[name])


def test_the_benchmark_cells_load(tmp_path):
    """Every cell of the repository's own BENCHMARK.json finds its files,
    and its program config has the file's sizes."""
    bench = json.loads((spec.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        cfg = spec.arch_config(cell)
        assert cfg.d_model == cell.config["hidden_size"]
        assert cell.end_to_end and cell.per_layer
