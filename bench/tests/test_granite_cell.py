"""The ``tiny_granite`` cell: granite-4.0-h's Mamba-2 and NoPE attention
layers with a held share of the experts and a shared one, run whole on the
CPU. Sound, the program serves what the float32 reference computes; the
bfloat16 control, or the served path broken underneath, is not correct."""
import json
import time

import jax
import pytest

from bench import run, spec, stats
from bench.tests.test_run import CPU, SECONDS, make_root

CELL = "tiny_granite.batch"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("root"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny_granite", "file": "bench/configs/tiny_granite.json",
        "source": "https://huggingface.co/ibm-granite/granite-4.0-h-small",
        "reduced": [], "why": "CPU test size: Mamba-2, NoPE, held experts"})
    bench["workloads"].append({"name": CELL, "config": "tiny_granite",
                               "traffic": "tiny_batch", "chips": 1,
                               "why": "CPU test"})
    bench["per_layer"].append({
        "name": "moe.rows_per_expert.serve", "unit": "rows",
        "better": "higher", "source": "program_counter",
        "layer": "sparse experts", "moves": "output_tok_s",
        "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _execute(root, seed, **kw):
    return run.execute(spec.load_cell(CELL, root), seed, SECONDS, False,
                       CPU, jax.devices(), t_start=time.perf_counter(), **kw)


def test_sound_run_is_correct_and_control_is_not(root):
    out = _execute(root, 2 ** 32 + 5, control=True)
    r = out["info"]["readings"]
    limit = out["checks"]["near_tie_gap"]["limit"]
    assert out["correct"] is True
    assert out["info"]["control_correct"] is False
    assert r["near_tie_gap"] <= limit
    assert r["control_near_tie_gap"] > 3 * limit
    assert out["failed"] == 0
    assert out["info"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    counts = out["info"]["counters"]
    assert counts["moe_experts_read"] > 0
    assert counts["moe_assignments"] >= counts["moe_experts_read"]


def test_rows_per_expert_reads_the_engine_counters(root):
    cell = spec.load_cell(CELL, root)
    (metric,) = [m for m in cell.per_layer
                 if m.name == "moe.rows_per_expert.serve"]

    class Window:
        counters_open = {"moe_assignments": 100, "moe_experts_read": 10}
        counters_close = {"moe_assignments": 460, "moe_experts_read": 30}

    assert metric.reader(stats.Run(Window, None, None, {}, 0.0)) == 18.0
    Window.counters_close = Window.counters_open = {}
    assert metric.reader(stats.Run(Window, None, None, {}, 0.0)) is None


def _shared_expert_left_out(monkeypatch):
    """The shared expert's output dropped."""
    import jax.numpy as jnp
    from repro.models import moe
    monkeypatch.setattr(moe, "shared_expert",
                        lambda x, p, cfg: jnp.zeros_like(x))


def _rope_on(monkeypatch):
    """Rotary embeddings applied to the NoPE attention layers."""
    import dataclasses
    orig = spec.arch_config
    monkeypatch.setattr(spec, "arch_config", lambda cell: dataclasses.replace(
        orig(cell), rope=True))


def _ssd_state_unchanged(monkeypatch):
    """A decode step that leaves the Mamba-2 state where it was."""
    from repro.models import ssm
    orig = ssm.ssd_decode

    def stale(x, p, cfg, cache):
        out, _ = orig(x, p, cfg, cache)
        return out, cache
    monkeypatch.setattr(ssm, "ssd_decode", stale)


@pytest.mark.parametrize("fault", [_shared_expert_left_out, _rope_on,
                                   _ssd_state_unchanged])
def test_a_broken_served_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    out = _execute(root, 17)
    assert out["correct"] is False
    assert out["checks"]["near_tie_gap"]["value"] > \
        out["checks"]["near_tie_gap"]["limit"]
