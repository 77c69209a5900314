"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound, it is correct; with the lower-precision control, or with the
served path broken underneath, ``correct`` comes out false."""
import shutil
import time
from pathlib import Path

import jax
import pytest

from bench import run, spec
from bench.peaks import Peaks

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
CPU = Peaks(bf16_flops=1e12, int8_ops=1e12, hbm_bytes=1e9, hbm_bw=1e11,
            ici_bw=1e10)
SECONDS = 1.5


def make_root(root: Path) -> Path:
    """A checkout-shaped directory with the tiny cells of ``data/`` and
    the benchmark's own readers and references."""
    shutil.copy(DATA / "BENCHMARK.json", root / "BENCHMARK.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DATA / d, root / "bench" / d)
    for d in ("metrics", "reference"):
        shutil.copytree(BENCH / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"))


def _execute(root, cell, seed, **kw):
    c = spec.load_cell(cell, root)
    return run.execute(c, seed, SECONDS, False, CPU, jax.devices(),
                       t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny_qwen.chat", {"output_tok_s", "ttft_p95_s", "itl_p95_ms",
                        "setup_s"}),
    ("tiny_starcoder.batch", {"output_tok_s", "setup_s"})])
def test_sound_run_is_correct_and_control_is_not(root, cell, metrics):
    out = _execute(root, cell, 2 ** 32 + 3, control=True)
    r = out["info"]["readings"]
    limit = out["checks"]["near_tie_gap"]["limit"]
    assert out["correct"] is True
    assert out["info"]["control_correct"] is False
    assert r["near_tie_gap"] <= limit
    assert r["control_near_tie_gap"] > 3 * limit
    assert out["info"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == metrics
    assert list(out)[-2:] == ["checks", "info"]


def test_backlog_cell_reports_its_own_metrics(root):
    out = _execute(root, "tiny_starcoder.batch", 11)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def _token_altered(monkeypatch):
    """A served token altered where it is produced."""
    from repro.models import model as model_lib
    orig = model_lib.sample_token

    def altered(logits, key, temperature):
        tok, finite = orig(logits, key, temperature)
        return (tok + 1) % logits.shape[-1], finite
    monkeypatch.setattr(model_lib, "sample_token", altered)


def _state_unchanged(monkeypatch):
    """A decode step that returns its cache unchanged."""
    from repro.models import model as model_lib
    orig = model_lib.decode

    def stale(params, batch, cache, cfg):
        logits, _ = orig(params, batch, cache, cfg)
        return logits, cache
    monkeypatch.setattr(model_lib, "decode", stale)


def _half_batch(monkeypatch):
    """Half of each decode batch left out: the first half's rows, which
    are filled first, get the logits of the second half's."""
    import jax.numpy as jnp
    from repro.models import model as model_lib
    orig = model_lib.decode

    def half(params, batch, cache, cfg):
        logits, new = orig(params, batch, cache, cfg)
        b = logits.shape[0]
        h = b // 2
        return jnp.concatenate([logits[h:2 * h], logits[h:]]), new
    monkeypatch.setattr(model_lib, "decode", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
@pytest.mark.parametrize("cell", ["tiny_qwen.chat", "tiny_starcoder.batch"])
def test_a_broken_served_path_is_not_correct(root, monkeypatch, fault, cell):
    fault(monkeypatch)
    out = _execute(root, cell, 7)
    assert out["correct"] is False
    assert out["checks"]["near_tie_gap"]["value"] > \
        out["checks"]["near_tie_gap"]["limit"]
