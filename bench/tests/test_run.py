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


def register_hybrid():
    """The program's config of the ``tiny_hybrid`` cell, registered as the
    program registers its own: (local, moe) x 3 + (attn, moe)."""
    from repro.configs.base import ArchConfig, MoEConfig, register
    register(ArchConfig(
        name="bench_tiny_hybrid", n_layers=10, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=128, vocab=512,
        pattern=(("local", "moe"),) * 3 + (("attn", "moe"),), window=24,
        mlp_type="swiglu", norm_type="rmsnorm",
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=64)))


def make_root(root: Path) -> Path:
    """A checkout-shaped directory with the tiny cells of ``data/`` (their
    configurations, mixes, sizings and the references of their own) and
    the benchmark's own readers and references."""
    register_hybrid()
    shutil.copy(DATA / "BENCHMARK.json", root / "BENCHMARK.json")
    for d in ("configs", "traffic", "workloads"):
        shutil.copytree(DATA / d, root / "bench" / d)
    for d in ("metrics", "reference"):
        shutil.copytree(BENCH / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(DATA / "reference", root / "bench" / "reference",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"))


def _execute(root, cell, seed, **kw):
    c = spec.load_cell(cell, root)
    return run.execute(c, seed, SECONDS, False, CPU, jax.devices(),
                       t_start=time.perf_counter(), **kw)


CHAT = {"output_tok_s", "ttft_p95_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny_qwen.chat", CHAT),
    ("tiny_starcoder.batch", {"output_tok_s", "setup_s"}),
    ("tiny_moe.chat", CHAT),
    ("tiny_hybrid.batch", {"output_tok_s", "setup_s"})])
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


def _top_k_less_one(monkeypatch):
    """Experts routed top-(k - 1): the last of a token's experts dropped
    and the rest renormalised."""
    from repro.models import moe
    orig = moe._route

    def fewer(x2, router_w, cfg):
        vals, idx, aux = orig(x2, router_w, cfg)
        vals = vals.at[:, -1].set(0.0)
        return vals / vals.sum(-1, keepdims=True), idx, aux
    monkeypatch.setattr(moe, "_route", fewer)


def _tokens_dropped(monkeypatch):
    """A capacity that drops tokens: each expert takes at most 8
    assignments of a call."""
    from repro.models import moe
    monkeypatch.setattr(moe, "moe_capacity", lambda n_tokens, cfg: 8)


@pytest.mark.parametrize("fault", [_top_k_less_one, _tokens_dropped])
@pytest.mark.parametrize("cell", ["tiny_moe.chat", "tiny_hybrid.batch"])
def test_a_broken_expert_path_is_not_correct(root, monkeypatch, fault, cell):
    fault(monkeypatch)
    out = _execute(root, cell, 13)
    assert out["correct"] is False
    assert out["checks"]["near_tie_gap"]["value"] > \
        out["checks"]["near_tie_gap"]["limit"]
