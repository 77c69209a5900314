"""granite-4.0-h-small's mechanisms at a tiny size on the CPU: Mamba-2 and
NoPE attention layers, each with a held share of routed experts and a
shared one, against the benchmark's plain float32 reference; the
held-share expert layer's routing, counts and shares; the served tree's
widths."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import attention as attn_mod
from repro.models import model as model_lib
from repro.models import moe as moe_mod
from repro.serving.engine import (Request, ServingEngine,
                                  init_serving_params)

REPO = Path(__file__).resolve().parents[1]
TINY = REPO / "bench/tests/data/configs/tiny_granite.json"


def _tiny(seed=2 ** 33 + 11):
    """The ``tiny_granite`` test configuration: the program's config, the
    reference and its shape, and the program's parameters made from the
    seed by the benchmark."""
    from bench import harness, spec
    from bench.reference import granite4_h_small as ref
    config = dict(json.loads(TINY.read_text()), reference="granite4_h_small")
    cell = spec.Cell(name="tiny_granite", config_name="tiny_granite",
                     chips=1, config=config, traffic={}, sizing={},
                     end_to_end=[], per_layer=[], root=REPO)
    cfg = spec.arch_config(cell)
    shape = ref.shape(config)
    return cfg, ref, shape, harness.make_params(cfg, ref, shape, seed), seed


def test_engine_prefill_and_decode_match_the_reference():
    """Two requests served together through ``ServingEngine`` (chunked
    prefill into the paged pool and the per-slot Mamba-2 rows, then
    batched decode): every logit that picks a served token agrees with the
    reference's full forward over the window and the served tokens, in
    float32 (the two differ only in the order of float32 sums: the
    program's chunked dual form against the reference's sequential
    recurrence)."""
    cfg, ref, shape, params, seed = _tiny()
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, prefill_len=32,
                        page_size=8, prefill_chunk=16)
    seen = {}          # rid -> the logits that picked its tokens, in order
    decode_step, chunk_fn = eng._decode_step, eng._chunk_fn

    def recorded_decode(*args):
        out = decode_step(*args)
        logits, active = np.asarray(out[2]), np.asarray(args[5])
        for slot, req in enumerate(eng.slot_req):
            if req is not None and active[slot]:
                seen[req.rid].append(logits[slot])
        return out

    def recorded_chunk(fmt, c):
        fn = chunk_fn(fmt, c)

        def run(p, b, cache):
            out = fn(p, b, cache)
            if c == eng.n_chunks - 1:
                seen[eng.slot_req[int(b["slot"])].rid] = [
                    np.asarray(out[0][0])]
            return out
        return run

    eng._decode_step, eng._chunk_fn = recorded_decode, recorded_chunk
    rng = np.random.default_rng(3)
    prompts = {0: rng.integers(1, cfg.vocab, 29, dtype=np.int32),
               1: rng.integers(1, cfg.vocab, 12, dtype=np.int32)}
    for rid, p in prompts.items():
        eng.submit(Request(rid=rid, prompt=p, max_tokens=9))
    out = eng.run()
    for rid, p in prompts.items():
        served = np.asarray(out[rid], np.int32)
        assert len(served) == 9
        window = np.pad(p, (32 - len(p), 0))
        want = ref.sequence_logits(shape, seed,
                                   [np.concatenate([window, served[:-1]])],
                                   [31 + np.arange(len(served))])[0]
        got = np.stack(seen[rid][:len(served)])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(served, want.argmax(-1))


def test_nope_attention_is_the_same_at_any_start_position():
    cfg, _, _, _, _ = _tiny()
    p = attn_mod.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    at = jnp.arange(12)[None].repeat(2, 0)

    def run(c, start):
        return np.asarray(attn_mod.attention(x, p, c, start + at))

    np.testing.assert_array_equal(run(cfg, 0), run(cfg, 1000))
    roped = dataclasses.replace(cfg, rope=True)
    assert not np.allclose(run(roped, 0), run(roped, 1000))


def _experts(n_experts=4, top_k=2, held=None, shared=0):
    cfg = get_config("granite4_h_small").reduced()
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(
            cfg.moe, n_experts=n_experts, top_k=top_k, capacity_factor=1.25,
            held=held, d_ff_shared=shared))


def _dense_layer(x2, p, cfg, experts):
    """Every row through each expert it picks (ids ``experts``), weighted
    by its renormalised top-k probability."""
    m = cfg.moe
    probs = jax.nn.softmax(x2 @ p["router"], -1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    vals = vals / vals.sum(-1, keepdims=True)
    y = jnp.zeros_like(x2)
    for j, e in enumerate(experts):
        wt = jnp.sum(jnp.where(idx == e, vals, 0.0), -1)
        h = jax.nn.silu(x2 @ p["gate"][j]) * (x2 @ p["up"][j])
        y = y + wt[:, None] * (h @ p["down"][j])
    return y


def test_the_served_layer_drops_no_row_where_capacity_would():
    """Routing forced onto experts 0 and 1 for every row: the held-share
    layer serves every row; the capacity path (1.25 x the even share)
    drops some."""
    cfg = _experts(held=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0) \
        .at[:, 1].set(9.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1),
                                  (1, 32, cfg.d_model)))
    want = _dense_layer(x[0], p, cfg, range(4))
    got, counts = moe_mod.apply_moe_held(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert counts.tolist() == [64, 2]
    dropped, _ = moe_mod.apply_moe(x, p, dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, held=None)))
    assert not np.allclose(np.asarray(dropped[0]), np.asarray(want),
                           atol=1e-3)


def test_counts_match_a_host_recount_of_the_routing():
    cfg = _experts(n_experts=8, top_k=3, held=3)
    p = moe_mod.init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 5, cfg.d_model))
    rows = np.array([True, False, True, False])
    _, counts = moe_mod.apply_moe_held(x, p, cfg, rows)
    logits = np.asarray(x, np.float64).reshape(4, 5, -1) @ np.asarray(
        p["router"], np.float64)
    picks = np.argsort(-logits, axis=-1)[..., :3][rows]      # counted rows
    held = picks[picks < 3]
    assert counts.tolist() == [held.size, len(set(held.tolist()))]


def test_decode_counts_only_the_active_slots():
    """A decode step counts the expert work of its active rows alone: two
    rows with one active count what that row's own step counts."""
    cfg, _, _, params, _ = _tiny()
    cache2 = model_lib.init_paged_cache(cfg, 2, 16, num_pages=5,
                                        page_size=8)
    cache1 = model_lib.init_paged_cache(cfg, 1, 16, num_pages=5,
                                        page_size=8)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

    def step(rows, cache, active):
        batch = {"tokens": jnp.asarray([[7], [9]][:rows], jnp.int32),
                 "pos": jnp.zeros(rows, jnp.int32),
                 "page_table": table[:rows],
                 "row_valid": jnp.asarray(active)}
        return model_lib.decode_and_sample(
            params, batch, cache, cfg, key=jax.random.PRNGKey(0),
            temperatures=jnp.zeros(rows), active_rows=jnp.asarray(active),
            with_counts=True)[-1]

    one = step(1, cache1, [True])
    assert step(2, cache2, [True, False]).tolist() == one.tolist()
    assert one[0] > 0 and one[1] > 0
    assert step(2, cache2, [False, False]).tolist() == [0, 0]


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 devices of 2: each device's held-share layer
    (its experts first in the router's order, as a device holding ids
    2j, 2j + 1 numbers them) gives its part; the parts and the shared
    expert, counted once, add up to the uncut layer."""
    whole = _experts(n_experts=8, top_k=3, held=8, shared=64)
    p = moe_mod.init_moe(jax.random.PRNGKey(4), whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 6, whole.d_model))
    share = dataclasses.replace(
        whole, moe=dataclasses.replace(whole.moe, held=2))
    total = moe_mod.shared_expert(x, p, whole)
    for j in range(4):
        ids = np.array([2 * j, 2 * j + 1])
        order = np.concatenate([ids, np.delete(np.arange(8), ids)])
        part = {"router": p["router"][:, order], "gate": p["gate"][ids],
                "up": p["up"][ids], "down": p["down"][ids]}
        total = total + moe_mod.apply_moe_held(x, part, share)[0]
    uncut = moe_mod.apply_moe_held(x, p, whole)[0] \
        + moe_mod.shared_expert(x, p, whole)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    want = _dense_layer(x.reshape(-1, whole.d_model), p, whole, range(8))
    np.testing.assert_allclose(
        np.asarray(uncut - moe_mod.shared_expert(x, p, whole)).reshape(
            want.shape), np.asarray(want), rtol=1e-5, atol=1e-5)


def _served(cfg):
    return jax.tree_util.tree_leaves_with_path(jax.eval_shape(
        lambda k: init_serving_params(k, cfg), jax.random.PRNGKey(0)))


def test_a_served_bf16_moe_tree_holds_no_large_float32_leaf():
    """At granite-4.0-h-small's published widths with the cut's 9 held
    experts: the expert stacks, the shared expert and the router are bf16;
    what stays float32 (norms, conv, A_log, D, dt_bias) is under 1 MB."""
    cfg = dataclasses.replace(
        get_config("granite4_h_small"), n_layers=20,
        moe=dataclasses.replace(get_config("granite4_h_small").moe, held=9))
    leaves = _served(cfg)
    big = [jax.tree_util.keystr(p) for p, x in leaves
           if x.dtype == jnp.float32 and x.size * 4 > 2 ** 20]
    assert not big, big
    named = {getattr(p[-1], "key", None): x.dtype for p, x in leaves}
    assert all(named[n] == jnp.bfloat16 for n in (
        "router", "gate", "up", "down", "in_proj", "w", "table")
               if n in named)
    assert named["A_log"] == named["conv_w"] == jnp.float32


def test_the_qwen_tree_keeps_its_widths():
    """qwen1.5-4b's served tree narrows exactly its ``w``, ``table`` and
    ``head`` leaves, as before expert stacks were narrowed too."""
    for path, x in _served(get_config("qwen15_4b")):
        narrow = getattr(path[-1], "key", None) in ("w", "table", "head")
        assert x.dtype == (jnp.bfloat16 if narrow else jnp.float32), path
