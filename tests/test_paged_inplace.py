"""The serving cache is updated where it lies.

The layer scan carries every stacked leaf of the group cache: each paged
layer scatters into / gathers from its own index of the pool, and each
layer with per-slot state (rings, RG-LRU and Mamba-2 state) reads and
writes its own index, one slot's in a prefill chunk; the decode and
prefill-chunk programs take the cache donated.  These tests hold that
formulation to the one it replaced — every cache leaf in the scan's
``xs``/``ys``, one layer slab sliced out and written back per layer —
bit for bit, hold the per-slot state to its slot discipline, and guard
the mechanism in the compiled programs.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.serving import ServingEngine

SLOTS, PAGE, MAXP = 3, 8, 4
NUM_PAGES = SLOTS * MAXP + 1


def _xs_ys_run_stack(x, params, cfg, positions, mode, cache=None, pos=None,
                     cache_len=None, page_table=None, slot=None,
                     chunk_pos0=None, row_valid=None):
    """The serving modes' layer stack with the pool in ``xs``/``ys``."""
    n_groups, n_tail = model_lib._group_layout(cfg)
    kinds = cfg.layer_kinds
    kw = dict(pos=pos, page_table=page_table, slot=slot,
              chunk_pos0=chunk_pos0, row_valid=row_valid)

    def body(carry, xs):
        xc, auxc = carry
        gp, gc = xs
        outs = []
        for j in range(cfg.period):
            xc, c_new, aux = model_lib._apply_layer(
                xc, model_lib._index_tree(gp, j), cfg, kinds[j], positions,
                mode, cache=gc[j], **kw)
            outs.append(c_new)
            auxc = auxc + aux
        return (xc, auxc), tuple(outs)

    (x, aux), ys = jax.lax.scan(body, (x, model_lib._zero_aux(cfg)),
                                (params["groups"], cache["groups"]))
    tail = []
    for j in range(n_tail):
        x, c_new, _ = model_lib._apply_layer(
            x, params["tail"][j], cfg, kinds[n_groups * cfg.period + j],
            positions, mode, cache=cache["tail"][j], **kw)
        tail.append(c_new)
    return x, {"groups": ys, "tail": tail}, aux


def _cfg(arch, kv_format):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              compute_dtype="bfloat16")
    return dataclasses.replace(cfg, kv_cache_format=kv_format)


def _filled_cache(cfg, key):
    """A paged cache whose every leaf holds seeded values, so reads and
    writes both show."""
    cache = model_lib.init_paged_cache(cfg, SLOTS, PAGE * MAXP,
                                       num_pages=NUM_PAGES, page_size=PAGE)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128, jnp.int8))
        elif leaf.shape[-1] == 1:   # f32 scale pages
            out.append(jax.random.uniform(k, leaf.shape, leaf.dtype,
                                          1e-3, 2e-2))
        else:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree.unflatten(tree, out)


def _batch(cfg, mode, key):
    # Slots 0 and 1 are live on distinct pages; slot 2 is inactive: its
    # all-unmapped table row sends its writes to the null page 0.
    table = np.full((SLOTS, MAXP), -1, np.int32)
    table[0] = [3, 7, 1, 9]
    table[1] = [2, 12, 5, 4]
    if mode.startswith("prefill_chunk"):
        size = PAGE if mode == "prefill_chunk" else PAGE - 2
        return {"tokens": jax.random.randint(key, (1, size), 0, cfg.vocab),
                "page_table": jnp.asarray(table[1:2]),
                "slot": jnp.int32(1)}
    width = 3 if mode == "verify" else 1
    return {"tokens": jax.random.randint(key, (SLOTS, width), 0, cfg.vocab),
            "pos": jnp.asarray([13, 22, 0], jnp.int32),
            "page_table": jnp.asarray(table),
            "row_valid": jnp.asarray([True, True, False])}


def _program(cfg, mode):
    if mode == "decode":
        return lambda p, b, c: model_lib.decode(p, b, c, cfg)
    if mode == "verify":
        return lambda p, b, c: model_lib.verify_chunk(p, b, c, cfg)
    # A chunk that fills whole pages, or one that starts and ends inside
    # pages.
    pos0 = PAGE if mode == "prefill_chunk" else PAGE + 2
    return lambda p, b, c: model_lib.prefill_chunk(p, b, c, cfg, pos0=pos0)


@pytest.mark.parametrize("mode", ["decode", "prefill_chunk",
                                  "prefill_chunk_ragged", "verify"])
@pytest.mark.parametrize("kv_format", [None, "int8pt"])
@pytest.mark.parametrize("arch", ["qwen15_4b", "gemma2_27b"])
def test_carried_pool_matches_xs_ys_stack(arch, kv_format, mode,
                                          monkeypatch):
    """One decode step, one prefill chunk (of whole pages, or ragged) and
    one verify window give the same logits and the same cache leaves, bit
    for bit, with the pool in the scan carry as with the pool in
    ``xs``/``ys`` — on a period-1 all-global stack and a period-2
    local/global one, bf16 and int8pt pages, with an inactive slot
    writing into the null page."""
    cfg = _cfg(arch, kv_format)
    assert cfg.period == (2 if arch == "gemma2_27b" else 1)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    cache = _filled_cache(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg, mode, jax.random.PRNGKey(2))

    logits, new = jax.jit(_program(cfg, mode))(params, batch, cache)
    monkeypatch.setattr(model_lib, "_run_stack", _xs_ys_run_stack)
    ref_logits, ref = jax.jit(_program(cfg, mode))(params, batch, cache)

    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    paged = [j for j, c in enumerate(cache["groups"])
             if model_lib._is_paged(c)]
    assert len(paged) == 1
    for j in paged:
        old_k = np.asarray(cache["groups"][j]["k_pages"])
        new_k = np.asarray(new["groups"][j]["k_pages"])
        assert not np.array_equal(old_k, new_k)     # the step wrote
        if mode == "decode":
            # the inactive slot's token lands in null page 0, slot 0
            assert not np.array_equal(old_k[:, 0, 0], new_k[:, 0, 0])


@pytest.mark.parametrize("mode", ["decode", "prefill_chunk",
                                  "prefill_chunk_fresh", "verify"])
@pytest.mark.parametrize("arch", ["recurrentgemma_9b", "granite4_h_small",
                                  "mamba2_130m"])
def test_carried_state_matches_xs_ys_stack(arch, mode, monkeypatch):
    """Layers with per-slot state — RG-LRU and a sliding-window ring in
    groups of three plus a two-layer unrolled tail, Mamba-2 around a paged
    NoPE attention layer with held experts, Mamba-2 alone — give the same
    logits and cache leaves, bit for bit, with the state in the scan carry
    as with the state in ``xs``/``ys``, in one decode step, one prefill
    chunk that resumes a slot or starts it, and one verify window.  A
    chunk on slot 1 writes that slot's state and leaves every other
    slot's state and conv history as they were; a decode or verify step
    writes the valid rows' and leaves the invalid row's."""
    cfg = get_config(arch).reduced()
    if arch == "recurrentgemma_9b":
        cfg = dataclasses.replace(cfg, n_layers=8)
    assert model_lib._group_layout(cfg)[1] == (2 if arch ==
                                               "recurrentgemma_9b" else 0)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    cache = _filled_cache(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg, mode.removesuffix("_fresh"), jax.random.PRNGKey(2))

    def program():      # a new function each time, so each run traces anew
        if mode == "prefill_chunk_fresh":
            return lambda p, b, c: model_lib.prefill_chunk(p, b, c, cfg,
                                                           pos0=0)
        return _program(cfg, mode)

    logits, new = jax.jit(program())(params, batch, cache)
    monkeypatch.setattr(model_lib, "_run_stack", _xs_ys_run_stack)
    ref_logits, ref = jax.jit(program())(params, batch, cache)

    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # (old, new) of every per-slot state leaf with its slot axis first
    state = [(o, n) for part, axis in (("groups", 1), ("tail", 0))
             for oc, nc in zip(cache[part] or (), new[part] or ())
             if not model_lib._is_paged(oc)
             for o, n in zip(jax.tree.leaves(oc), jax.tree.leaves(nc))
             for o, n in [(np.moveaxis(np.asarray(o), axis, 0),
                           np.moveaxis(np.asarray(n), axis, 0))]]
    assert state
    written = [1] if mode.startswith("prefill_chunk") else [0, 1]
    for old, upd in state:
        for row in range(SLOTS):
            same = np.array_equal(old[row], upd[row])
            assert same == (row not in written), (row, old.shape)


# -- the compiled programs ------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = _cfg("qwen15_4b", None)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, slots=SLOTS, cache_len=PAGE * MAXP,
                         prefill_len=2 * PAGE, prefill_chunk=PAGE,
                         page_size=PAGE, num_pages=NUM_PAGES)


def _lowered(eng):
    slots, maxp = eng.slots, eng.sched.max_pages_per_seq
    dec_batch = eng._make_batch(np.zeros((slots, 1), np.int32),
                                pos=np.zeros(slots, np.int32),
                                table=np.full((slots, maxp), -1, np.int32))
    chunk_batch = eng._make_batch(np.zeros((1, eng.prefill_chunk), np.int32),
                                  table=np.full((1, maxp), -1, np.int32),
                                  slot=0)
    return {
        "decode": eng._decode_step.lower(
            eng.params, dec_batch, eng.cache, eng._zero_key,
            jnp.zeros(slots, jnp.float32), jnp.zeros(slots, bool)),
        "chunk": eng._chunk_fn(None, 1).lower(eng.params, chunk_batch,
                                              eng.cache),
    }


def _shape_re(shape):
    return "x".join(map(str, shape))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_programs_update_the_pool_in_place(engine, program):
    """The compiled decode and chunk programs alias every pool parameter
    to an output (the pool is donated), and no program asks for a
    dynamic slice or update of a whole layer slab (num_pages, page, kv,
    hd): the layer scan never slices a slab out nor writes one back."""
    lowered = _lowered(engine)[program]
    pool = [leaf for c in engine.cache["groups"] if model_lib._is_paged(c)
            for leaf in c.values()]
    assert pool
    stacked = pool[0].shape
    slab = stacked[1:]

    # 1. every stacked pool parameter is aliased to an output
    hlo = lowered.compile().as_text()
    header = hlo.splitlines()[0]
    aliased = {int(m) for m in re.findall(r"\(\s*(\d+),\s*\{\}", header)}
    entry = hlo[hlo.index("\nENTRY"):]
    pool_params = {int(m) for m in re.findall(
        r"= \w+\[" + ",".join(map(str, stacked)) +
        r"\]\{[^}]*\} parameter\((\d+)\)", entry)}
    assert len(pool_params) == len(pool), (pool_params, header[:300])
    assert pool_params <= aliased, (pool_params, header[:300])

    # 2. no dynamic slice or update of a whole layer slab
    text = lowered.as_text()
    slab_t = _shape_re(slab)
    bad = [line.strip()[:200] for line in text.splitlines()
           if ("dynamic_slice" in line or "dynamic_update_slice" in line)
           and re.search(r"tensor<(1x)?" + slab_t + r"x", line)]
    assert not bad, bad
