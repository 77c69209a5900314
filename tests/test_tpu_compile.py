"""Main-path kernels compiled for a described TPU v5e at gemma-2b widths.

Nothing runs: each test lowers one kernel with ``interpret=False`` for a
chip that is described, not attached, and checks that the TPU compiler
accepts it and that a Mosaic kernel (``tpu_custom_call``) is in the
program.  This is what the Pallas interpreter cannot show: misaligned
blocks, unsupported layouts and VMEM overruns are refused here as they
would be on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  JAX's persistent compilation cache is off around
these compiles, since a program compiled for a described chip is written
to it but cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import autotune

# gemma-2b (configs/gemma_2b.py) served with the launcher's defaults.
D_MODEL, D_FF, N_HEADS, HEAD_DIM = 2048, 16384, 8, 256
SLOTS, CHUNK, PAGE, CACHE_LEN = 4, 128, 16, 512
RGLRU_WIDTH = 4096                      # recurrentgemma-9b's RG-LRU width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,n,k,route", [
    (CHUNK, D_FF, D_MODEL, "mte"),      # prefill-chunk up/gate projection
    (SLOTS, D_FF, D_MODEL, "mte"),      # decode up/gate projection
    (SLOTS, D_MODEL, D_FF, "splitk"),   # decode down projection, M=4
])
def test_planned_gemm_compiles(one_chip, m, n, k, route):
    """bf16 operands, f32 accumulation, bf16 out — the signature a
    ``dense`` layer under the bf16 format policy asks the plan cache for."""
    plan = autotune.get_plan(m, n, k, jnp.bfloat16, jnp.bfloat16,
                             fmt="bf16")
    assert plan.route == route, plan.describe()
    text = _compiled_text(
        lambda a, b: autotune.execute_plan(plan, a, b, interpret=False),
        one_chip, ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
def test_splitk_masked_tail_compiles(one_chip, dtype):
    """Three K slices of the decode down projection leave a ragged last
    slice, so the kernel masks its K tail on a packed 4-row block."""
    from repro.core.geometry import solve_block_geometry
    from repro.core.tile_state import SEW
    from repro.kernels.splitk_gemm import mte_gemm_splitk_pallas
    geom = solve_block_geometry(SLOTS, D_MODEL, D_FF, SEW.from_dtype(dtype),
                                SEW.E32)
    text = _compiled_text(
        lambda a, b: mte_gemm_splitk_pallas(a, b, geom=geom, n_split=3,
                                            interpret=False),
        one_chip, ((SLOTS, D_FF), dtype), ((D_FF, D_MODEL), dtype))
    assert "tpu_custom_call" in text


def test_grouped_decode_qkv_compiles(one_chip):
    """The decode step's q/k/v GEMVs as one grouped launch over the
    stacked (3, D, Nmax) weight."""
    plan = autotune.get_plan(SLOTS, N_HEADS * HEAD_DIM, D_MODEL,
                             jnp.bfloat16, jnp.bfloat16, group=3, fmt="bf16")
    assert plan.route == "grouped", plan.describe()
    text = _compiled_text(
        lambda x, w: autotune.execute_plan(plan, x, w, interpret=False),
        one_chip, ((3, SLOTS, D_MODEL), jnp.bfloat16),
        ((3, D_MODEL, N_HEADS * HEAD_DIM), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_flash_decode_paged_compiles(one_chip, kv_dtype):
    """Paged decode attention over 16-token pages (MQA, head_dim 256);
    int8 pages carry per-slot scales dequantized in the kernel."""
    from repro.kernels.flash_decode import flash_decode_paged_pallas
    max_pages = CACHE_LEN // PAGE
    n_pages = SLOTS * max_pages + 1
    pages = ((n_pages, PAGE, 1, HEAD_DIM), kv_dtype)
    shapes = [((SLOTS, N_HEADS, HEAD_DIM), jnp.bfloat16), pages, pages,
              ((SLOTS, max_pages), jnp.int32), ((SLOTS,), jnp.int32)]
    if kv_dtype == jnp.int8:
        shapes += [((n_pages, PAGE, 1, 1), jnp.float32)] * 2
    text = _compiled_text(
        lambda *a: flash_decode_paged_pallas(*a, interpret=False),
        one_chip, *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv_len", [CHUNK, 2 * CHUNK])
def test_flash_attention_compiles(one_chip, kv_len):
    """Prefill-chunk attention: 128 queries against the first and the
    second chunk's keys."""
    from repro.kernels.flash_attention import flash_attention_pallas
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               interpret=False),
        one_chip, ((1, N_HEADS, CHUNK, HEAD_DIM), jnp.bfloat16),
        ((1, 1, kv_len, HEAD_DIM), jnp.bfloat16),
        ((1, 1, kv_len, HEAD_DIM), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_rglru_scan_compiles(one_chip):
    from repro.kernels.rglru_scan import rglru_scan_pallas
    text = _compiled_text(
        lambda a, b: rglru_scan_pallas(a, b, interpret=False),
        one_chip, ((1, CHUNK, RGLRU_WIDTH), jnp.float32),
        ((1, CHUNK, RGLRU_WIDTH), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cache_len", [CACHE_LEN, 4096])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_serving_programs_keep_the_pool_in_place(one_chip, program,
                                                 cache_len):
    """qwen1.5-4b's decode and prefill-chunk programs at its published
    widths (two of its 40 layers: every layer runs the same scan body),
    sized as its benchmark cells serve it, and at a cache of 4096, whose
    spans are read by the KV-chunked scan: the donated pool is aliased to
    the output, no operation yields one layer's whole (pages, page, kv,
    hd) slab, and none copies or transposes the stacked pool.  The TPU
    keeps a pool of 20 KV heads with its kv axis between the page and
    slot axes, so a KV write or read that asks for another layout shows
    here as a whole-pool copy, which no CPU compile shows."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import init_serving_params

    cfg = dataclasses.replace(get_config("qwen15_4b"), n_layers=2,
                              format_policy="bf16")
    slots, maxp = 16, cache_len // PAGE
    num_pages = slots * maxp + 1

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(lambda k: init_serving_params(k, cfg),
                                   jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, slots, cache_len, num_pages=num_pages, page_size=PAGE)))
    rows = slots if program == "decode" else 1
    batch = shaped({"tokens": jnp.zeros((rows, 1 if rows > 1 else CHUNK),
                                        jnp.int32),
                    "page_table": jnp.zeros((rows, maxp), jnp.int32)})
    if program == "decode":
        batch["pos"] = shaped(jnp.zeros(slots, jnp.int32))
        extra = shaped((jax.random.PRNGKey(0), jnp.zeros(slots, jnp.float32),
                        jnp.zeros(slots, bool)))

        def step(p, b, c, key, temps, active):
            return model_lib.decode_and_sample(
                p, b, c, cfg, key=key, temperatures=temps,
                active_rows=active)
    else:
        batch["slot"] = shaped(jnp.int32(0))
        extra = ()

        def step(p, b, c):
            return model_lib.prefill_chunk(p, b, c, cfg,
                                           pos0=cache_len - CHUNK)

    text = jax.jit(step, donate_argnums=(2,)).lower(
        params, batch, cache, *extra).compile().as_text()

    slab = f"{num_pages},{PAGE},{cfg.n_kv_heads},{cfg.hd}"
    stacked = f"{cfg.n_layers},{slab}"
    entry = text[text.index("\nENTRY"):]
    pool_params = set(re.findall(
        r"= bf16\[" + stacked + r"\]\{[^}]*\} parameter\((\d+)\)", entry))
    aliased = set(re.findall(r"\(\s*(\d+),\s*\{\}", text.splitlines()[0]))
    assert len(pool_params) == 2 and pool_params <= aliased, aliased
    per_layer = re.findall(r"%(\S+) = bf16\[(?:1,)?" + slab + r"\]", text)
    assert not per_layer, per_layer
    moves = re.findall(r"%(\S+) = bf16\[" + stacked +
                       r"\]\{[^}]*\} (?:copy|copy-start|transpose)\(", text)
    assert not moves, moves


GRANITE_SLOTS, GRANITE_CACHE, GRANITE_CHUNK = 32, 1280, 256


@pytest.fixture(scope="module")
def granite_programs(one_chip):
    """granite-4.0-h-small's serving programs as its benchmark cell serves
    them, compiled once per module: 20 layers at published widths, 9 of 72
    experts held, 32 slots of 1280 positions, chunks of 256.  ``decode``
    is the decode step; ``chunk`` and ``fresh_chunk`` are prefill chunks
    that resume a slot's state (at 768) and start it (at 0)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import init_serving_params

    base = get_config("granite4_h_small")
    cfg = dataclasses.replace(base, n_layers=20, moe=dataclasses.replace(
        base.moe, held=9))
    slots, cache_len, chunk = GRANITE_SLOTS, GRANITE_CACHE, GRANITE_CHUNK
    maxp = cache_len // PAGE

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(lambda k: init_serving_params(k, cfg),
                                   jax.random.PRNGKey(0)))
    cache = shaped(jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, slots, cache_len, num_pages=slots * maxp + 1, page_size=PAGE)))
    compiled = {}

    def get(program):
        if program in compiled:
            return compiled[program]
        if program == "decode":
            batch = shaped({"tokens": jnp.zeros((slots, 1), jnp.int32),
                            "page_table": jnp.zeros((slots, maxp), jnp.int32),
                            "pos": jnp.zeros(slots, jnp.int32),
                            "row_valid": jnp.zeros(slots, bool)})
            extra = shaped((jax.random.PRNGKey(0),
                            jnp.zeros(slots, jnp.float32),
                            jnp.zeros(slots, bool)))

            def step(p, b, c, key, temps, active):
                return model_lib.decode_and_sample(
                    p, b, c, cfg, key=key, temperatures=temps,
                    active_rows=active, with_counts=True)
        else:
            batch = shaped({"tokens": jnp.zeros((1, chunk), jnp.int32),
                            "page_table": jnp.zeros((1, maxp), jnp.int32),
                            "slot": jnp.int32(0)})
            extra = ()
            pos0 = 0 if program == "fresh_chunk" else cache_len - 2 * chunk

            def step(p, b, c):
                return model_lib.prefill_chunk(p, b, c, cfg, pos0=pos0,
                                               with_counts=True)

        lowered = jax.jit(step, donate_argnums=(2,)).lower(
            params, batch, cache, *extra)
        exe = lowered.compile()
        compiled[program] = (lowered, exe.memory_analysis(), exe.as_text())
        return compiled[program]

    return get


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_granite_serving_programs_fit_one_chip(granite_programs, program):
    """granite-4.0-h-small's decode and last prefill-chunk programs as its
    benchmark cell serves them (:func:`granite_programs`).  The weights,
    the pool and the per-slot Mamba-2 state (8.84e9 + 2.79e9 bytes) and
    the program's temporaries fit one v5e's 16 GiB, the donated cache is
    aliased to the output, and the expert counts are outputs."""
    lowered, mem, _ = granite_programs(program)
    assert lowered.out_info[-1].shape == (2,)
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 0.9 * 2 ** 34, held
    assert mem.alias_size_in_bytes >= 2.79e9


def _unfused_lines(text):
    """The instructions of an HLO module outside fused computations: the
    ones that yield buffers (a fused computation's values never leave
    the fusion)."""
    import re
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    lines, keep = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            keep = head.group(1) not in called
        elif keep and line.startswith("  "):
            lines.append(line)
    return lines


@pytest.mark.parametrize("program", ["decode", "fresh_chunk", "chunk"])
def test_granite_programs_update_the_state_in_place(granite_programs,
                                                    program):
    """The per-slot Mamba-2 state rides in the layer scan's carry: in the
    decode step and in a prefill chunk that starts a slot (at 0) or
    resumes one (at 768), no operation yields one layer's state slab
    (32, 128, 64, 128) of every slot, none copies, transposes or
    allocates a stacked (2, 32, 128, 64, 128) state leaf (the program
    reads and writes each where it lies), every stacked state leaf is
    aliased to its output, and the temporaries stay under 1e9 bytes,
    where one copy of the state is 2.4e9."""
    import re

    _, mem, text = granite_programs(program)
    state = "128,64,128"
    stacked = f"2,{GRANITE_SLOTS},{state}"
    slab = re.compile(r"= f32\[(?:1,)?" + f"{GRANITE_SLOTS},{state}" +
                      r"\]\{")
    per_layer = [line.strip()[:160] for line in _unfused_lines(text)
                 if slab.search(line)]
    assert not per_layer, per_layer
    moves = re.findall(r"%(\S+) = f32\[" + stacked + r"\]\{[^}]*\} "
                       r"(?:copy|copy-start|transpose)\(", text)
    moves += re.findall(r"%(\S+) = f32\[" + stacked + r"\]\{[^}]*\} "
                        r"custom-call\([^)]*\), custom_call_target="
                        r"\"AllocateBuffer\"", text)
    assert not moves, moves
    entry = text[text.index("\nENTRY"):]
    leaves = set(re.findall(r"= f32\[" + stacked +
                            r"\]\{[^}]*\} parameter\((\d+)\)", entry))
    aliased = set(re.findall(r"\(\s*(\d+),\s*\{\}", text.splitlines()[0]))
    assert len(leaves) == 9 and leaves <= aliased, (leaves, aliased)
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes
