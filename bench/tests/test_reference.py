"""The plain reference against the program's own prefill-chunk and decode
logits, at reduced width in float32 on the CPU: the two must agree to
rounding, through the paged pool (qwen) and the sliding-window rings with
the window biting (starcoder2)."""
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    from bench.tests.test_run import make_root
    return make_root(tmp_path_factory.mktemp("root"))


@pytest.mark.parametrize("cell", ["tiny_qwen.chat", "tiny_starcoder.batch"])
def test_reference_matches_prefill_chunks_and_decode(tiny_root, cell):
    from repro.models import model as model_lib
    c = spec.load_cell(cell, tiny_root)
    cfg = spec.arch_config(c)
    ref = c.module("reference", c.config["reference"])
    shape = ref.shape(c.config)
    seed = 2 ** 33 + 7
    params = harness.make_params(cfg, shape, seed)
    chunk, n_chunks, page, n_dec = 8, 3, 8, 16
    total = chunk * n_chunks + n_dec
    maxp = total // page
    cache = model_lib.init_paged_cache(cfg, 1, total, num_pages=maxp + 1,
                                       page_size=page)
    table = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
    toks = np.random.default_rng(0).integers(1, cfg.vocab, total,
                                             dtype=np.int32)
    got = []
    for i in range(n_chunks):
        batch = {"tokens": jnp.asarray(toks[None, i * chunk:(i + 1) * chunk]),
                 "page_table": table, "slot": jnp.int32(0)}
        lg, cache = model_lib.prefill_chunk(params, batch, cache, cfg,
                                            pos0=i * chunk)
        got.append(np.asarray(lg[0]))
    for p in range(chunk * n_chunks, total - 1):
        batch = {"tokens": jnp.asarray(toks[None, p:p + 1]),
                 "pos": jnp.asarray([p], jnp.int32), "page_table": table}
        lg, cache = model_lib.decode(params, batch, cache, cfg)
        got.append(np.asarray(lg[0]))
    positions = np.concatenate([
        np.arange(1, n_chunks + 1) * chunk - 1,
        np.arange(chunk * n_chunks, total - 1)])
    want = ref.sequence_logits(shape, seed, [toks], [positions])[0]
    got = np.stack(got)
    assert got.shape == want.shape
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    assert (got.argmax(-1) == want.argmax(-1)).all()
