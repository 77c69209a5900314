"""The plain reference against the program's own prefill-chunk and decode
logits, at reduced width in float32 on the CPU: the two must agree to
rounding, through the paged pool (qwen), the sliding-window rings with
the window biting (starcoder2), sparse experts (a GraniteMoe) and a
hybrid of windowed and global attention layers with experts, whose
window bites too."""
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    from bench.tests.test_run import make_root
    return make_root(tmp_path_factory.mktemp("root"))


@pytest.mark.parametrize("cell", ["tiny_qwen.chat", "tiny_starcoder.batch",
                                  "tiny_moe.chat", "tiny_hybrid.batch"])
def test_reference_matches_prefill_chunks_and_decode(tiny_root, cell):
    from repro.models import model as model_lib
    c = spec.load_cell(cell, tiny_root)
    cfg = spec.arch_config(c)
    ref = c.module("reference", c.config["reference"])
    shape = ref.shape(c.config)
    seed = 2 ** 33 + 7
    params = harness.make_params(cfg, ref, shape, seed)
    chunk, n_chunks, page, n_dec = 8, 3, 8, 16
    total = chunk * n_chunks + n_dec
    maxp = total // page
    cache = model_lib.init_paged_cache(cfg, 1, total, num_pages=maxp + 1,
                                       page_size=page)
    table = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
    toks = np.random.default_rng(0).integers(1, cfg.vocab, total,
                                             dtype=np.int32)
    # Compiled, as the engine runs them.
    prefill_chunk = jax.jit(model_lib.prefill_chunk,
                            static_argnames=("cfg", "pos0"))
    decode = jax.jit(model_lib.decode, static_argnames="cfg")
    got = []
    for i in range(n_chunks):
        batch = {"tokens": jnp.asarray(toks[None, i * chunk:(i + 1) * chunk]),
                 "page_table": table, "slot": jnp.int32(0)}
        lg, cache = prefill_chunk(params, batch, cache, cfg=cfg,
                                  pos0=i * chunk)
        got.append(np.asarray(lg[0]))
    for p in range(chunk * n_chunks, total - 1):
        batch = {"tokens": jnp.asarray(toks[None, p:p + 1]),
                 "pos": jnp.asarray([p], jnp.int32), "page_table": table}
        lg, cache = decode(params, batch, cache, cfg=cfg)
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


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        x = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    # Recorded with the weights of the harness before configurations
    # brought their own layers (one scanned dense layer per group).
    (2 ** 33 + 7,
     "8a6854de5edb1452bf57a244967dc47be9afa1d28a1477c7ccd9a87d3f6fe0d5"),
    (5, "1d5f54d8f4d1d6c9f6e69f3b27b7407be6bb3a1c606c07ece6c6f3bfdd21431a")])
def test_dense_weights_are_the_recorded_ones(tiny_root, seed, digest):
    """A dense decoder's weights from a seed are bit for bit what they
    were: its cells keep reading what they read."""
    c = spec.load_cell("tiny_qwen.chat", tiny_root)
    ref = c.module("reference", c.config["reference"])
    params = harness.make_params(spec.arch_config(c), ref,
                                 ref.shape(c.config), seed)
    assert _digest(params) == digest


def test_a_leaf_the_reference_does_not_make_is_refused(tiny_root):
    """The program holds a q/k norm that the reference has no weight for:
    the harness names it and makes nothing."""
    import dataclasses
    c = spec.load_cell("tiny_qwen.chat", tiny_root)
    ref = c.module("reference", c.config["reference"])
    cfg = dataclasses.replace(spec.arch_config(c), qk_norm=True)
    with pytest.raises(ValueError, match=r"'mixer'\]\['[qk]_norm'\]"):
        harness.make_params(cfg, ref, ref.shape(c.config), 3)
