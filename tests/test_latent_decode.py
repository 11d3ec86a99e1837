"""The latent-attention decode kernel (``kernels/latent_decode.py``) in
interpret mode against the einsums ``mla_decode`` runs off the TPU: per-row
positions on and around block edges, free rows at position 0, a stacked
cache read at its first and last layer, the published widths, and
``mla_decode`` and a whole decode step through the kernel on the reduced
DeepSeek-V3 block of ``test_moonlight.py``."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import latent_decode as kernel
from repro.models import mla
from repro.models.model import Model
from test_moonlight import small_cfg

BLK = 8
CAP = 32
# per-row positions: a free row, one row, a block less one, a block, a
# block and one, the last row; then two more free rows
POS = [0, 1, BLK - 1, BLK, BLK + 1, CAP - 1, 0, 0]
# bf16 operands and p, f32 accumulation on both sides; the kernel rounds p
# against a running max, the einsums against the row's max
BF16 = dict(rtol=2e-2, atol=2e-2)
F32 = dict(rtol=1e-5, atol=1e-5)


def _blocks_of(monkeypatch, rows, width, itemsize):
    """The kernel's copies cut to ``rows`` cache rows of ``width``."""
    monkeypatch.setattr(kernel, "_BLOCK_BYTES", rows * width * itemsize)


def _use_kernel(monkeypatch):
    """``mla_decode`` through the kernel, interpreted, as on a TPU."""
    monkeypatch.setattr(kernel, "_on_tpu", lambda: True)
    monkeypatch.setattr(kernel, "latent_decode", functools.partial(
        kernel.latent_decode, interpret=True))


def _reference(qc, cache, pos, r):
    """Normalised latent [B, H, r] over each row's rows below ``pos``, by
    the einsums over all rows; a row at 0 reads nothing (zeros)."""
    s = jnp.einsum("bhc,btc->bht", qc, cache, preferred_element_type=jnp.float32)
    seen = jnp.arange(cache.shape[1])[None, :] < pos[:, None]
    s = jnp.where(seen[:, None], s, kernel.NEG_INF)
    p = jnp.where(seen[:, None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    o = jnp.einsum("bht,btc->bhc", p.astype(cache.dtype), cache[..., :r],
                   preferred_element_type=jnp.float32)
    return o / jnp.maximum(p.sum(-1), 1e-30)[..., None]


@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_matches_einsums(layer, monkeypatch):
    """Each row reads exactly its rows below ``pos`` of the layer's cache in
    a stack of 3; free rows come out empty (max NEG_INF, sum 0)."""
    rng = np.random.default_rng(layer)
    h, c, r = 4, 48, 32
    _blocks_of(monkeypatch, BLK, c, 2)
    assert kernel.block_rows(CAP, c, 2) == BLK
    stack = jnp.asarray(rng.standard_normal((3, len(POS), CAP, c)), jnp.bfloat16)
    qc = jnp.asarray(rng.standard_normal((len(POS), h, c)), jnp.bfloat16)
    pos = jnp.asarray(POS, jnp.int32)
    acc, top, total = kernel.latent_decode(qc, stack, pos, layer, r,
                                           interpret=True)
    assert acc.shape == (len(POS), h, r) and top.shape == total.shape == (len(POS), h)
    want = _reference(qc, stack[layer], pos, r)
    got = acc / jnp.maximum(total, 1e-30)[..., None]
    np.testing.assert_allclose(got, want, **BF16)
    free = np.asarray(POS) == 0
    assert not np.asarray(acc)[free].any() and not np.asarray(total)[free].any()
    assert (np.asarray(top)[free] == kernel.NEG_INF).all()
    # the max is the row's own largest score
    s = jnp.einsum("bhc,btc->bht", qc, stack[layer],
                   preferred_element_type=jnp.float32)
    for b in np.flatnonzero(~free):
        np.testing.assert_allclose(top[b], s[b, :, :POS[b]].max(-1), rtol=1e-6)


def test_kernel_at_published_width():
    """The published latent (512 + rope 64) and 16 heads, at the default
    block (``block_rows``: 256 rows of 576 bf16): rows on both sides of a
    block edge and a row over every block."""
    rng = np.random.default_rng(7)
    cap = 1024
    blk = kernel.block_rows(cap, 576, 2)
    assert blk == 256
    pos = jnp.asarray([0, 255, 257, cap - 1], jnp.int32)
    stack = jnp.asarray(rng.standard_normal((2, 4, cap, 576)), jnp.bfloat16)
    qc = jnp.asarray(0.05 * rng.standard_normal((4, 16, 576)), jnp.bfloat16)
    acc, _, total = kernel.latent_decode(qc, stack, pos, 1, 512, interpret=True)
    got = acc / jnp.maximum(total, 1e-30)[..., None]
    np.testing.assert_allclose(got, _reference(qc, stack[1], pos, 512), **BF16)


@pytest.mark.parametrize("width", [1, 64, 576, 3 * 2 ** 10])
@pytest.mark.parametrize("cap", [20, 2048, 3648])
def test_block_rows(width, cap):
    """A power of two that divides cap and whose rows fit the block's
    bytes, the largest such."""
    blk = kernel.block_rows(cap, width, 2)
    assert cap % blk == 0 and blk & (blk - 1) == 0
    assert blk * width * 2 <= kernel._BLOCK_BYTES
    assert cap % (2 * blk) or 2 * blk * width * 2 > kernel._BLOCK_BYTES


@pytest.fixture(scope="module")
def block():
    cfg = small_cfg()
    stacked = Model(cfg).init(jax.random.key(0))["layers"][1]["mixer"]
    return cfg, jax.tree.map(lambda a: a[0], stacked)  # the first MoE layer


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mla_decode_unchanged(block, dtype, monkeypatch):
    """``mla_decode`` through the kernel gives the einsums' output and the
    same new row, on a stacked cache read at its layer index."""
    cfg, p = block
    cfg = dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    dt = cfg.jnp_compute_dtype()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((len(POS), 1, cfg.d_model)), dt)
    stack = jnp.asarray(rng.standard_normal((2, len(POS), CAP, cfg.mla.cache_dim)),
                        dt)
    pos = jnp.asarray(POS, jnp.int32)
    y_ref, new_ref = mla.mla_decode(cfg, p, x, stack, pos, 1)
    _blocks_of(monkeypatch, BLK, cfg.mla.cache_dim, stack.dtype.itemsize)
    _use_kernel(monkeypatch)
    y, new = mla.mla_decode(cfg, p, x, stack, pos, 1)
    np.testing.assert_array_equal(new, new_ref)
    tol = F32 if dtype == "float32" else BF16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **tol)


def test_decode_step_through_kernel(monkeypatch):
    """A whole decode step of the reduced block through the kernel (both
    segments' scans read the stacked latent cache at their layer index)
    gives the reference's logits and the same cache after it."""
    cfg = small_cfg()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(2)
    cap, lens = 20, [9, 13, 4]
    rows = [model.prefill(params, {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (1, n)), jnp.int32)}, cap)[0] for n in lens]
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *rows)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, len(lens)), jnp.int32)
    pos = jnp.asarray(lens, jnp.int32).at[2].set(0)  # the last row free
    want, want_cache = model.decode_step(params, cache, tok, pos)
    _use_kernel(monkeypatch)  # blocks of 4 rows: the most that divide 20
    got, got_cache = model.decode_step(params, cache, tok, pos)
    np.testing.assert_allclose(got, want, **F32)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(a, b, **F32)
