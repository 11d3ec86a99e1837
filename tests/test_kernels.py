"""Pallas kernel allclose sweeps against the ref.py oracles (interpret mode
executes the kernel body on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


class TestMatmulKernel:
    @pytest.mark.parametrize("m,n,k,bm,bn,bk", [
        (128, 128, 128, 64, 64, 64),
        (256, 128, 512, 128, 128, 128),
        (64, 256, 128, 64, 128, 128),   # blocks clamp to shape
        (384, 256, 256, 128, 256, 128),  # non-pow2 M
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_against_oracle(self, m, n, k, bm, bn, bk, dtype):
        x, y = _rand((m, k), dtype), _rand((k, n), dtype)
        got = matmul_pallas(x, y, bm=bm, bn=bn, bk=bk, interpret=True)
        want = ref.matmul(x, y)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype] * np.sqrt(k), rtol=TOL[dtype],
        )

    def test_rejects_indivisible(self):
        x, y = _rand((100, 128), jnp.float32), _rand((128, 128), jnp.float32)
        with pytest.raises(AssertionError):
            matmul_pallas(x, y, bm=64, bn=64, bk=64, interpret=True)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,hq,hkv,s,d,bq,bk", [
        (1, 2, 2, 128, 64, 64, 64),     # MHA
        (2, 4, 2, 256, 64, 128, 64),    # GQA 2:1
        (1, 8, 1, 128, 32, 64, 128),    # MQA
        (2, 4, 4, 512, 128, 256, 128),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_against_oracle(self, b, hq, hkv, s, d, bq, bk, causal):
        q = _rand((b, hq, s, d), jnp.float32)
        k = _rand((b, hkv, s, d), jnp.float32)
        v = _rand((b, hkv, s, d), jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                     block_k=bk, interpret=True)
        want = ref.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-4)

    def test_bf16(self):
        q = _rand((1, 4, 128, 64), jnp.bfloat16)
        k = _rand((1, 2, 128, 64), jnp.bfloat16)
        v = _rand((1, 2, 128, 64), jnp.bfloat16)
        got = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                     block_k=64, interpret=True)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=5e-2, rtol=5e-2)


class TestChunkedAttention:
    """The jnp flash mirror used on non-TPU backends must match the oracle."""

    @pytest.mark.parametrize("s,chunk", [(256, 64), (512, 128), (128, 128)])
    def test_matches_oracle(self, s, chunk):
        from repro.models.attention import chunked_attention

        q = _rand((2, 4, s, 32), jnp.float32)
        k = _rand((2, 2, s, 32), jnp.float32)
        v = _rand((2, 2, s, 32), jnp.float32)
        got = chunked_attention(q, k, v, causal=True, chunk=chunk)
        want = ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-4)


class TestFlashAttentionGradient:
    """The Pallas flash kernel is forward-only; ``models.attention.
    flash_attention`` gives it the gradient of the jnp mirror, so training
    at prompt lengths that take the kernel can differentiate through it."""

    @pytest.mark.parametrize("pallas,mesh", [(False, False), (True, False),
                                             (True, True)])
    def test_grad_matches_oracle(self, pallas, mesh, monkeypatch):
        import contextlib
        import functools

        from repro.kernels import ops
        from repro.launch.mesh import make_mesh
        from repro.models import attention as attn
        from repro.parallel import context as pctx

        if pallas:  # the kernel, interpreted, where a TPU would compile it
            monkeypatch.setattr(ops, "attention", functools.partial(
                ops.attention, target="tpu_v5e"))
        ctx = contextlib.ExitStack()
        if mesh:  # a Mosaic kernel under a mesh has to run per shard
            ctx.enter_context(jax.set_mesh(make_mesh((1, 1), ("data", "model"))))
            ctx.enter_context(pctx.activation_sharding(("data",), tp_size=1))
        q = _rand((1, 4, 128, 32), jnp.float32)
        k = _rand((1, 2, 128, 32), jnp.float32)
        v = _rand((1, 2, 128, 32), jnp.float32)
        w = _rand((1, 4, 128, 32), jnp.float32)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

        with ctx:
            got = jax.jit(jax.grad(loss(lambda q, k, v: attn.flash_attention(
                q, k, v, True, 64)), argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(loss(lambda q, k, v: ref.attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for g, h in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                       atol=2e-4, rtol=2e-3)
