"""ViT self-attention kernel (Pallas, interpret mode on the CPU) against the
einsum oracle: the output and its gradient with respect to qkv, at a short
and at ViT-S/16's sequence, with one and several samples per grid step,
with one to three heads sliced together, under ``jax.vmap`` over clients
and inside a ``lax.scan``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.vit_attention import kernel, ops
from repro.kernels.vit_attention.ref import self_attention_ref

HEADS, DH = 6, 64


def _inputs(shape, seed=0, heads=HEADS, dh=DH):
    kq, kd = jax.random.split(jax.random.PRNGKey(seed))
    qkv = 1.5 * jax.random.normal(kq, shape + (3 * heads * dh,))
    do = jax.random.normal(kd, shape + (heads * dh,))
    return qkv, do


def _vjp(fn, qkv, do):
    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(fn, qkv)
        return o, pull(do)[0]


def _close(got, want):
    # float32 throughout; the kernel sums in another order than XLA
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("t", [17, 197])
def test_kernel_matches_the_reference(t):
    qkv, do = _inputs((2, t))
    _close(_vjp(lambda x: ops.self_attention(x, HEADS), qkv, do),
           _vjp(lambda x: self_attention_ref(x, HEADS), qkv, do))


@pytest.mark.parametrize("heads,dh,group", [(6, 64, 2), (2, 128, 1),
                                             (3, 32, 3), (2, 32, 2)])
def test_head_groups_match_the_reference(heads, dh, group):
    """Heads sliced together in one lane group, each masked to its own
    lanes, give each head's own attention."""
    assert kernel._group(heads, dh) == group
    qkv, do = _inputs((2, 17), seed=4, heads=heads, dh=dh)
    _close(_vjp(lambda x: ops.self_attention(x, heads), qkv, do),
           _vjp(lambda x: self_attention_ref(x, heads), qkv, do))


@pytest.mark.parametrize("block", [1, 2, 4])
def test_every_block_gives_the_same_result(block):
    """Samples per grid step change the grid, not the numbers."""
    qkv, do = _inputs((4, 17), seed=1)
    o, lse = kernel.vit_attention_fwd(qkv, heads=HEADS, block=block,
                                      interpret=True, mxu_dtype=jnp.float32)
    dqkv = kernel.vit_attention_bwd(qkv, o, lse, do, heads=HEADS,
                                    block=block, interpret=True,
                                    mxu_dtype=jnp.float32)
    _close((o, dqkv), _vjp(lambda x: self_attention_ref(x, HEADS), qkv, do))
    s = jnp.einsum("bqhd,bkhd->bhqk", *(
        qkv.reshape(4, 17, 3, HEADS, DH)[:, :, i] for i in (0, 1))) / 8.0
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), rtol=1e-6)


def test_kernel_under_vmap_over_clients():
    qkv, do = _inputs((3, 2, 17), seed=2)
    _close(_vjp(jax.vmap(lambda x: ops.self_attention(x, HEADS)), qkv, do),
           _vjp(jax.vmap(lambda x: self_attention_ref(x, HEADS)), qkv, do))


def test_kernel_inside_a_scan():
    """Three gradient steps on qkv, as local training takes them."""
    qkv, do = _inputs((2, 17), seed=3)

    def descend(fn):
        def step(x, _):
            loss, g = jax.value_and_grad(
                lambda y: jnp.sum(fn(y, HEADS) * do))(x)
            return x - 0.1 * g, loss
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(step, qkv, None, length=3)

    _close(descend(ops.self_attention), descend(self_attention_ref))


@pytest.mark.parametrize("b", [1, 7, 50, 64])
def test_block_is_the_largest_divisor_up_to_the_cap(b):
    block = ops._block(b)
    assert b % block == 0 and block <= ops.MAX_BLOCK
    assert not any(b % c == 0 for c in range(block + 1, ops.MAX_BLOCK + 1))
