"""Compile the transmit kernels, and a small round around them, for a
described TPU v5e with no chip attached.

Interpret mode runs Pallas kernels that Mosaic refuses (an ``e_ref[0, 0]``
scalar store to VMEM compiled in interpret mode only). Compiling for the
described chip shows such refusals, and tiling or VMEM limits, with no
chip time. The topology is described inside a module fixture, never at
import, so every test-runner worker collects the same tests and only the
worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pfels_transmit.kernel import client_sumsq, fused_combine

BLOCK = 4096
D_PAD = 2251 * BLOCK     # the paper's VGG-11 update, d ~ 9.2M, in tiles


@pytest.fixture(scope="module")
def topo():
    # the TPU compiler otherwise writes its logs to a fixed directory
    # under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("r,m_ant", [(8, 1), (32, 1), (32, 4), (256, 1)])
def test_fused_combine_compiles_for_v5e(one_chip, no_compile_cache, r,
                                        m_ant):
    args = (_spec((r, D_PAD), one_chip), _spec((1, D_PAD), one_chip),
            _spec((1, D_PAD), one_chip), _spec((r, m_ant), one_chip),
            _spec((r, 1), one_chip), _spec((r, 1), one_chip))
    compiled = fused_combine.lower(*args, block=BLOCK,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    y, e = compiled.out_info
    assert (y.shape, e.shape) == ((1, D_PAD), (1, 1))


def test_client_sumsq_compiles_for_v5e(one_chip, no_compile_cache):
    compiled = client_sumsq.lower(_spec((32, D_PAD), one_chip), block=BLOCK,
                                  interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (32, 1)


def test_trainer_step_compiles_for_v5e(one_chip, no_compile_cache,
                                       monkeypatch):
    """The whole default round (fused kernel, resident bank) at a small
    width. ``fused_pipeline`` picks the compiled kernel from
    ``jax.default_backend()``, which is the CPU here, so the test steers
    it to the TPU branch."""
    from repro.configs import PFELSConfig
    from repro.configs.paper_models import BENCH_MLP
    from repro.fl import Trainer
    from repro.models import cnn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = cnn.init_cnn(jax.random.PRNGKey(0), BENCH_MLP)
    cfg = PFELSConfig(num_clients=20, clients_per_round=8, local_steps=2,
                      rounds=1, transmit_clip=0.5)
    trainer = Trainer(cfg, lambda p, b: cnn.cnn_loss(p, BENCH_MLP, b),
                      params)
    put = lambda s: _spec(s.shape, one_chip, s.dtype)
    state = jax.tree.map(put, jax.eval_shape(trainer.init,
                                             jax.random.PRNGKey(0)))
    x = _spec((20, 10, 1, 8, 8), one_chip)
    y = _spec((20, 10), one_chip, jnp.int32)
    hlo = trainer.step.lower(state, x, y).compile().as_text()
    # both passes: the clip's sum of squares and the fused combine
    assert hlo.count("tpu_custom_call") >= 2


# the allocator's limit on one v5e chip (16 GiB of HBM less what the
# runtime keeps), as the TPU compiler states it: 15.75 GiB
V5E_BYTES_LIMIT = 16_911_433_728


@pytest.mark.parametrize("model,clients,samples", [
    ("PAPER_VGG11_CIFAR10", 1000, 50), ("PAPER_RESNET18_FEMNIST", 3550, 226)])
def test_cnn_cohorts_train_at_once_on_one_v5e(one_chip, no_compile_cache,
                                             model, clients, samples):
    """The paper's CNNs at their published widths keep the vmapped
    cohort (chunk = r = 32) on a 16 GB chip that holds the cell's resident
    data and four parameter-sized arrays of state."""
    from repro.configs import PFELSConfig, paper_models
    from repro.fl import rounds
    from repro.models import cnn

    mc = getattr(paper_models, model)
    cfg = PFELSConfig(num_clients=clients, clients_per_round=32,
                      local_steps=5, momentum=0.9)
    params = jax.eval_shape(lambda k: cnn.init_cnn(k, mc),
                            jax.random.PRNGKey(0))
    d = sum(a.size for a in jax.tree.leaves(params))
    image = (mc.in_channels, mc.image_size, mc.image_size)
    in_use = 4 * (clients * samples * (1 + int(np.prod(image))) + 4 * d)
    train_slice = rounds.local_train_slice(
        cfg, lambda p, b: cnn.cnn_loss(p, mc, b))
    chunk = rounds.client_chunk(
        train_slice, jax.tree.map(lambda a: _spec(a.shape, one_chip), params),
        _spec((32, samples) + image, one_chip),
        _spec((32, samples), one_chip, jnp.int32),
        _spec((32, 2), one_chip, jnp.uint32),
        {"bytes_limit": V5E_BYTES_LIMIT, "bytes_in_use": in_use})
    assert chunk == 32


# ------------------------------------------------------------ ViT attention

def _kernels(hlo):
    """The compiled Pallas kernels of an HLO text, by the name of the jitted
    entry point that called them: ``.../jit(<name>)/pallas_call``, or
    ``vmap(jit(<name>))/pallas_call`` where it was vmapped."""
    names = []
    for line in hlo.splitlines():
        if '"tpu_custom_call"' in line:
            op = re.search(r'op_name="[^"]*jit\((\w+)\)\)*/pallas_call"',
                           line)
            names.append(op.group(1) if op else None)
    return names


def test_vit_attention_compiles_for_v5e(one_chip, no_compile_cache):
    """Forward and backward kernels at ViT-S/16 widths (197 tokens, 6 heads
    of 64), vmapped over a chunk of 2 clients of B = 50, with the matmul
    inputs in bfloat16: Mosaic takes them and their blocks fit VMEM."""
    from repro.kernels.vit_attention.kernel import (vit_attention_bwd,
                                                    vit_attention_fwd)

    kw = dict(heads=6, block=2, interpret=False, mxu_dtype=jnp.bfloat16)

    def fwd_bwd(qkv, do):
        o, lse = jax.vmap(lambda x: vit_attention_fwd(x, **kw))(qkv)
        return o, jax.vmap(lambda *a: vit_attention_bwd(*a, **kw))(
            qkv, o, lse, do)

    compiled = jax.jit(fwd_bwd).lower(
        _spec((2, 50, 197, 1152), one_chip),
        _spec((2, 50, 197, 384), one_chip)).compile()
    assert _kernels(compiled.as_text()) == ["vit_attention_fwd",
                                            "vit_attention_bwd"]
    o, dqkv = compiled.out_info
    assert (o.shape, dqkv.shape) == ((2, 50, 197, 384), (2, 50, 197, 1152))


def test_vit_trainer_step_holds_the_kernels_for_v5e(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch):
    """A small ViT round compiled for the chip holds the attention kernel
    forward and backward in every block and both transmit kernels, each
    found by its name, and no float32 (t, t) scores or probabilities."""
    from repro.configs import PFELSConfig
    from repro.configs.paper_models import BENCH_VIT_TINY as mc
    from repro.fl import Trainer
    from repro.models import vit

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = vit.init_vit(jax.random.PRNGKey(0), mc)
    cfg = PFELSConfig(num_clients=20, clients_per_round=4, local_steps=2,
                      rounds=1, transmit_clip=0.5)
    trainer = Trainer(cfg, lambda p, b: vit.vit_loss(p, mc, b), params)
    put = lambda s: _spec(s.shape, one_chip, s.dtype)
    state = jax.tree.map(put, jax.eval_shape(trainer.init,
                                             jax.random.PRNGKey(0)))
    x = _spec((20, 50, 3, 32, 32), one_chip)
    y = _spec((20, 50), one_chip, jnp.int32)
    hlo = trainer.step.lower(state, x, y).compile().as_text()
    kernels = _kernels(hlo)
    assert kernels.count("vit_attention_fwd") == mc.depth
    assert kernels.count("vit_attention_bwd") == mc.depth
    assert {"client_sumsq", "fused_combine"} <= set(kernels)
    t = vit.num_tokens(mc)
    assert not re.search(rf"f32\[(\d+,)*{t},{t}\]", hlo)
