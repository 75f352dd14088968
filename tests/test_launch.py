"""Launch-layer glue: input specs + lower/compile for every step kind on a
host mesh with reduced archs (the 512-device production meshes are covered
by the dry-run itself)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import reduced_config
from repro.configs.base import PFELSConfig
from repro.configs.shapes import InputShape
from repro.launch import inputs as I
from repro.launch import steps as S
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.sharding.rules import tree_shardings

TRAIN_S = InputShape("t_train", 128, 8, "train")
PREFILL_S = InputShape("t_prefill", 256, 4, "prefill")
DECODE_S = InputShape("t_decode", 256, 4, "decode")
LONG_S = InputShape("long_500k", 512, 1, "decode")  # triggers window mode


def _params_in(cfg, mesh):
    with jax.set_mesh(mesh):
        shapes = T.init_shapes(cfg)
        logical = T.logical_axes(cfg)
    sh = tree_shardings(mesh, logical, shapes)
    return jax.tree.map(
        lambda sd, s: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=s),
        shapes, sh)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "zamba2-2.7b",
                                  "granite-moe-3b-a800m", "whisper-tiny",
                                  "qwen2-vl-72b"])
@pytest.mark.parametrize("shape", [TRAIN_S, PREFILL_S, DECODE_S, LONG_S])
def test_lower_compile_all_kinds(arch, shape):
    cfg = reduced_config(arch)
    mesh = make_host_mesh((1, 1), ("data", "model"))
    pfels = PFELSConfig(num_clients=100, compression_ratio=0.5, epsilon=2.0,
                        local_steps=1)
    params_in = _params_in(cfg, mesh)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            batch = I.train_batch_specs(cfg, shape, mesh)
            d = sum(x.size for x in jax.tree.leaves(params_in))
            step = S.make_pfels_train_step(cfg, pfels, d, mesh)
            lowered = jax.jit(step).lower(
                params_in, batch, jax.ShapeDtypeStruct((2,), jnp.uint32))
        elif shape.kind == "prefill":
            batch = I.prefill_batch_specs(cfg, shape, mesh)
            step = S.make_prefill_step(cfg)
            lowered = jax.jit(step).lower(params_in, batch)
        else:
            window = I.long_context_window(cfg, shape)
            spec = I.decode_specs(cfg, shape, mesh, window=window)
            step = S.make_serve_step(cfg, window=window)
            kw = {}
            if cfg.is_encoder_decoder:
                kw["enc_out"] = spec["enc_out"]
            lowered = jax.jit(step).lower(params_in, spec["token"],
                                          spec["caches"], **kw)
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    coll = collective_bytes(compiled.as_text())
    terms = roofline_terms(compiled.cost_analysis(), coll, mesh.size)
    assert terms["dominant"] in ("compute", "memory", "collective")


def test_long_context_window_policy():
    assert I.long_context_window(reduced_config("mamba2-130m"),
                                 LONG_S) is None            # attention-free
    assert I.long_context_window(reduced_config("phi3-mini-3.8b"),
                                 LONG_S) == 256             # sliding window
    assert I.long_context_window(reduced_config("phi3-mini-3.8b"),
                                 DECODE_S) is None          # full attention
