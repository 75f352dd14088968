"""The FLOP counter against XLA's own cost analysis, on the CPU."""
import functools
import json

import jax
import jax.numpy as jnp
import pytest

import flops
import harness


@pytest.mark.parametrize("config", ["vgg11-cifar10", "resnet18-femnist"])
def test_forward_flops_match_xla(config):
    config = json.loads(
        (harness.BENCH / "configs" / f"{config}.json").read_text())
    model, net = config["model"], harness.net_of(config)
    params = jax.eval_shape(functools.partial(net.init_params, model=model),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2,) + harness.input_shape(model), jnp.float32)
    fwd = functools.partial(net.forward, model=model)

    ours = flops.forward_flops(lambda p, x: fwd(p, x=x), params, x)
    xla = jax.jit(lambda p, x: fwd(p, x=x)).lower(
        params, x).compile().cost_analysis()["flops"]
    # XLA also counts the elementwise work (ReLU, pooling, the bias); the
    # matrix units' share is all but 0.2% of it at these shapes
    assert 0.995 * xla <= ours <= xla


def test_padding_taps_are_not_counted():
    """A 3x3 SAME convolution of a 4x4 image has 36 of 144 taps on the
    padding ring: each of the 4 corners loses 5, each of 8 edges 3."""
    x = jax.ShapeDtypeStruct((1, 1, 4, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((1, 1, 3, 3), jnp.float32)
    conv = functools.partial(jax.lax.conv_general_dilated,
                             window_strides=(1, 1), padding="SAME")
    assert flops.forward_flops(conv, x, w) == 2 * (144 - 4 * 5 - 8 * 3)


def test_training_is_three_forwards():
    model = {"arch": "vgg", "in_channels": 3, "image_size": 32,
             "num_classes": 10}
    net = harness.net_of({"reference": "cnn"})
    params = jax.eval_shape(functools.partial(net.init_params, model=model),
                            jax.random.PRNGKey(0))
    fwd = functools.partial(net.forward, model=model)
    f = flops.train_flops_per_sample(lambda p, x: fwd(p, x=x), params,
                                     (3, 32, 32))
    x = jax.ShapeDtypeStruct((1, 3, 32, 32), jnp.float32)
    assert f == 3 * flops.forward_flops(lambda p, x: fwd(p, x=x), params, x)
