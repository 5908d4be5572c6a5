"""The paper's client model zoo: small image classifiers.

These are the architectures Co-Boosting's own experiments ensemble over —
LeNet-5 (MNIST/FMNIST), the 5-layer CNN of McMahan et al. (SVHN/CIFAR), the
PyTorch-tutorial CNN, a small residual net, and an MLP. They are the
*heterogeneous client* zoo of Table 3.

All models share one functional interface:

    params = init_cnn(key, arch, num_classes, in_shape)
    logits = cnn_apply(params, images)            # images: (B, H, W, C)

Normalization is GroupNorm (stateless) rather than BatchNorm so that client
models are pure functions of (params, x) — no running-stat state to
transport through the one-shot upload. Documented deviation; the paper's
qualitative claims do not depend on the norm flavor.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

CNN_ARCHS = ("lenet5", "cnn5", "cnn2", "miniresnet", "mlp")


def _conv_init(key, k, cin, cout, dtype=jnp.float32):
    fan_in = k * k * cin
    std = jnp.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, (k, k, cin, cout)) * std).astype(dtype)


def _dense_init(key, din, dout, dtype=jnp.float32):
    std = jnp.sqrt(2.0 / din)
    return (jax.random.normal(key, (din, dout)) * std).astype(dtype)


def conv2d(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def max_pool(x, k=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, k, k, 1), "VALID"
    )


def avg_pool_global(x):
    return jnp.mean(x, axis=(1, 2))


def group_norm(x, scale, bias, groups=8, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    x = xg.reshape(b, h, w, c)
    return (x * (1 + scale) + bias).astype(x.dtype)


def _gn_params(c):
    return {"scale": jnp.zeros((c,)), "bias": jnp.zeros((c,))}


# ---------------------------------------------------------------------------
# architectures


def _init_lenet5(key, num_classes, in_shape):
    h, w, c = in_shape
    ks = jax.random.split(key, 5)
    fh, fw = h // 4, w // 4  # two 2x2 pools
    return {
        "c1": _conv_init(ks[0], 5, c, 6),
        "c2": _conv_init(ks[1], 5, 6, 16),
        "f1": _dense_init(ks[2], fh * fw * 16, 120),
        "f2": _dense_init(ks[3], 120, 84),
        "out": _dense_init(ks[4], 84, num_classes),
    }


def _apply_lenet5(p, x):
    x = jnp.tanh(conv2d(x, p["c1"]))
    x = max_pool(x)
    x = jnp.tanh(conv2d(x, p["c2"]))
    x = max_pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(x @ p["f1"])
    x = jnp.tanh(x @ p["f2"])
    return x @ p["out"]


def _init_cnn5(key, num_classes, in_shape):
    """McMahan et al. 5-layer CNN: 2 conv + 3 fc."""
    h, w, c = in_shape
    ks = jax.random.split(key, 5)
    fh, fw = h // 4, w // 4
    return {
        "c1": _conv_init(ks[0], 5, c, 32),
        "c2": _conv_init(ks[1], 5, 32, 64),
        "f1": _dense_init(ks[2], fh * fw * 64, 512),
        "f2": _dense_init(ks[3], 512, 128),
        "out": _dense_init(ks[4], 128, num_classes),
    }


def _apply_cnn5(p, x):
    # ReLU after the pool: exact, as ReLU is monotone, and it then runs on the
    # quarter-size pooled tensor, so no full-resolution activation is kept.
    x = jax.nn.relu(max_pool(conv2d(x, p["c1"])))
    x = jax.nn.relu(max_pool(conv2d(x, p["c2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"])
    x = jax.nn.relu(x @ p["f2"])
    return x @ p["out"]


def _init_cnn2(key, num_classes, in_shape):
    """PyTorch-tutorial CNN: conv6/conv16 + 3 fc."""
    h, w, c = in_shape
    ks = jax.random.split(key, 5)
    fh, fw = h // 4, w // 4
    return {
        "c1": _conv_init(ks[0], 5, c, 6),
        "c2": _conv_init(ks[1], 5, 6, 16),
        "f1": _dense_init(ks[2], fh * fw * 16, 120),
        "f2": _dense_init(ks[3], 120, 84),
        "out": _dense_init(ks[4], 84, num_classes),
    }


def _apply_cnn2(p, x):
    x = jax.nn.relu(max_pool(conv2d(x, p["c1"])))  # after the pool, as in cnn5
    x = jax.nn.relu(max_pool(conv2d(x, p["c2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"])
    x = jax.nn.relu(x @ p["f2"])
    return x @ p["out"]


def _init_resblock(key, cin, cout, stride):
    ks = jax.random.split(key, 3)
    p = {
        "c1": _conv_init(ks[0], 3, cin, cout),
        "n1": _gn_params(cout),
        "c2": _conv_init(ks[1], 3, cout, cout),
        "n2": _gn_params(cout),
        "stride": stride,
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], 1, cin, cout)
    return p


def _apply_resblock(p, x):
    s = p["stride"]
    h = jax.nn.relu(group_norm(conv2d(x, p["c1"], stride=s), **p["n1"]))
    h = group_norm(conv2d(h, p["c2"]), **p["n2"])
    sc = conv2d(x, p["proj"], stride=s) if "proj" in p else x
    return jax.nn.relu(h + sc)


def _init_miniresnet(key, num_classes, in_shape):
    _, _, c = in_shape
    ks = jax.random.split(key, 6)
    return {
        "stem": _conv_init(ks[0], 3, c, 32),
        "stem_n": _gn_params(32),
        "b1": _init_resblock(ks[1], 32, 32, 1),
        "b2": _init_resblock(ks[2], 32, 64, 2),
        "b3": _init_resblock(ks[3], 64, 128, 2),
        "out": _dense_init(ks[4], 128, num_classes),
    }


def _apply_miniresnet(p, x):
    x = jax.nn.relu(group_norm(conv2d(x, p["stem"]), **p["stem_n"]))
    x = _apply_resblock(p["b1"], x)
    x = _apply_resblock(p["b2"], x)
    x = _apply_resblock(p["b3"], x)
    return avg_pool_global(x) @ p["out"]


def _init_mlp(key, num_classes, in_shape):
    h, w, c = in_shape
    ks = jax.random.split(key, 3)
    return {
        "f1": _dense_init(ks[0], h * w * c, 256),
        "f2": _dense_init(ks[1], 256, 128),
        "out": _dense_init(ks[2], 128, num_classes),
    }


def _apply_mlp(p, x):
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f1"])
    x = jax.nn.relu(x @ p["f2"])
    return x @ p["out"]


_ARCHS = {
    "lenet5": (_init_lenet5, _apply_lenet5),
    "cnn5": (_init_cnn5, _apply_cnn5),
    "cnn2": (_init_cnn2, _apply_cnn2),
    "miniresnet": (_init_miniresnet, _apply_miniresnet),
    "mlp": (_init_mlp, _apply_mlp),
}


def init_cnn(key, arch: str, num_classes: int, in_shape: Tuple[int, int, int]):
    init, _ = _ARCHS[arch]
    return init(key, num_classes, in_shape)


def cnn_apply(arch: str, params, x):
    _, apply = _ARCHS[arch]
    return apply(params, x)


def make_cnn(arch: str, num_classes: int, in_shape: Tuple[int, int, int]):
    """Returns (init_fn(key) -> params, apply_fn(params, images) -> logits)."""
    init, apply = _ARCHS[arch]
    return partial(init, num_classes=num_classes, in_shape=in_shape), apply
