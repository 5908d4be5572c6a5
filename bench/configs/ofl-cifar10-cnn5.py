"""Plain reference for the ofl-cifar10-cnn5 configuration: Co-Boosting
(Dai et al., ICLR 2024, Algorithm 1) in straightforward jax.numpy, with no
kernels, no grouped client bank, no ring buffer and no fused epoch.

It also makes the weights every run starts from: ten cnn5 clients, a cnn5
server and the DCGAN-style generator, drawn from one key in one jitted call.
Both the program and this reference take those weights.

One epoch, as the paper states it and as the program keys its randomness:

1. z ~ N(0, 1), y ~ U{0..C-1}; T_G Adam steps on the generator loss
   L = mean(d * CE(A_w(x), y)) - beta * mean(KL(A_w(x) || f_S(x))), with
   x = G(z, y), A_w = sum_k w_k f_k and d = 1 - softmax(A_w)_y held constant;
   the new batch joins the last ``buffer_batches`` batches.
2. EE: one sign step w <- normalize(clip(w - mu/K * sign(grad_w CE(A_w(x~), y))))
   on the DHS-perturbed batch x~ = x + eps * g / |g|, g = grad_x u.A_w(x).
3. KD: for each kept batch, in the order ``RandomState(epoch).permutation``
   gives, one SGD-momentum step on T^2 * KL(A_w(x~)/T || f_S(x~)/T).

Everything is float32, and every matrix product and convolution runs at the
precision the configuration states (``matmul_precision``): ``default``, which
on the TPU is one bfloat16 pass over the f32 operands with f32 accumulation,
as the program's products run. ``precision="highest"`` runs them as exact f32
products instead: a witness of how far product rounding alone moves the
epochs. Two lower precisions serve as controls: ``dtype=bfloat16`` puts
weights, optimizer state and activations in bfloat16, and ``products="fp8"``
rounds both operands of every product to float8 (e4m3, one scale per
tensor) and keeps the rest in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"default": None, "highest": jax.lax.Precision.HIGHEST}


# -- weights ---------------------------------------------------------------------


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _cnn5_init(key, cfg):
    h, w, c = cfg["image"]
    c1, c2 = cfg["cnn5"]["conv_channels"]
    f1, f2 = cfg["cnn5"]["fc_widths"]
    k = cfg["cnn5"]["conv_kernel"]
    ks = jax.random.split(key, 5)
    flat = (h // 4) * (w // 4) * c2
    return {
        "c1": _normal(ks[0], (k, k, c, c1), (2.0 / (k * k * c)) ** 0.5),
        "c2": _normal(ks[1], (k, k, c1, c2), (2.0 / (k * k * c1)) ** 0.5),
        "f1": _normal(ks[2], (flat, f1), (2.0 / flat) ** 0.5),
        "f2": _normal(ks[3], (f1, f2), (2.0 / f1) ** 0.5),
        "out": _normal(ks[4], (f2, cfg["classes"]), (2.0 / f2) ** 0.5),
    }


def _generator_init(key, cfg):
    h, w, c = cfg["image"]
    nz, base, classes = cfg["generator"]["latent_dim"], cfg["generator"]["base"], cfg["classes"]
    ks = jax.random.split(key, 10)
    fc_out = (h // 4) * (w // 4) * 2 * base

    def bn(k, ch):
        ka, kb = jax.random.split(k)
        return {"scale": _normal(ka, (ch,), 0.1), "bias": _normal(kb, (ch,), 0.1)}

    return {
        "label_embed": _normal(ks[0], (classes, nz), 0.1),
        "fc": _normal(ks[1], (2 * nz, fc_out), (2.0 / (2 * nz)) ** 0.5),
        "bn0": bn(ks[5], 2 * base),
        "conv1": _normal(ks[2], (3, 3, 2 * base, 2 * base), (2.0 / (9 * 2 * base)) ** 0.5),
        "bn1": bn(ks[6], 2 * base),
        "conv2": _normal(ks[3], (3, 3, 2 * base, base), (2.0 / (9 * 2 * base)) ** 0.5),
        "bn2": bn(ks[7], base),
        "conv3": _normal(ks[4], (3, 3, base, c), (2.0 / (9 * base)) ** 0.5),
    }


def make_weights(key, cfg):
    """(clients: tuple of K trees, server tree, generator tree), all float32,
    made on the device in one call."""

    @jax.jit
    def make(key):
        kc, ks, kg = jax.random.split(key, 3)
        clients = tuple(_cnn5_init(k, cfg) for k in jax.random.split(kc, cfg["clients"]))
        return clients, _cnn5_init(ks, cfg), _generator_init(kg, cfg)

    return make(key)


# -- models ----------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _operands(a, b, precision):
    if precision == "fp8":
        return _fp8(a), _fp8(b), PRECISIONS["highest"]
    return a, b, precision


def _dot(x, w, precision):
    x, w, precision = _operands(x, w.astype(x.dtype), precision)
    return jnp.dot(x, w, precision=precision)


def _conv(x, w, precision):
    x, w, precision = _operands(x, w.astype(x.dtype), precision)
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
    )


def _pool(x):
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def cnn5(p, x, precision):
    x = _pool(jax.nn.relu(_conv(x, p["c1"], precision)))
    x = _pool(jax.nn.relu(_conv(x, p["c2"], precision)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(_dot(x, p["f1"], precision))
    x = jax.nn.relu(_dot(x, p["f2"], precision))
    return _dot(x, p["out"], precision)


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * (1 + p["scale"]) + p["bias"]


def _up2(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def generator(p, z, y, cfg, precision):
    h, w, _ = cfg["image"]
    base = cfg["generator"]["base"]
    x = jnp.concatenate([z, p["label_embed"][y]], axis=-1)
    x = _dot(x, p["fc"], precision).reshape(-1, h // 4, w // 4, 2 * base)
    x = _up2(_batch_norm(x, p["bn0"]))
    x = _up2(jax.nn.leaky_relu(_batch_norm(_conv(x, p["conv1"], precision), p["bn1"]), 0.2))
    x = jax.nn.leaky_relu(_batch_norm(_conv(x, p["conv2"], precision), p["bn2"]), 0.2)
    return jnp.tanh(_conv(x, p["conv3"], precision))


# -- losses ----------------------------------------------------------------------


def ensemble(clients, w, x, precision):
    return sum(w[k] * cnn5(p, x, precision) for k, p in enumerate(clients))


def _mean(v, rows):
    return jnp.mean(v[:rows])


def _log_softmax(t):
    t = t - jnp.max(t, axis=-1, keepdims=True)
    return t - jnp.log(jnp.sum(jnp.exp(t), axis=-1, keepdims=True))


def kl(teacher, student, temperature):
    lt = _log_softmax(teacher / temperature)
    ls = _log_softmax(student / temperature)
    return jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1) * temperature**2


def ce(logits, y):
    lp = _log_softmax(logits)
    return -jnp.take_along_axis(lp, y[:, None], axis=-1)[:, 0]


def dhs(clients, w, x, key, eps, precision):
    def score(x_in):
        ens = ensemble(clients, w, x_in, precision)
        u = jax.random.uniform(key, ens.shape, jnp.float32, -1.0, 1.0).astype(ens.dtype)
        return jnp.sum(u * ens)

    g = jax.grad(score)(x)
    flat = g.reshape(g.shape[0], -1)
    norm = jnp.sqrt(jnp.sum(jnp.square(flat), axis=-1, keepdims=True))
    return x + eps * (flat / jnp.maximum(norm, 1e-12)).reshape(g.shape).astype(x.dtype)


# -- one epoch -------------------------------------------------------------------


def _epoch_fn(cfg, dtype, rows, precision):
    b, nz, classes = cfg["batch_size"], cfg["generator"]["latent_dim"], cfg["classes"]
    lr_g, lr_s, mom = cfg["gen_lr"], cfg["server_lr"], cfg["server_momentum"]
    b1, b2, adam_eps = 0.9, 0.999, 1e-8
    mu = cfg["mu"] / cfg["clients"]
    eps = cfg["epsilon"]
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)

    def gen_loss(gp, z, y, clients, w, server):
        x = generator(gp, z, y, cfg, precision)
        ens = ensemble(clients, w, x, precision)
        d = jax.lax.stop_gradient(1.0 - jnp.exp(-ce(ens, y)))
        loss = _mean(d * ce(ens, y), rows)
        s = cnn5(server, x, precision)
        return loss - cfg["beta"] * _mean(kl(ens, s, cfg["gen_kl_temperature"]), rows)

    def generator_phase(gp, m, v, key, clients, w, server):
        kz, ky = jax.random.split(key)
        z = jax.random.normal(kz, (b, nz)).astype(dtype)
        y = jax.random.randint(ky, (b,), 0, classes)

        def step(i, carry):
            gp, m, v = carry
            g = jax.grad(gen_loss)(gp, z, y, clients, w, server)
            m = jax.tree_util.tree_map(lambda a, c: b1 * a + (1 - b1) * c, m, g)
            v = jax.tree_util.tree_map(lambda a, c: b2 * a + (1 - b2) * c * c, v, g)
            t = (i + 1).astype(jnp.float32)
            bc1, bc2 = 1 - b1**t, 1 - b2**t
            gp = jax.tree_util.tree_map(
                lambda p, a, c: (p - lr_g * (a / bc1) / (jnp.sqrt(c / bc2) + adam_eps)).astype(dtype), gp, m, v
            )
            return gp, m, v

        gp, m, v = jax.lax.fori_loop(0, cfg["gen_iters"], step, (gp, m, v))
        gloss = gen_loss(gp, z, y, clients, w, server)
        return gp, m, v, generator(gp, z, y, cfg, precision), y, gloss

    def ee(w, x, y, key, clients):
        xe = dhs(clients, w, x, key, eps, precision)
        logits = [cnn5(p, xe, precision) for p in clients]

        def loss(w_):
            return _mean(ce(sum(w_[k] * l for k, l in enumerate(logits)), y), rows)

        g = jax.grad(loss)(w)
        w = jnp.clip(w - mu * jnp.sign(g), 0.0, 1.0)
        return w / jnp.maximum(jnp.sum(w), 1e-12)

    def kd_step(server, mom_buf, x, key, clients, w):
        x = dhs(clients, w, x, key, eps, precision)
        ens = ensemble(clients, w, x, precision)

        def loss(sp):
            return _mean(kl(ens, cnn5(sp, x, precision), cfg["kd_temperature"]), rows)

        val, g = jax.value_and_grad(loss)(server)
        mom_buf = jax.tree_util.tree_map(lambda a, c: mom * a + c, mom_buf, g)
        server = jax.tree_util.tree_map(lambda p, a: p - lr_s * a, server, mom_buf)
        return server, mom_buf, val

    return (
        jax.jit(generator_phase),
        jax.jit(ee),
        jax.jit(kd_step),
        cast,
    )


def run(weights, key, cfg, epochs: int, dtype=jnp.float32, rows=None, precision=None, products=None):
    """Run ``epochs`` Co-Boosting epochs from ``weights`` with the run key.

    Returns ``{"gen_loss": [...], "kd_loss": [...], "first_grad": tree,
    "gen": tree, "gen_m": tree, "server": tree}``: the generator loss after
    each epoch's T_G steps, each epoch's mean KD loss, the server optimizer's
    momentum after its first step (the first gradient), the generator and
    its Adam momentum after epoch 0, and the server after ``epochs`` epochs,
    all on the host in float64. ``precision`` (``default`` or ``highest``)
    overrides the configuration's ``matmul_precision``; ``products="fp8"``
    rounds every product's operands to float8; ``rows`` (for fault checks)
    takes the losses' means over the first ``rows`` samples of each batch
    only."""
    rows = rows or cfg["batch_size"]
    prec = "fp8" if products == "fp8" else PRECISIONS[precision or cfg["matmul_precision"]]
    gen_phase, ee, kd_step, cast = _epoch_fn(cfg, dtype, rows, prec)
    clients, server, gp = cast(weights[0]), cast(weights[1]), cast(weights[2])
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    m, v, mom_buf = zeros(gp), zeros(gp), zeros(server)
    w = jnp.full((cfg["clients"],), 1.0 / cfg["clients"], dtype)
    ring = []
    out = {"gen_loss": [], "kd_loss": [], "first_grad": None}
    cap = cfg["buffer_batches"]
    for epoch in range(epochs):
        keys = jax.random.split(key, 4)
        key, k1, k2, k3 = keys[0], keys[1], keys[2], keys[3]
        gp, m, v, x_new, y, gloss = gen_phase(gp, m, v, k1, clients, w, server)
        if epoch == 0:
            out["gen"], out["gen_m"] = to_host(gp), to_host(m)
        ring = (ring + [x_new])[-cap:]
        w = ee(w, x_new, y, k2, clients)
        losses = []
        for i in np.random.RandomState(epoch).permutation(len(ring)):
            k3, kb = jax.random.split(k3)
            server, mom_buf, val = kd_step(server, mom_buf, ring[i], kb, clients, w)
            losses.append(val)
            if out["first_grad"] is None:
                out["first_grad"] = to_host(mom_buf)
        out["gen_loss"].append(float(gloss))
        out["kd_loss"].append(float(np.mean([float(l) for l in losses])))
    out["server"] = to_host(server)
    return out


def to_host(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a), np.float64), tree)
