"""Operations a Co-Boosting epoch needs, from shapes.

A layer's forward is counted as 2 x multiply-adds (padded positions of a
``SAME`` convolution included). Its backward costs one forward for the
input gradient and one for the weight gradient, each only where the
algorithm needs it. Recomputed work (rematerialization) does not count.
"""
from __future__ import annotations

from typing import List


def conv(h: int, w: int, k: int, cin: int, cout: int) -> float:
    return 2.0 * h * w * k * k * cin * cout


def dense(din: int, dout: int) -> float:
    return 2.0 * din * dout


def cnn5_layers(cfg: dict) -> List[float]:
    """Per-image forward operations of each cnn5 layer, input side first."""
    h, w, c = cfg["image"]
    c1, c2 = cfg["cnn5"]["conv_channels"]
    f1, f2 = cfg["cnn5"]["fc_widths"]
    k = cfg["cnn5"]["conv_kernel"]
    return [
        conv(h, w, k, c, c1),
        conv(h // 2, w // 2, k, c1, c2),
        dense((h // 4) * (w // 4) * c2, f1),
        dense(f1, f2),
        dense(f2, cfg["classes"]),
    ]


def generator_layers(cfg: dict) -> List[float]:
    h, w, c = cfg["image"]
    nz, base = cfg["generator"]["latent_dim"], cfg["generator"]["base"]
    return [
        dense(2 * nz, (h // 4) * (w // 4) * 2 * base),
        conv(h // 2, w // 2, 3, 2 * base, 2 * base),
        conv(h, w, 3, 2 * base, base),
        conv(h, w, 3, base, c),
    ]


def fwd(layers) -> float:
    return sum(layers)


def fwd_input_grad(layers) -> float:
    """Forward plus the gradient with respect to the input (a frozen model)."""
    return 2 * sum(layers)


def train(layers, input_grad: bool) -> float:
    """Forward, weight gradients, and input gradients where they are needed:
    every layer's but the first's, unless the input's own is wanted too."""
    return 3 * sum(layers) - (0 if input_grad else layers[0])


def epoch(cfg: dict, kd_batches: int) -> float:
    """One epoch: T_G generator steps, the generator loss and the new batch,
    EE on the DHS-perturbed batch, and ``kd_batches`` KD steps."""
    b, k = cfg["batch_size"], cfg["clients"]
    cnn, gen = cnn5_layers(cfg), generator_layers(cfg)
    # generator step: G trained; K clients and the server frozen, but their
    # input gradients feed the generator's
    gen_step = train(gen, input_grad=False) + k * fwd_input_grad(cnn) + fwd_input_grad(cnn)
    gen_loss_eval = fwd(gen) + k * fwd(cnn) + fwd(cnn)
    new_batch = fwd(gen)
    dhs = k * fwd_input_grad(cnn)
    ee = dhs + k * fwd(cnn)
    kd_step = dhs + k * fwd(cnn) + train(cnn, input_grad=False)
    per_image = cfg["gen_iters"] * gen_step + gen_loss_eval + new_batch + ee + kd_batches * kd_step
    return b * per_image


def kd_batches(epoch_index: int, capacity: int) -> int:
    return min(epoch_index + 1, capacity)
