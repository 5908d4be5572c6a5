"""Operation and byte counts against hand counts at small shapes."""
import pytest

import benchpath  # noqa: F401
from flops import losses, ofl


def tiny_ofl():
    return {
        "image": [4, 4, 1], "classes": 2, "clients": 2, "batch_size": 3, "gen_iters": 2, "buffer_batches": 8,
        "cnn5": {"conv_channels": [2, 4], "conv_kernel": 3, "fc_widths": [5, 3]},
        "generator": {"latent_dim": 2, "base": 1},
    }


def test_cnn5_and_generator_layers_by_hand():
    cfg = tiny_ofl()
    # conv 4x4 out, 3x3x1 -> 2: 2*16*9*1*2; conv 2x2 out, 3x3x2 -> 4: 2*4*9*2*4;
    # dense (1*1*4) -> 5, 5 -> 3, 3 -> 2
    assert ofl.cnn5_layers(cfg) == [576.0, 576.0, 40.0, 30.0, 12.0]
    # dense 4 -> 1*1*2: 2*4*2; conv 2x2 out, 3x3x2 -> 2: 2*4*9*2*2;
    # conv 4x4 out, 3x3x2 -> 1: 2*16*9*2; conv 4x4 out, 3x3x1 -> 1: 2*16*9
    assert ofl.generator_layers(cfg) == [16.0, 288.0, 576.0, 288.0]


def test_epoch_by_hand():
    cfg = tiny_ofl()
    cnn, gen = 1234.0, 1168.0
    gen_step = (3 * gen - 16) + 2 * (2 * cnn) + 2 * cnn
    gen_eval = gen + 2 * cnn + cnn
    dhs = 2 * 2 * cnn
    ee = dhs + 2 * cnn
    kd = dhs + 2 * cnn + (3 * cnn - 576)
    for batches in (1, 8):
        want = 3 * (2 * gen_step + gen_eval + gen + ee + batches * kd)
        assert ofl.epoch(cfg, batches) == pytest.approx(want)
    assert [ofl.kd_batches(e, 8) for e in (0, 3, 7, 50)] == [1, 4, 8, 8]


def test_loss_kernels_by_hand():
    assert losses.ensemble_kl_fwd(2, 3, 5) == (2 * 2 * 3 * 5 + 12 * 3 * 5, 4 * (30 + 15 + 2 + 3 + 6))
    assert losses.ghm_ce_bwd(2, 3, 5) == (4 * 30 + 10 * 15, 4 * (60 + 4 + 15))
    cfg = tiny_ofl()
    calls = losses.epoch_calls(cfg, 3)
    assert len(calls) == 4 * 2 + 2 + 2 + 2 * 3
    # bandwidth-bound: every call's least time is its bytes over the bandwidth
    t = losses.least_time(calls, 1e30, 1.0)
    assert t == pytest.approx(sum(b for _, b in calls))
