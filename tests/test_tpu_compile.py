"""Compile every main-path Pallas kernel for a TPU v5e, without a chip.

Interpret mode runs a kernel body as plain JAX, so it cannot show what the
TPU compiler (Mosaic) refuses: blocks that break the (8, 128) tiling, values
it has no layout for, more VMEM than a kernel may use. These tests describe
a ``v5e:2x2`` topology, lower each kernel for one of its chips at real
widths, and compile it with the installed TPU compiler. Nothing runs; a
pass says the kernel compiles, not that it is right (the interpret-mode
parity tests say that).

Shape sets: the paper's image-scale OFL epoch (K=10 clients, b=128, 10
classes) and smollm-135m (9 query heads, 3 KV heads, head_dim 64, vocab
49152, 2048-token sequences, 16-token KV pages).

The client-bank test compiles the input gradient of the K=10 cnn5 bank at
b=128 and counts the full-resolution tensors the program writes: ReLU
after the max-pool leaves the first convolution's output and its pool
backward, with no ReLU pass or mask at that size.

The kernel-name tests lower each kernel for the TPU without a topology and
without compiling: every ``pallas_call`` carries a stable ``kernel_name``,
and the name is a component of the kernel's op name, so a device trace
tells the kernels apart.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.
"""
from __future__ import annotations

import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.client_bank import make_ensemble
from repro.kernels.ensemble_kl.kernel import ensemble_kl_bwd_pallas, ensemble_kl_pallas
from repro.kernels.flash_attention.kernel import (
    flash_attention_bwd_pallas,
    flash_attention_pallas,
)
from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels.ghm_ce.kernel import ghm_ce_bwd_pallas, ghm_ce_pallas
from repro.models.cnn import cnn_apply, init_cnn

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32

# (K clients, rows, vocab, logit dtype)
LOSS_SHAPES = {
    "ofl-image": (10, 128, 10, F32),
    "smollm-135m": (4, 256, 49152, BF16),
}
# smollm-135m attention: (batch, seq, heads, kv_heads, head_dim)
ATTN_SHAPES = {
    "prefill-256": (8, 256, 9, 3, 64),
    "train-2048": (1, 2048, 9, 3, 64),
}
# smollm-135m paged decode: (slots, pages per slot, window, softcap)
DECODE_SHAPES = {
    "full": (8, 128, 0, 0.0),
    "window-softcap": (8, 32, 256, 30.0),
}
DECODE_HEADS, DECODE_KV_HEADS, HEAD_DIM, PAGE = 9, 3, 64, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("shape", list(LOSS_SHAPES), ids=list(LOSS_SHAPES))
def test_ensemble_kl_compiles(one_chip, shape):
    k, b, v, dt = LOSS_SHAPES[shape]
    S = lambda s, d=F32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    fwd = _compile(
        lambda c, s, w: ensemble_kl_pallas(c, s, w, 4.0, return_stats=True),
        S((k, b, v), dt), S((b, v), dt), S((k,)),
    )
    assert _kernels(fwd) >= 1
    bwd = _compile(
        lambda c, s, w, g, o, lt, ls: ensemble_kl_bwd_pallas(c, s, w, g, o, lt, ls, 4.0),
        S((k, b, v), dt), S((b, v), dt), S((k,)), S((b,)), S((b,)), S((b,)), S((b,)),
    )
    assert _kernels(bwd) >= 1


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "plain"])
@pytest.mark.parametrize("shape", list(LOSS_SHAPES), ids=list(LOSS_SHAPES))
def test_ghm_ce_compiles(one_chip, shape, weighted):
    k, b, v, dt = LOSS_SHAPES[shape]
    S = lambda s, d=F32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    fwd = _compile(
        lambda c, y, w: ghm_ce_pallas(c, y, w, weighted=weighted, return_stats=True),
        S((k, b, v), dt), S((b,), I32), S((k,)),
    )
    assert _kernels(fwd) >= 1
    bwd = _compile(
        lambda c, y, w, g, lse, ly: ghm_ce_bwd_pallas(c, y, w, g, lse, ly, weighted=weighted),
        S((k, b, v), dt), S((b,), I32), S((k,)), S((b,)), S((b,)), S((b,)),
    )
    assert _kernels(bwd) >= 1


@pytest.mark.parametrize("shape", list(ATTN_SHAPES), ids=list(ATTN_SHAPES))
def test_flash_attention_compiles(one_chip, shape):
    b, s, h, kh, hd = ATTN_SHAPES[shape]
    S = lambda sh, d=BF16: jax.ShapeDtypeStruct(sh, d, sharding=one_chip)
    q, kv = S((b, s, h, hd)), S((b, s, kh, hd))
    assert _kernels(_compile(flash_attention_pallas, q, kv, kv)) >= 1
    fwd = _compile(lambda q, k, v: flash_attention_pallas(q, k, v, return_lse=True), q, kv, kv)
    assert _kernels(fwd) >= 1
    # the backward is two kernels: dq (kv minor) and dk/dv (q minor)
    bwd = _compile(flash_attention_bwd_pallas, q, kv, kv, q, S((b, s, h), F32), q)
    assert _kernels(bwd) >= 2


@pytest.mark.parametrize("shape", list(DECODE_SHAPES), ids=list(DECODE_SHAPES))
def test_flash_decode_compiles(one_chip, shape):
    b, w, window, softcap = DECODE_SHAPES[shape]
    pages = b * w + 1
    S = lambda sh, d=BF16: jax.ShapeDtypeStruct(sh, d, sharding=one_chip)
    kv = S((pages, PAGE, DECODE_KV_HEADS, HEAD_DIM))
    text = _compile(
        lambda q, k, v, t, p: flash_decode_pallas(
            q, k, v, t, p, window=window, softcap=softcap,
            cache_len=min(window, w * PAGE) if window else 0,
        ),
        S((b, DECODE_HEADS, HEAD_DIM)), kv, kv, S((b, w), I32), S((b,), I32),
    )
    assert _kernels(text) >= 1


def _entry_results(text: str):
    """(opcode, element counts of the result or of each tuple element) for
    every instruction of the compiled program's entry computation."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", entry, re.M):
        result, opcode = m.groups()
        dims = re.findall(r"\w+\[([\d,]*)\]", result)
        yield opcode, [math.prod(int(d) for d in ds.split(",") if d) for ds in dims]


def test_client_bank_grad_writes_two_full_resolution_tensors(one_chip):
    """The cnn5 bank's input gradient (K=10, b=128, 32x32 images, as in a
    generator step) writes exactly two tensors of the first convolution's
    full output size (128 x 32 x 32 x 10*32): the convolution and the pool's
    select-and-scatter. With ReLU before the pool there were four, the ReLU
    and its mask besides."""
    k, b, classes, image = 10, 128, 10, (32, 32, 3)
    held = {}

    def build():
        clients = [init_cnn(jax.random.key(i), "cnn5", classes, image) for i in range(k)]
        held["logits_all"], bank_params = make_ensemble([partial(cnn_apply, "cnn5")] * k, clients)
        return bank_params

    S = lambda s, d=F32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = jax.tree.map(lambda a: S(a.shape, a.dtype), jax.eval_shape(build))

    def input_grad(p, x, ct):
        return jax.vjp(lambda v: held["logits_all"](p, v), x)[1](ct)[0]

    text = _compile(input_grad, params, S((b, *image)), S((k, b, classes)))
    full = b * 32 * 32 * k * 32
    writes = [op for op, sizes in _entry_results(text)
              if full in sizes and op not in ("bitcast", "get-tuple-element", "parameter")]
    assert len(writes) == 2 and "select-and-scatter" in writes, writes


# -- kernel names ---------------------------------------------------------------


def _lowered_tpu(fn, *args) -> str:
    """``fn`` lowered for the TPU on the CPU (no chip, no compile), with the
    op names as locations."""
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


def _kernel_names(text: str) -> list:
    return re.findall(r'kernel_name = "([\w.]+)"', text)


def _op_names(text: str) -> set:
    return set(re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M))


def _loss_args(kind):
    k, b, v, _ = LOSS_SHAPES["ofl-image"]
    c, w = jnp.ones((k, b, v)), jnp.full((k,), 1.0 / k)
    if kind == "ensemble_kl":
        return c, jnp.ones((b, v)), w
    return c, jnp.zeros((b,), I32), w


def _kernel_programs():
    """Each kernel alone, as its module's entry point calls it."""
    k, b, v, _ = LOSS_SHAPES["ofl-image"]
    g = jnp.ones((b,))
    bq, s, h, kh, hd = 2, 256, 4, 2, 64
    q, kv = jnp.ones((bq, s, h, hd), BF16), jnp.ones((bq, s, kh, hd), BF16)
    slots, w = 2, 4
    pages = jnp.ones((slots * w + 1, PAGE, DECODE_KV_HEADS, HEAD_DIM), BF16)
    return {
        "ensemble_kl_fwd": (lambda c, s_, w_: ensemble_kl_pallas(c, s_, w_, 4.0), _loss_args("ensemble_kl")),
        "ensemble_kl_bwd": (lambda c, s_, w_: ensemble_kl_bwd_pallas(c, s_, w_, g, g, g, g, 4.0), _loss_args("ensemble_kl")),
        "ghm_ce_fwd": (lambda c, y, w_: ghm_ce_pallas(c, y, w_), _loss_args("ghm_ce")),
        "ghm_ce_bwd": (lambda c, y, w_: ghm_ce_bwd_pallas(c, y, w_, g, g, g), _loss_args("ghm_ce")),
        "flash_attention_fwd": (flash_attention_pallas, (q, kv, kv)),
        "flash_attention_dq": (lambda *a: flash_attention_bwd_pallas(*a)[0],
                               (q, kv, kv, q, jnp.zeros((bq, s, h)), q)),
        "flash_attention_dkdv": (lambda *a: flash_attention_bwd_pallas(*a)[1:],
                                 (q, kv, kv, q, jnp.zeros((bq, s, h)), q)),
        "flash_decode": (lambda qd, kp, vp, t, n: flash_decode_pallas(qd, kp, vp, t, n),
                         (jnp.ones((slots, DECODE_HEADS, HEAD_DIM), BF16), pages, pages,
                          jnp.zeros((slots, w), I32), jnp.full((slots,), 8, I32))),
    }


KERNEL_NAMES = (
    "ensemble_kl_fwd", "ensemble_kl_bwd", "ghm_ce_fwd", "ghm_ce_bwd",
    "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkdv", "flash_decode",
)


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_kernel_is_named(name):
    """Each ``pallas_call`` carries a stable ``kernel_name``, and the name
    is a component of the kernel's op name, so a device trace tells the
    kernels apart."""
    fn, args = _kernel_programs()[name]
    text = _lowered_tpu(fn, *args)
    assert name in _kernel_names(text)
    assert any(re.search(rf"(^|/){name}/pallas_call$", n) for n in _op_names(text)), name


@pytest.mark.parametrize("kind", ["ensemble_kl", "ghm_ce"])
def test_loss_kernel_names_in_both_passes(kind):
    """Under ``jax.grad`` the forward kernel's op name holds ``<kind>_fwd``
    inside the forward pass (``jvp(...)``) and the backward kernel's holds
    ``<kind>_bwd`` inside the backward pass (``transpose(jvp(...))``)."""
    from repro.kernels.ensemble_kl.ops import _ensemble_kl_kernel
    from repro.kernels.ghm_ce.ops import _ghm_ce_kernel

    def loss(c, other, w):
        with jax.named_scope("loss"):
            if kind == "ensemble_kl":
                out = _ensemble_kl_kernel(c, other, w, 4.0, False, 8, 512)
            else:
                out = _ghm_ce_kernel(c, other, w, True, True, False, 8, 512)
            return jnp.mean(out)

    text = _lowered_tpu(jax.grad(loss, argnums=(0, 2)), *_loss_args(kind))
    assert sorted(_kernel_names(text)) == sorted([f"{kind}_bwd", f"{kind}_fwd"])
    names = _op_names(text)
    assert any(n.endswith(f"/jvp(loss)/{kind}_fwd/pallas_call") for n in names), names
    assert any(n.endswith(f"/transpose(jvp(loss))/{kind}_bwd/pallas_call") for n in names), names
