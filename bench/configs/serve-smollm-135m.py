"""Plain reference for ``serve-smollm-135m``: SmolLM-135M (a Llama
architecture) as its config.json describes it, in float32 ``jax.numpy``.

- ``make_weights(key, cfg)``: the weights the engine is handed, from the
  seed, in one jitted call on the device, float32 as published, laid out as
  the program's parameter tree (layers stacked on a leading axis).
- ``hidden(params, tokens, cfg, products)``: the final-normed hidden state
  at every position of one sequence: token embedding, then per layer
  RMSNorm, rotary GQA causal attention, residual, RMSNorm, SwiGLU MLP,
  residual; then the final RMSNorm. No cache, no kernels, no batching.
- ``logits(params, h, products)``: the tied head, ``h @ embed.T``.
- ``served_gaps`` / ``control_gaps``: the numbers a run compares (see
  ``benchlib/serve.py``).

``products`` is ``"highest"`` (exact float32 products: the reference) or
``"fp8"`` (both operands of every product, attention's included, rounded
to float8 e4m3 with one scale per tensor: the control, one precision below
the program's bfloat16).

Departures from the published model, none of them in the mathematics:

- RMSNorm weights are stored as ``w - 1`` (the program's layout); the
  reference applies ``x * rsqrt(mean(x^2) + eps) * (1 + s)``, which is the
  published ``x * rsqrt(mean(x^2) + eps) * w``;
- the query heads sharing key/value head ``j`` are ``j*g .. j*g+g-1``
  (``g = heads / kv_heads``), as ``repeat_kv`` orders them;
- rotary embedding rotates the two halves of each head (``rotate_half``),
  with inverse frequencies ``theta ** (-2i / head_dim)``.

The whole 2048-token sequence fits one call: its attention scores are 9 x
2048 x 2048 float32 per layer (151 MB), and the head is applied only to the
rows compared.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"], "H": heads,
            "KH": cfg["num_key_value_heads"], "hd": cfg["hidden_size"] // heads,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def make_weights(key, cfg: dict):
    """Fan-in scaled normal projections, embeddings of std 0.02, and RMSNorm
    weights ``1 + s`` with ``s`` of std 0.1, so the norms are exercised."""
    n = dims(cfg)
    L, d, H, KH, hd, F, V = (n[k] for k in ("L", "d", "H", "KH", "hd", "F", "V"))

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 11))

        def w(shape, fan_in):
            return jax.random.normal(next(ks), shape, jnp.float32) / np.sqrt(fan_in)

        def s(shape):
            return 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

        block = {
            "attn": {"wq": w((L, d, H, hd), d), "wk": w((L, d, KH, hd), d), "wv": w((L, d, KH, hd), d),
                     "wo": w((L, H, hd, d), H * hd)},
            "norm1": {"scale": s((L, d))},
            "mlp": {"wi": w((L, d, F), d), "wg": w((L, d, F), d), "wo": w((L, F, d), F)},
            "norm2": {"scale": s((L, d))},
        }
        return {"groups": {"p0": block}, "final_norm": {"scale": s((d,))},
                "embed": {"table": 0.02 * jax.random.normal(next(ks), (V, d), jnp.float32)}}

    return make(key)


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, products):
    if products == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + s)


def _rope(x, theta):
    """x: (S, heads, hd), rotated by position."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def hidden(params, tokens, cfg: dict, products: str = "highest"):
    """tokens: (S,) int32 -> (S, d) float32, after the final RMSNorm."""
    n = dims(cfg)
    g, hd, eps, theta = n["H"] // n["KH"], n["hd"], cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rms(x, p["norm1"]["scale"], eps)
        q = _rope(_mm("sd,dhk->shk", h, a["wq"], products), theta)
        k = _rope(_mm("sd,dhk->shk", h, a["wk"], products), theta)
        v = _mm("sd,dhk->shk", h, a["wv"], products)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = _mm("qhk,shk->hqs", q, k, products) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        o = _mm("hqs,shk->qhk", probs, v, products)
        x = x + _mm("qhk,hkd->qd", o, a["wo"], products)
        h = _rms(x, p["norm2"]["scale"], eps)
        up = _mm("sd,df->sf", h, m["wi"], products)
        gate = _mm("sd,df->sf", h, m["wg"], products)
        return x + _mm("sf,fd->sd", up * jax.nn.silu(gate), m["wo"], products), None

    x, _ = jax.lax.scan(layer, x, params["groups"]["p0"])
    return _rms(x, params["final_norm"]["scale"], eps)


def logits(params, h, products: str = "highest"):
    return _mm("rd,vd->rv", h, params["embed"]["table"], products)


def served_gaps(params, tokens, rows, served, cfg: dict):
    """For a sequence ``tokens`` (prompt then served tokens, padded) and the
    ``rows`` at which the served tokens ``served`` were chosen, how far each
    served token's reference logit lies below the reference's best."""
    ref = logits(params, hidden(params, tokens, cfg)[rows])
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]


def control_gaps(params, tokens, rows, cfg: dict, products: str = "fp8"):
    """The same gap for the token the control, the reference at ``products``,
    puts first at each row."""
    ref = logits(params, hidden(params, tokens, cfg)[rows])
    low = logits(params, hidden(params, tokens, cfg, products)[rows], products)
    first = jnp.argmax(low, axis=-1)
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
