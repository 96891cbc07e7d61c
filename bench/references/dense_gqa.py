"""Plain float32 reference of a dense grouped-query-attention decoder with
ternary (BitNet b1.58) linears, as the configurations under
``bench/configs/`` state it.

The model, layer by layer:

    x   = embed[tokens]
    per layer:
        h = rmsnorm(x);  q, k, v = L_q(h), L_k(h), L_v(h)
        q, k = rope(q), rope(k)                 (rotate-half, theta per config)
        a = softmax(q k^T / sqrt(head_dim) + causal mask) v   (GQA groups)
        x = x + L_o(a)
        h = rmsnorm(x);  x = x + L_2(silu(L_1(h)) * L_3(h))
    logits = rmsnorm(x) @ head          (head = embed^T when tied)

where every ternary linear L is W1.58A8: the input is quantized per token
to int8 (scale max|x| / 127, round to nearest), multiplied exactly with the
ternary weight (absmean per output row: t = clip(round(w / mean|w|), -1, 1))
and rescaled by both scales.

The weights are not taken from the program: they are derived here from the
run's seed by the recipe the benchmark builds the served model with (normal
draws scaled by 1/sqrt(fan_in) and stored in bfloat16, the embedding scaled
by 0.02, norm scales 1). Every matmul runs at ``precision="highest"``,
except the integer-by-ternary product inside a ternary linear: its inputs
are exact in bfloat16 and its float32 sums are exact (``_exact_product``),
so one bfloat16 pass gives the float32 result bit for bit.

``lowp`` rounds every tensor that the served program keeps in bfloat16 to a
lower type (for the control run that must come out not correct); ``None``
keeps float32 throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS_SCALE = 1e-6
Q_MAX = 127.0
#: query rows a block of attention computes at once; sequences are padded
#: to a whole number of blocks
QUERY_BLOCK = 1024


def _linear_weight(key, k_in: int, m_out: int) -> jax.Array:
    w = jax.random.normal(key, (k_in, m_out), jnp.float32) * (1.0 / k_in ** 0.5)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _ternary(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(K, M) float weight → ternary (K, M) f32 and per-output scale (M,)."""
    scale = jnp.mean(jnp.abs(w), axis=0) + EPS_SCALE
    return jnp.clip(jnp.round(w / scale[None, :]), -1.0, 1.0), scale


def _layer_weights(key, cfg: dict) -> dict:
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    r = jax.random.split(key, 4)
    a = jax.random.split(r[0], 6)
    m = jax.random.split(r[2], 3)
    raw = {
        "q": _linear_weight(a[0], d, h * hd),
        "k": _linear_weight(a[1], d, kv * hd),
        "v": _linear_weight(a[2], d, kv * hd),
        "o": _linear_weight(a[3], h * hd, d),
        "w1": _linear_weight(m[0], d, f),
        "w3": _linear_weight(m[1], d, f),
        "w2": _linear_weight(m[2], f, d),
    }
    return {name: _ternary(w) for name, w in raw.items()}


def _round(x, lowp):
    return x if lowp is None else x.astype(lowp).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _exact_product(xq, t):
    """xq @ t for integers |xq| <= 127 and ternary t, both exact in
    bfloat16: every product is an integer under 128 and every sum over K
    inputs is one under 127 * K < 2**24, exact in float32 whatever the
    order of the additions."""
    if Q_MAX * xq.shape[-1] >= 2 ** 24:
        raise ValueError(f"K = {xq.shape[-1]}: float32 sums would round")
    return jnp.matmul(xq.astype(jnp.bfloat16), t.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _ternary_linear(x, tw, lowp):
    t, w_scale = tw
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    a_scale = jnp.maximum(amax, EPS_SCALE) / Q_MAX
    xq = jnp.clip(jnp.round(x / a_scale), -Q_MAX, Q_MAX)
    return _round(_exact_product(xq, t) * a_scale * w_scale[None, :], lowp)


def _rmsnorm(x, eps, lowp):
    return _round(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps), lowp)


def _rope(x, theta):
    """x: (S, H, D); positions 0..S-1."""
    s, _, dim = x.shape
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, lowp, block):
    """Causal GQA attention of q (S, H, D) over k, v (S, KV, D), in blocks of
    ``block`` query rows: each block's scores span only the keys at or
    before its last row, so a long sequence never holds (S, S) scores."""
    s, kv, hd = k.shape
    h = q.shape[1]
    qg = q.reshape(s, kv, h // kv, hd)
    out = []
    for i0 in range(0, s, block):
        i1 = min(i0 + block, s)
        scores = jnp.einsum("qkgd,skd->kgqs", qg[i0:i1], k[:i1],
                            precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        causal = (jnp.arange(i0, i1)[:, None] >= jnp.arange(i1)[None, :])
        p = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf),
                           axis=-1)
        out.append(jnp.einsum("kgqs,skd->qkgd", _round(p, lowp), v[:i1],
                              precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(out).reshape(s, h * hd)


def _layer(w, x, *, cfg, lowp, block=QUERY_BLOCK):
    s = x.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hn = _rmsnorm(x, eps, lowp)
    q = _round(_rope(_ternary_linear(hn, w["q"], lowp).reshape(s, h, hd), theta), lowp)
    k = _round(_rope(_ternary_linear(hn, w["k"], lowp).reshape(s, kv, hd), theta), lowp)
    v = _ternary_linear(hn, w["v"], lowp).reshape(s, kv, hd)
    att = _round(_attention(q, k, v, lowp, block), lowp)
    x = _round(x + _ternary_linear(att, w["o"], lowp), lowp)
    hn = _rmsnorm(x, eps, lowp)
    g = _round(jax.nn.silu(_ternary_linear(hn, w["w1"], lowp))
               * _ternary_linear(hn, w["w3"], lowp), lowp)
    return _round(x + _ternary_linear(g, w["w2"], lowp), lowp)


class Reference:
    """Logits of the configuration's model, from the seed's weights."""

    def __init__(self, cfg: dict, root_key: jax.Array, lowp=None):
        self.cfg = cfg
        self.root = root_key
        self.lowp = lowp
        self._weights = jax.jit(functools.partial(_layer_weights, cfg=cfg))
        self._layer = jax.jit(functools.partial(_layer, cfg=cfg, lowp=lowp))

    def _embedding(self) -> jax.Array:
        c = self.cfg
        t = jax.random.normal(jax.random.fold_in(self.root, 0),
                              (c["vocab_size"], c["hidden_size"]), jnp.float32)
        return (t.astype(jnp.bfloat16) * 0.02).astype(jnp.float32)

    def _head(self) -> jax.Array:
        """(d, vocab) f32."""
        c = self.cfg
        if c["tie_word_embeddings"]:
            return self._embedding().T
        return _linear_weight(jax.random.fold_in(self.root, 1),
                              c["hidden_size"], c["vocab_size"])

    def logits(self, seqs: list[tuple[np.ndarray, np.ndarray]]
               ) -> list[np.ndarray]:
        """For each (tokens (S,), positions (n,)) the logits (n, vocab) that
        follow tokens[:p+1] at each position p. Sequences are padded at the
        end to the longest of them, rounded up to a whole query block
        (causal: padding never reaches earlier rows), so one program serves
        them all."""
        c = self.cfg
        longest = max(len(tokens) for tokens, _ in seqs)
        length = -(-longest // QUERY_BLOCK) * QUERY_BLOCK
        embed = jax.jit(lambda t, ids: _round(t[ids], self.lowp))
        table = self._embedding()
        xs = []
        for tokens, _ in seqs:
            ids = np.zeros(length, np.int32)
            ids[:len(tokens)] = tokens
            xs.append(embed(table, jnp.asarray(ids)))
        del table
        stage = jax.random.fold_in(self.root, 100)
        layer_keys = jax.random.split(jax.random.fold_in(stage, 0),
                                      c["num_hidden_layers"])
        for li in range(c["num_hidden_layers"]):
            w = self._weights(layer_keys[li])
            xs = [self._layer(w, x) for x in xs]
            del w
        head = self._head()
        final = jax.jit(lambda x, pos, hw: _mm(
            _rmsnorm(x[pos], c["rms_norm_eps"], self.lowp), hw))
        out = []
        for x, (_, pos) in zip(xs, seqs):
            padded = np.zeros(-(-len(pos) // 64) * 64, np.int32)
            padded[:len(pos)] = pos
            out.append(np.asarray(final(x, jnp.asarray(padded), head))[:len(pos)])
        return out
