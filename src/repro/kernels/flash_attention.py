"""Pallas TPU kernel: flash (IO-aware) self-attention.

Motivation (EXPERIMENTS.md §Perf): the XLA-level chunked attention
materializes per-chunk score tensors to HBM — the dominant memory term for
every train/prefill cell (e.g. internlm2 train_4k: ~0.9 of all traffic is
attention interior). This kernel keeps the (bq × bk) score tile, the running
max/sum and the output accumulator in VMEM scratch across the KV grid
dimension, so per-layer attention traffic drops to Q+K+V+O streaming.

Supports causal masking, sliding windows (gemma3), GQA (KV-head sharing via
the BlockSpec index map — no KV replication in HBM), and softcap. Validated
bit-close against models.attention.sdpa in interpret mode (tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, softcap: float,
    sq: int, sk: int, bq: int, bk: int, nk: int,
):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qb = q_ref[0, 0].astype(jnp.float32)                   # (bq, D)
    kb = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
    vb = v_ref[0, 0].astype(jnp.float32)                   # (bk, D)

    s = jax.lax.dot_general(
        qb, kb, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (bq, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = pl.program_id(2) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (q_pos < sq) & (k_pos < sk)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                    # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                 # (bq, bk)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, vb, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KV, Sk, D)
    v: jax.Array,  # (B, KV, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, kv, sk, _ = k.shape
    g = h // kv
    scale = d ** -0.5
    bq = min(bq, max(sq, 8))
    bk = min(bk, max(sk, 8))
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (sq + pq) // bq, (sk + pk) // bk

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, sq=sq, sk=sk, bq=bq, bk=bk, nk=nk,
        ),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            # GQA: query head h reads KV head h // g — no HBM replication
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j, g=g: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j, g=g: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :, :sq, :]


def flash_attention_bsnd(
    q: jax.Array,  # (B, Sq, H, D) — model layout
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    **kw,
) -> jax.Array:
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), **kw,
    )
    return out.transpose(0, 2, 1, 3)


# ----------------------------------------------------------------------------
# Differentiable wrapper: Pallas forward, reference backward.
#
# The backward re-derives gradients through the numerically-identical
# reference attention (recompute-style, like flash-attention's own backward
# recomputes p = softmax(qk) — here at XLA level rather than in a second
# kernel; a dedicated backward kernel is the next step and changes traffic,
# not semantics). This makes `attn_impl='flash'` usable in train_step today.
# ----------------------------------------------------------------------------
import functools as _functools


def _ref_attention(q, k, v, causal, window, softcap):
    b, h, sq, d = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32)) * (d ** -0.5)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, k.shape[2]), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, k.shape[2]), 1)
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return out.reshape(b, h, sq, d).astype(q.dtype)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_trainable(q, k, v, causal=True, window=0, softcap=0.0,
                              interpret=False):
    return flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=interpret,
    )


def _fa_fwd(q, k, v, causal, window, softcap, interpret):
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        interpret=interpret,
    )
    return out, (q, k, v)


def _fa_bwd(causal, window, softcap, interpret, res, dout):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal, window, softcap),
        q, k, v,
    )
    return vjp(dout)


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
