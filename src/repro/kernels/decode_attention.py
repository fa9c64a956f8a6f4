"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Grid over KV blocks with online-softmax scratch; the valid-length mask makes
it usable against partially-filled caches.  ``ref.decode_attention`` is
its pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, o_ref, acc_ref, m_ref, l_ref, *, bk):
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # [H, D]
    k = k_ref[0].astype(jnp.float32)          # [bk, H, D]
    v = v_ref[0].astype(jnp.float32)
    kv_len = len_ref[0]
    s = jnp.einsum("hd,khd->hk", q, k) * (q.shape[-1] ** -0.5)
    ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(ki < kv_len, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.einsum("hk,khd->hd", p, v)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, kv_len, *, bk: int = 1024, interpret: bool = False):
    """q [B,H,D]; k/v [B,S,H,D]; kv_len int32 [B] -> out [B,H,D]."""
    B, S, H, D = k.shape
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(B, S // bk),
        in_specs=[pl.BlockSpec((1, H, D), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, bk, H, D), lambda b, j: (b, j, 0, 0)),
                  pl.BlockSpec((1, bk, H, D), lambda b, j: (b, j, 0, 0)),
                  pl.BlockSpec((1,), lambda b, j: (b,))],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((H, D), jnp.float32),
                        pltpu.VMEM((H,), jnp.float32),
                        pltpu.VMEM((H,), jnp.float32)],
        interpret=interpret,
    )(q, k, v, lens)
