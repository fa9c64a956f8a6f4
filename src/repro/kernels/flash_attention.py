"""Causal flash attention Pallas kernel (fwd): online softmax, VMEM tiles.

Scores never leave VMEM (a chunked XLA attention spills [Sq, ck]-sized f32
tensors to HBM).  Block shapes are MXU-aligned (multiples of 128 on the
contracted dims).  ``ref.flash_attention`` is its pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, bq, bk, causal):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32)       # [bq, d]
        k = k_ref[0].astype(jnp.float32)       # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    if causal:
        # whole blocks above the diagonal are skipped (block-sparse causal)
        pl.when(kj * bk <= qi * bq + bq - 1)(_block)
    else:
        _block()

    @pl.when(kj == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "interpret"))
def flash_attention(q, k, v, *, bq: int = 512, bk: int = 512,
                    causal: bool = True, interpret: bool = False):
    """q/k/v [B,S,H,D] -> [B,S,H,D].  S % bq == S % bk == 0."""
    B, S, H, D = q.shape
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    # fold batch x heads into the leading grid dim
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal),
        grid=(B * H, S // bq, S // bk),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
