"""Pure-jnp oracles for every Pallas kernel (the ``ref`` side of allclose tests).

These are the RiVec suite apps (paper §4) re-expressed as array programs, plus
the attention and state-space kernels of the ML workloads.  Each function is
the semantic ground truth the corresponding ``pallas_call`` kernel must
reproduce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SQRT2 = 1.4142135623730951


def _cndf(x):
    return 0.5 * (1.0 + jax.lax.erf(x / SQRT2))


def blackscholes(spot, strike, rate, vol, time, is_call):
    """Black-Scholes option pricing (PARSEC blackscholes ROI)."""
    sqrt_t = jnp.sqrt(time)
    d1 = (jnp.log(spot / strike) + (rate + 0.5 * vol * vol) * time) / (vol * sqrt_t)
    d2 = d1 - vol * sqrt_t
    call = spot * _cndf(d1) - strike * jnp.exp(-rate * time) * _cndf(d2)
    put = strike * jnp.exp(-rate * time) * _cndf(-d2) - spot * _cndf(-d1)
    return jnp.where(is_call, call, put)


def jacobi2d(a, iters=1):
    """5-point Jacobi relaxation; boundary rows/cols held fixed."""
    for _ in range(iters):
        interior = 0.2 * (a[1:-1, 1:-1] + a[1:-1, :-2] + a[1:-1, 2:]
                          + a[:-2, 1:-1] + a[2:, 1:-1])
        a = a.at[1:-1, 1:-1].set(interior)
    return a


def pathfinder(wall):
    """Rodinia pathfinder: min-cost path, row by row (dynamic programming)."""
    def row(cost, w):
        left = jnp.pad(cost[:-1], (1, 0), constant_values=jnp.inf)
        right = jnp.pad(cost[1:], (0, 1), constant_values=jnp.inf)
        return w + jnp.minimum(cost, jnp.minimum(left, right)), None
    cost, _ = jax.lax.scan(row, wall[0].astype(jnp.float32), wall[1:])
    return cost


def streamcluster_dist(points, centers):
    """Pairwise squared euclidean distances [M,D]x[N,D] -> [M,N]."""
    p2 = jnp.sum(points.astype(jnp.float32) ** 2, -1, keepdims=True)
    c2 = jnp.sum(centers.astype(jnp.float32) ** 2, -1)
    pc = points.astype(jnp.float32) @ centers.astype(jnp.float32).T
    return jnp.maximum(p2 + c2[None, :] - 2.0 * pc, 0.0)


# Moro (1995) rational approximation of the inverse cumulative normal,
# as used by PARSEC swaptions' CumNormalInv.
_MORO_A = jnp.array([2.50662823884, -18.61500062529, 41.39119773534,
                     -25.44106049637])
_MORO_B = jnp.array([-8.47351093090, 23.08336743743, -21.06224101826,
                     3.13082909833])
_MORO_C = jnp.array([0.3374754822726147, 0.9761690190917186,
                     0.1607979714918209, 0.0276438810333863,
                     0.0038405729373609, 0.0003951896511919,
                     0.0000321767881768, 0.0000002888167364,
                     0.0000003960315187])


def cum_normal_inv(u):
    """Swaptions CumNormalInv (Moro's algorithm)."""
    x = u - 0.5
    r_c = x * x
    num = x * (_MORO_A[0] + r_c * (_MORO_A[1] + r_c * (_MORO_A[2] + r_c * _MORO_A[3])))
    den = 1.0 + r_c * (_MORO_B[0] + r_c * (_MORO_B[1] + r_c * (_MORO_B[2] + r_c * _MORO_B[3])))
    central = num / den
    rr = jnp.where(x > 0, 1.0 - u, u)
    rr = jnp.clip(rr, 1e-12, 0.5)
    z = jnp.log(-jnp.log(rr))
    tail = (_MORO_C[0] + z * (_MORO_C[1] + z * (_MORO_C[2] + z * (_MORO_C[3]
            + z * (_MORO_C[4] + z * (_MORO_C[5] + z * (_MORO_C[6]
            + z * (_MORO_C[7] + z * _MORO_C[8]))))))))
    tail = jnp.where(x > 0, tail, -tail)
    return jnp.where(jnp.abs(x) < 0.42, central, tail)


def canneal_swap_cost(locs, fan_idx, cand_a, cand_b):
    """Canneal swap_cost: manhattan routing cost of each element's fan
    against two candidate locations.

    locs [N,2]; fan_idx [B,F] (entries -1 = padding); cand_a/b [B,2].
    Returns (cost_a [B], cost_b [B]).
    """
    valid = fan_idx >= 0
    fl = locs[jnp.maximum(fan_idx, 0)].astype(jnp.float32)       # [B,F,2]
    da = jnp.abs(fl - cand_a[:, None, :].astype(jnp.float32)).sum(-1)
    db = jnp.abs(fl - cand_b[:, None, :].astype(jnp.float32)).sum(-1)
    va = jnp.where(valid, da, 0.0).sum(-1)
    vb = jnp.where(valid, db, 0.0).sum(-1)
    return va, vb


def particlefilter_findindex(cdf, u):
    """Rodinia particle filter guess-update: for each u_j, the first index i
    with cdf[i] >= u_j (the vfirst.m/vpopc.m pattern)."""
    counts = jnp.sum(cdf[None, :] < u[:, None], axis=1)
    return jnp.minimum(counts, cdf.shape[0] - 1).astype(jnp.int32)


def flash_attention(q, k, v, causal=True):
    """Exact softmax attention. q/k/v [B,S,H,D] -> [B,S,H,D]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", a.astype(q.dtype), v)


def decode_attention(q, k, v, kv_len):
    """Single-token attention vs cache. q [B,H,D], k/v [B,S,H,D], kv_len int."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhd,bkhd->bhk", q, k).astype(jnp.float32) * scale
    mask = jnp.arange(k.shape[1]) < kv_len
    s = jnp.where(mask[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", a.astype(q.dtype), v)


def _ssd_chunked(x, dt, A, Bm, Cm, D_skip, chunk):
    """SSD core.  x [B,S,H,P]; dt [B,S,H]; A [H]; Bm/Cm [B,S,N].

    Returns y [B,S,H,P] and final state [B,H,P,N].
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    f32 = jnp.float32

    dA = dt.astype(f32) * A.astype(f32)                      # [B,S,H] (negative)
    xd = x.astype(f32) * dt.astype(f32)[..., None]           # dt-weighted input
    # chunked views, scan axis leading
    rs = lambda t, d: t.reshape((Bsz, nc, Q) + t.shape[2:]).swapaxes(0, 1)
    dAc, xc = rs(dA, 3), rs(xd, 4)
    Bc, Cc = rs(Bm.astype(f32), 3), rs(Cm.astype(f32), 3)

    tri = jnp.tril(jnp.ones((Q, Q), f32))
    idx = jnp.arange(Q)

    def body(state, ch):
        dAq, xq, Bq, Cq = ch                                  # [B,Q,H], [B,Q,H,P], [B,Q,N]
        seg = jnp.cumsum(dAq, axis=1)                         # [B,Q,H]
        # intra-chunk: scores[t,u] = (C_t.B_u) * exp(seg_t - seg_u) for u<=t
        diff = seg[:, :, None] - seg[:, None, :, :]           # [B,Q,Q,H]
        diff = jnp.where(tri[None, :, :, None] > 0, diff, -jnp.inf)  # mask pre-exp
        decay = jnp.exp(diff)
        cb = jnp.einsum("btn,bun->btu", Cq, Bq)               # [B,Q,Q]
        y_intra = jnp.einsum("btu,btuh,buhp->bthp", cb, decay, xq)
        # contribution of carried-in state: y_state[t] = exp(seg_t) * C_t . state
        y_state = jnp.einsum("btn,bhpn,bth->bthp", Cq, state, jnp.exp(seg))
        # chunk end state: state' = exp(seg_Q) * state + sum_u exp(seg_Q-seg_u) B_u x_u
        tot = seg[:, -1]                                      # [B,H]
        sdecay = jnp.exp(tot[:, None] - seg)                  # [B,Q,H]
        state_new = (jnp.exp(tot)[:, :, None, None] * state
                     + jnp.einsum("bun,buhp,buh->bhpn", Bq, xq, sdecay))
        return state_new, y_intra + y_state

    state0 = jnp.zeros((Bsz, H, P, N), f32)
    state, yc = jax.lax.scan(body, state0, (dAc, xc, Bc, Cc))
    y = yc.swapaxes(0, 1).reshape(Bsz, S, H, P)
    y = y + x.astype(f32) * D_skip.astype(f32)[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_scan(x, dt, A, B, C, chunk):
    """Mamba-2 SSD reference: the chunked SSD algorithm with no skip term.

    x [b,S,H,P]; dt [b,S,H]; A [H]; B/C [b,S,N] -> y [b,S,H,P]."""
    y, _ = _ssd_chunked(x, dt, A, B, C, jnp.zeros(x.shape[2]), chunk)
    return y
