"""Public jit'd wrappers for every Pallas kernel.

Each compiles to Mosaic for the TPU.  ``interpret=True`` runs the Pallas
interpreter instead (how the tests check the kernels on the CPU); nothing
switches to it silently.
"""
from __future__ import annotations

from repro.kernels import (blackscholes as _bs, canneal as _ca,
                           decode_attention as _da, flash_attention as _fa,
                           jacobi2d as _j2, particlefilter as _pf,
                           pathfinder as _path, ssd_scan as _ssd,
                           streamcluster as _sc, swaptions as _sw)


def blackscholes(spot, strike, rate, vol, time, is_call, *, block=2048,
                 interpret=False):
    return _bs.blackscholes(spot, strike, rate, vol, time, is_call,
                            block=block, interpret=interpret)


def jacobi2d_step(a, *, rows_per_block=64, interpret=False):
    return _j2.jacobi2d_step(a, rows_per_block=rows_per_block,
                             interpret=interpret)


def pathfinder(wall, *, interpret=False):
    return _path.pathfinder(wall, interpret=interpret)


def streamcluster_dist(points, centers, *, bm=256, bn=256, interpret=False):
    return _sc.streamcluster_dist(points, centers, bm=bm, bn=bn,
                                  interpret=interpret)


def cum_normal_inv(u, *, block=2048, interpret=False):
    return _sw.cum_normal_inv(u, block=block, interpret=interpret)


def canneal_swap_cost(locs, fan_idx, cand_a, cand_b, *, block=256,
                      interpret=False):
    return _ca.swap_cost(locs, fan_idx, cand_a, cand_b, block=block,
                         interpret=interpret)


def particlefilter_findindex(cdf, u, *, bu=256, bc=2048, interpret=False):
    return _pf.find_index(cdf, u, bu=bu, bc=bc, interpret=interpret)


def flash_attention(q, k, v, *, bq=512, bk=512, causal=True, interpret=False):
    return _fa.flash_attention(q, k, v, bq=bq, bk=bk, causal=causal,
                               interpret=interpret)


def decode_attention(q, k, v, kv_len, *, bk=1024, interpret=False):
    return _da.decode_attention(q, k, v, kv_len, bk=bk, interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, chunk=256, interpret=False):
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=interpret)
