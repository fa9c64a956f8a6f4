"""Plain reference of the RiVEC suite's timing semantics.

It answers the same question as the simulator's timed path, an (app,
config) cell's steady-state loop-body time, whole-app vector runtime and
speedup over the scalar baseline, by the most direct route and with nothing
imported from the program:

* the seven RiVEC loop bodies (arXiv 2111.01949, section 4) are built here
  from their published per-chunk instruction mix, record by record;
* the decoupled vector engine's list-scheduler recurrence is scanned over the
  bare ``warmup + measure`` tiles of each body, one lane per cell: no
  chunking, no NOP padding, no batch buckets, no cache and no sharding; the
  time after the warm-up tiles is read at its exact record;
* the whole-app runtime and the scalar-pipeline baseline follow their closed
  forms in float64 and float32 numpy.

``dtype`` selects the precision of every float in the scan: float32 is the
reference, and bfloat16, the next precision below, is the control that the
comparison must reject.
"""
from __future__ import annotations

import math

import numpy as np

# instruction kinds, FU classes and access patterns of the trace records
SCALAR, VARITH, VLOAD, VSTORE, VSLIDE, VREDUCE, VMASK, VMOVE = range(8)
FU_SIMPLE, FU_MUL = 0, 1
MEM_UNIT, MEM_INDEXED = 0, 2

FIELDS = ("kind", "vl", "fu", "n_src", "src1", "src2", "dst", "mem_pattern",
          "footprint_kb", "scalar_count", "dep_scalar")
BYTES_PER_RECORD = 4 * len(FIELDS)   # one int32/float32 word per field

RING = 64                     # occupancy ring capacity
BODY_MAX = 1024               # longest body (particlefilter: 985 records)
BLOCK = 512                   # cells per reference dispatch

# calibrated latency classes (per FU class)
SCALAR_CYCLES = (1.1, 3.0, 20.0, 24.0)
VEC_PIPE_DEPTH = (2.0, 4.0, 8.0, 8.0)
VEC_ELEM_CYCLES = (1.0, 1.0, 2.0, 2.0)
SC_BLOCK_BRANCH_FRAC = 0.12
SC_BLOCK_BMISS_RATE = 0.08
DEFAULT_BRANCH_MISS_PENALTY = 6.0
FUSION_SIMPLE_SAVE = 0.15
# memory system
DRAM_MLP = 8.0
PREFETCH_DEPTH = 16.0


# --------------------------------------------------------------------------
# loop bodies
# --------------------------------------------------------------------------

def _rec(kind, vl=0, fu=FU_SIMPLE, n_src=2, src1=-1, src2=-1, dst=-1,
         mem_pattern=MEM_UNIT, footprint_kb=0.0, scalar_count=0,
         dep_scalar=False):
    return (kind, vl, fu, n_src, src1, src2, dst, mem_pattern, footprint_kb,
            scalar_count, dep_scalar)


def scalar(count, dep=False):
    return _rec(SCALAR, scalar_count=int(round(count)), dep_scalar=dep)


def arith(vl, fu, src1, src2, dst):
    return _rec(VARITH, vl, fu, 2, src1, src2, dst)


def load(vl, dst, fp, pattern=MEM_UNIT):
    return _rec(VLOAD, vl, n_src=0, dst=dst, mem_pattern=pattern,
                footprint_kb=fp)


def store(vl, src1, fp):
    return _rec(VSTORE, vl, n_src=1, src1=src1, footprint_kb=fp)


def slide(vl, src1, dst):
    return _rec(VSLIDE, vl, n_src=1, src1=src1, dst=dst)


def reduce_(vl, src1, dst):
    return _rec(VREDUCE, vl, n_src=1, src1=src1, dst=dst)


def mask(vl, src1):
    return _rec(VMASK, vl, n_src=1, src1=src1)


def move(vl, src1, dst):
    return _rec(VMOVE, vl, n_src=1, src1=src1, dst=dst)


def fu_classes(n, mix):
    """n arithmetic FU classes in the suite's fixed shuffled order: counts
    rounded per class (the residue on the simple class), shuffled by a
    RandomState seeded 0."""
    counts, acc = [], 0
    for name in ("simple", "mul", "div", "trans"):
        k = int(round(n * mix.get(name, 0.0)))
        counts.append(k)
        acc += k
    counts[0] += n - acc
    seq = []
    for cls, k in enumerate(counts):
        seq += [cls] * k
    np.random.RandomState(0).shuffle(seq)
    return seq


def chain(n, mix, vl, start=4, window=16):
    """n arithmetic records over a rotating window of registers."""
    return [arith(vl, cls, start + (i + 5) % window,
                  start + (i + 11) % window, start + i % window)
            for i, cls in enumerate(fu_classes(n, mix))]


BS_MIX = {"simple": 0.58, "mul": 0.36, "div": 0.04, "trans": 0.02}
J2_MIX = {"simple": 0.6, "mul": 0.4}
PF_MIX = {"simple": 0.50, "mul": 0.30, "div": 0.05, "trans": 0.15}
SC_MIX = {"simple": 0.5, "mul": 0.5}
SW_MIX = {"simple": 0.50, "mul": 0.35, "div": 0.05, "trans": 0.10}
SIMPLE = {"simple": 1.0}
CA_FAN = {6: 0.18, 8: 0.422, 12: 0.15, 14: 0.12, 16: 0.125, 20: 0.003}


def _bs(vl, cfg_mvl):
    fp = 65_536 * 27 * 8 / 1024
    return ([scalar(243.65)] + [load(vl, i % 4, fp) for i in range(22)]
            + chain(269, BS_MIX, vl)
            + [store(vl, 4 + i, fp) for i in range(5)])


def _ca(vl_max, cfg_mvl):
    vl = min(12, vl_max, cfg_mvl)
    recs = []
    for _ in range(2):
        recs += [move(cfg_mvl, i % 4, 8 + i % 4) for i in range(16)]
        for it in range(math.ceil(12 / vl)):
            recs.append(scalar(99.4 if it else 12))
            recs.append(load(vl, 0, 3072.0, MEM_INDEXED))
            recs.append(load(vl, 1, 3072.0, MEM_INDEXED))
            recs += chain(22, SIMPLE, vl)
        recs += [reduce_(vl, 6, 20), mask(vl, 20), scalar(820, dep=True)]
    return recs


def _j2(vl, cfg_mvl):
    fp = 2 * (13_056_000 / 4000 * 8) * 8 / 1024
    return ([scalar(87.16)] + [load(vl, i, fp) for i in range(4)]
            + [slide(vl, 0, 4), slide(vl, 0, 5)]
            + chain(20, J2_MIX, vl, start=6)
            + [slide(vl, 6, 20), slide(vl, 7, 21), slide(vl, 8, 22),
               store(vl, 20, fp)])


def _pf(vl, cfg_mvl):
    recs = [load(vl, 0, 781.0)] + chain(760, PF_MIX, vl)
    for _ in range(16):
        recs += chain(11, SIMPLE, vl)
        recs += [mask(vl, 5), mask(vl, 6), scalar(84, dep=True)]
    return recs


def _path(vl, cfg_mvl):
    row = 100_000 * 8 / 1024
    wall = 20_054_016 * 8 * 8 / 1024
    return [scalar(38.33), load(vl, 0, wall), load(vl, 1, row),
            load(vl, 2, row), slide(vl, 1, 3), slide(vl, 1, 4),
            arith(vl, FU_SIMPLE, 3, 1, 5), arith(vl, FU_SIMPLE, 5, 4, 6),
            arith(vl, FU_SIMPLE, 6, 0, 7), arith(vl, FU_SIMPLE, 7, 2, 8),
            slide(vl, 8, 9), slide(vl, 8, 10),
            arith(vl, FU_SIMPLE, 9, 10, 11), arith(vl, FU_SIMPLE, 11, 8, 12),
            load(vl, 13, row), store(vl, 12, row)]


def _sc(vl_max, cfg_mvl):
    vl = min(vl_max, 128, cfg_mvl)
    recs = []
    for i in range(math.ceil(128 / vl)):
        recs += [scalar(2.5), load(vl, i % 8, 768.0),
                 arith(vl, FU_MUL, i % 8, 8, 9 + i % 8)]
    return recs + [reduce_(vl, 9, 20), mask(vl, 20), scalar(30, dep=True)]


def _sw(vl, cfg_mvl):
    fp = vl * 8 * 350 / 1024
    return ([scalar(52.35)] + [load(vl, i, fp) for i in range(4)]
            + chain(24, SW_MIX, vl) + [store(vl, 10, fp)])


def _ca_iter_mult(mvl):
    return sum(p * math.ceil(f / mvl) for f, p in CA_FAN.items())


# name: (body, max_vl, chunks(mvl), residual scalar instrs(mvl),
#        scalar ROI instrs, element ops at MVL 8, FU mix)
APPS = {
    "blackscholes": (
        _bs, 10 ** 9, lambda m: 6_553_600 / m,
        lambda m: 285_041_709 + 243.65 * (6_553_600 / m),
        4_316_765_131, 296 * 6_553_600, BS_MIX),
    "canneal": (
        _ca, 22, lambda m: float(1_920_000),
        lambda m: 3_217_635_854 + 99.4 * (
            (_ca_iter_mult(m) - 1.0) * 2 * 1_920_000),
        5_239_983_271, 2_128_669_087 + 60_928_171 * 5.277, SIMPLE),
    "jacobi-2d": (
        _j2, 10 ** 9, lambda m: 13_056_000 * 8 / m,
        lambda m: 137_308_272 + 87.16 * (13_056_000 * 8 / m),
        4_797_698_032, 3_121_152_000 + 4000 * 8, J2_MIX),
    "particlefilter": (
        _pf, 10 ** 9, lambda m: 12_359_078_569 / m / 960,
        lambda m: 1_139_468_117 + 1.845e10 / m,
        20_232_505_095, 12_371_423_928 + 659_566 * 8, PF_MIX),
    "pathfinder": (
        _path, 10 ** 9, lambda m: 20_054_016 * 8 / m,
        lambda m: 268_401_305 + 38.33 * (20_054_016 * 8 / m),
        6_213_455_512, 2_406_481_920, SIMPLE),
    "streamcluster": (
        _sc, 128, lambda m: float(59_533_158),
        lambda m: 1_944_277_308 + 2.50 * (59_533_158 * math.ceil(128 / m)),
        36_068_326_139, 2 * 128 * 59_533_158 + 2 * 59_533_158 * 8, SC_MIX),
    "swaptions": (
        _sw, 10 ** 9, lambda m: 17_314_316_288 / m / 29,
        lambda m: 266_357_033 + 52.35 * (17_314_316_288 / m) / 29,
        26_846_776_223, 17_314_316_288, SW_MIX),
}

# scalar-code profile: branch, branch-miss, load, RAW, fusible fractions,
# memory stall cycles per load, share of the published count in the ROI
PROFILES = {
    "blackscholes": (0.10, 0.06, 0.22, 0.35, 0.30, 11.03, 1.0),
    "canneal": (0.18, 0.12, 0.28, 0.30, 0.20, 5.25, 1.0),
    "jacobi-2d": (0.08, 0.03, 0.30, 0.30, 0.30, 7.49, 1.0),
    "particlefilter": (0.14, 0.10, 0.22, 0.35, 0.25, 4.0, 0.0763),
    "pathfinder": (0.16, 0.10, 0.25, 0.35, 0.30, 5.73, 1.0),
    "streamcluster": (0.12, 0.08, 0.28, 0.30, 0.25, 4.31, 1.0),
    "swaptions": (0.10, 0.06, 0.20, 0.30, 0.30, 1.43, 1.0),
}


def effective_mvl(app, cfg):
    return min(cfg["mvl"], APPS[app][1])


_BODIES: dict = {}


def body(app, cfg):
    """The app's loop body at this config, as an (n, 11) table of records
    (memoized on what the body depends on: the clamped and the raw MVL)."""
    eff = effective_mvl(app, cfg)
    key = (app, eff, cfg["mvl"])
    out = _BODIES.get(key)
    if out is None:
        out = _BODIES[key] = APPS[app][0](eff, cfg["mvl"])
    return out


def body_len(app, cfg):
    return len(body(app, cfg))


def work_bytes(apps, knobs, warmup, measure):
    """Trace bytes one study must read: every distinct cell (the same body
    on the same engine parameters) scans ``warmup + measure`` tiles of its
    body once, at ``BYTES_PER_RECORD`` a record."""
    seen = set()
    records = 0
    for app in apps:
        for cfg in knobs:
            b = body(app, cfg)
            key = (tuple(b), tuple(params(cfg).values()))
            if key not in seen:
                seen.add(key)
                records += (warmup + measure) * len(b)
    return records * BYTES_PER_RECORD


# --------------------------------------------------------------------------
# the engine recurrence
# --------------------------------------------------------------------------

PARAMS = ("lanes", "phys_extra", "rob", "queue", "read_ports", "line_elems",
          "mem_ports", "lat_l1", "lat_l2", "lat_dram", "scalar_scale",
          "dispatch_lat", "ooo", "ring", "l1_kb", "l2_kb", "mshrs",
          "dram_line_cyc", "bmiss_extra", "fuse_save")
INT_PARAMS = ("phys_extra", "rob", "queue")


def params(cfg):
    """The engine's per-config parameters, as plain Python numbers."""
    freq_ratio = cfg["vector_freq_ghz"] / cfg["scalar_freq_ghz"]
    return {
        "lanes": cfg["lanes"], "phys_extra": cfg["phys_regs"] - 32,
        "rob": cfg["rob_entries"], "queue": cfg["queue_entries"],
        "read_ports": cfg["vrf_read_ports"],
        "line_elems": cfg["cache_line_bits"] / 64,
        "mem_ports": cfg["mem_ports"], "lat_l1": cfg["lat_l1"],
        "lat_l2": cfg["lat_l2"], "lat_dram": cfg["lat_dram"],
        "scalar_scale": freq_ratio / cfg["issue_width"],
        "dispatch_lat": cfg["dispatch_latency"],
        "ooo": 1.0 if cfg["ooo_issue"] else 0.0,
        "ring": 1.0 if cfg["interconnect"] == "ring" else 0.0,
        "l1_kb": cfg["l1_kb"], "l2_kb": cfg["l2_kb"], "mshrs": cfg["mshrs"],
        "dram_line_cyc": cfg["cache_line_bits"] / 8.0
        / cfg["dram_bw_bytes_cycle"],
        "bmiss_extra": (SC_BLOCK_BRANCH_FRAC * SC_BLOCK_BMISS_RATE
                        * (cfg["branch_miss_penalty"]
                           - DEFAULT_BRANCH_MISS_PENALTY) * freq_ratio),
        "fuse_save": FUSION_SIMPLE_SAVE if cfg["fusion"] else 0.0,
    }


def _lane_program(dtype):
    """Scan of one cell: ``warmup + measure`` tiles of its body, returning
    the time after the warm-up tiles and at the end."""
    import jax
    import jax.numpy as jnp

    f = jnp.dtype(dtype)
    sc_cost = jnp.asarray(SCALAR_CYCLES, f)
    pipe = jnp.asarray(VEC_PIPE_DEPTH, f)
    elem = jnp.asarray(VEC_ELEM_CYCLES, f)

    def ring_read(ring, count, cap):
        return jnp.where(count >= cap, ring[jnp.mod(count - cap, RING)],
                         f.type(0))

    def run(recs, n_body, warm_end, n_total, p, n_steps):
        (kind_a, vl_a, fu_a, nsrc_a, src1_a, src2_a, dst_a, mpat_a, fp_a,
         scount_a, dep_a) = recs

        def step(carry, i):
            (reg, rob_r, n_rob, phys_r, n_phys, aq_r, n_aq, mq_r, n_mq,
             t_sc, lane_free, vmu_free, last_aq, last_mq, last_commit,
             sres, t_warm) = carry
            j = jnp.mod(i, n_body)
            kind, vl, fu = kind_a[j], vl_a[j], fu_a[j]
            n_src, src1, src2, dst = nsrc_a[j], src1_a[j], src2_a[j], dst_a[j]
            mpat, fp, s_count, dep = mpat_a[j], fp_a[j], scount_a[j], dep_a[j]

            vlf = vl.astype(f)
            is_scalar = kind == SCALAR
            # scalar block
            t_wait = jnp.where(dep, jnp.maximum(t_sc, sres), t_sc)
            s_cf = s_count.astype(f)
            eff_cost = sc_cost[fu] * (1.0 - p["fuse_save"] * (fu == 0))
            sc_time = (s_cf * eff_cost * p["scalar_scale"]
                       + s_cf * p["bmiss_extra"])
            t_sc_s = t_wait + sc_time
            # vector instruction: dispatch, operands, FU, in-order gate
            t_sc_v = t_sc + sc_cost[0] * p["scalar_scale"]
            rob_slot = ring_read(rob_r, n_rob, p["rob"])
            phys_slot = ring_read(phys_r, n_phys, p["phys_extra"])
            is_mem = (kind == VLOAD) | (kind == VSTORE)
            q_slot = jnp.where(is_mem, ring_read(mq_r, n_mq, p["queue"]),
                               ring_read(aq_r, n_aq, p["queue"]))
            dispatch = jnp.maximum(
                jnp.maximum(t_sc_v + p["dispatch_lat"], rob_slot),
                jnp.maximum(phys_slot, q_slot))
            r1 = jnp.where(src1 >= 0, reg[jnp.maximum(src1, 0)], 0.0)
            r2 = jnp.where(src2 >= 0, reg[jnp.maximum(src2, 0)], 0.0)
            ops_ready = jnp.maximum(r1, r2)
            fu_free = jnp.where(is_mem, vmu_free, lane_free)
            inorder = jnp.where(is_mem, last_mq, last_aq)
            issue = jnp.maximum(jnp.maximum(dispatch, ops_ready), fu_free)
            issue = jnp.where(p["ooo"] > 0, issue,
                              jnp.maximum(issue, inorder))
            # execution
            startup = pipe[fu] + jnp.ceil(n_src.astype(f) / p["read_ports"])
            per_lane = jnp.ceil(vlf / p["lanes"])
            hops = jnp.where(p["ring"] > 0, p["lanes"] - 1.0,
                             jnp.ceil(jnp.log2(jnp.maximum(p["lanes"], 2.0))))
            # memory: capacity residency per cache, MSHR-gated gathers
            r1c = jnp.minimum(1.0, p["l1_kb"] / jnp.maximum(fp, 1e-6))
            r2c = jnp.minimum(1.0, p["l2_kb"] / jnp.maximum(fp, 1e-6))
            m1 = 1.0 - r1c
            m2 = jnp.clip((1.0 - r2c) / jnp.maximum(m1, 1e-6), 0.0, 1.0)
            ovl = jnp.where(mpat == MEM_INDEXED,
                            jnp.minimum(p["mshrs"], DRAM_MLP), PREFETCH_DEPTH)
            lead = p["lat_l1"] + (m1 * p["lat_l2"]
                                  + m1 * m2 * p["lat_dram"]) / ovl
            per_acc = jnp.maximum(
                1.0 / p["mem_ports"],
                jnp.maximum(m1 * p["lat_l2"] / ovl,
                            m1 * m2 * jnp.maximum(p["lat_dram"] / ovl,
                                                  p["dram_line_cyc"])))
            n_acc = jnp.where(mpat == MEM_UNIT,
                              jnp.ceil(vlf / p["line_elems"]), vlf)
            exec_mem = lead + n_acc * per_acc
            exec_c = jnp.select(
                [kind == VARITH, kind == VLOAD, kind == VSTORE,
                 kind == VSLIDE, kind == VREDUCE, kind == VMASK,
                 kind == VMOVE],
                [per_lane * elem[fu], exec_mem, exec_mem, per_lane + 1.0,
                 per_lane + hops + pipe[fu], per_lane + hops, per_lane], 0.0)
            complete = issue + startup + exec_c
            commit = jnp.maximum(complete, last_commit)

            vec = ~is_scalar
            vec_arith = vec & ~is_mem
            vec_mem = vec & is_mem
            one = jnp.int32(1)
            new = (
                jnp.where(vec & (dst >= 0),
                          reg.at[jnp.maximum(dst, 0)].set(complete), reg),
                jnp.where(vec, rob_r.at[jnp.mod(n_rob, RING)].set(commit),
                          rob_r),
                jnp.where(vec, n_rob + one, n_rob),
                jnp.where(vec, phys_r.at[jnp.mod(n_phys, RING)].set(commit),
                          phys_r),
                jnp.where(vec, n_phys + one, n_phys),
                jnp.where(vec_arith, aq_r.at[jnp.mod(n_aq, RING)].set(issue),
                          aq_r),
                jnp.where(vec_arith, n_aq + one, n_aq),
                jnp.where(vec_mem, mq_r.at[jnp.mod(n_mq, RING)].set(issue),
                          mq_r),
                jnp.where(vec_mem, n_mq + one, n_mq),
                jnp.where(is_scalar, t_sc_s, t_sc_v),
                jnp.where(vec_arith, complete, lane_free),
                jnp.where(vec_mem, complete, vmu_free),
                jnp.where(vec_arith, issue, last_aq),
                jnp.where(vec_mem, issue, last_mq),
                jnp.where(vec, commit, last_commit),
                jnp.where(vec & ((kind == VMASK) | (kind == VREDUCE)),
                          complete, sres),
            )
            live = i < n_total
            new = tuple(jnp.where(live, a, b) for a, b in zip(new, carry[:16]))
            t_warm = jnp.where(i == warm_end - 1,
                               jnp.maximum(new[9], new[14]), t_warm)
            return new + (t_warm,), None

        z = f.type(0)
        iz = jnp.int32(0)
        carry = (jnp.zeros(32, f), jnp.zeros(RING, f), iz,
                 jnp.zeros(RING, f), iz, jnp.zeros(RING, f), iz,
                 jnp.zeros(RING, f), iz, z, z, z, z, z, z, z, z)
        carry, _ = jax.lax.scan(step, carry,
                                jnp.arange(n_steps, dtype=jnp.int32))
        return carry[16], jnp.maximum(carry[9], carry[14])

    return run


_PROGRAMS: dict = {}


def _program(dtype, n_steps):
    import jax
    key = (str(dtype), n_steps)
    fn = _PROGRAMS.get(key)
    if fn is None:
        lane = _lane_program(dtype)

        def block(recs, n_body, warm_end, n_total, p):
            return jax.vmap(lambda r, nb, we, nt, pp:
                            lane(r, nb, we, nt, pp, n_steps))(
                recs, n_body, warm_end, n_total, p)

        fn = _PROGRAMS[key] = jax.jit(block)
    return fn


def _steps_bucket(n):
    b = 512
    while b < n:
        b *= 2
    return b


def steady_ns(cells, warmup, measure, dtype="float32", block=BLOCK):
    """Steady-state loop-body time (ns) of each ``(app, cfg)`` cell, in
    blocks of ``block`` cells that all scan as many steps as the longest
    cell needs (shorter ones stop early)."""
    import jax.numpy as jnp

    f = jnp.dtype(dtype)
    lens = [body_len(a, c) for a, c in cells]
    out = np.zeros(len(cells))
    if not cells:
        return out
    run = _program(f, _steps_bucket((warmup + measure) * max(lens)))
    block = min(block, max(8, 1 << (len(cells) - 1).bit_length()))
    for s in range(0, len(cells), block):
        part = list(range(s, min(s + block, len(cells))))
        rows = part + [part[0]] * (block - len(part))
        recs = np.zeros((len(FIELDS), block, BODY_MAX))
        for lane, k in enumerate(rows):
            b = np.asarray(body(*cells[k]), np.float64)
            recs[:, lane, :len(b)] = b.T
        args = (tuple(jnp.asarray(recs[i].astype(np.int32)) for i in range(8))
                + (jnp.asarray(recs[8], f),
                   jnp.asarray(recs[9].astype(np.int32)),
                   jnp.asarray(recs[10] != 0)))
        n_body = np.asarray([lens[k] for k in rows], np.int32)
        p = {name: jnp.asarray(
                 np.asarray([params(cells[k][1])[name] for k in rows]),
                 jnp.int32 if name in INT_PARAMS else f)
             for name in PARAMS}
        t_warm, t_end = run(args, jnp.asarray(n_body),
                            jnp.asarray(n_body * warmup),
                            jnp.asarray(n_body * (warmup + measure)), p)
        t_warm = np.asarray(t_warm.astype(jnp.float32), np.float64)
        t_end = np.asarray(t_end.astype(jnp.float32), np.float64)
        for lane, k in enumerate(part):
            out[k] = (float(t_end[lane]) - float(t_warm[lane])) / measure
    return out


# --------------------------------------------------------------------------
# whole-app runtime and the scalar baseline
# --------------------------------------------------------------------------

def runtime_ns(app, cfg, per_chunk):
    """chunks x per-chunk time plus the residual scalar instructions not
    inside any loop body, on the config's scalar core."""
    mk_body, _, chunks_of, scalar_instrs, *_ = APPS[app]
    mvl = effective_mvl(app, cfg)
    chunks = chunks_of(mvl)
    in_body = sum(r[9] for r in body(app, cfg))
    residual = max(scalar_instrs(mvl) - in_body * chunks, 0.0)
    res_scale = 1.0 / (cfg["scalar_freq_ghz"] * cfg["issue_width"])
    return float(chunks * per_chunk
                 + residual * float(np.float32(SCALAR_CYCLES[0])) * res_scale)


OP_LATENCY = (1.0, 4.0, 20.0, 24.0, 4.0, 1.0)
DIV_STRUCT_RATE = 0.25


def scalar_ns(app, cfg):
    """Scalar-version runtime (ns): issue slots, RAW, divider, branch-miss
    and load-stall cycles of six instruction classes, in float32."""
    _, _, _, _, roi_total, ops8, mix = APPS[app]
    br, bmr, ld, raw, fusible, mem, roi = PROFILES[app]
    n = roi_total * roi
    work = ops8 * roi
    n_branch, n_load = br * n, ld * n
    n_mul, n_div, n_trans = (work * mix.get(k, 0.0)
                             for k in ("mul", "div", "trans"))
    n_simple = max(n - n_branch - n_load - n_mul - n_div - n_trans, 0.0)
    f32 = np.float32
    counts = (n_simple, n_mul, n_div, n_trans, n_load, n_branch)
    issue_w = f32(cfg["issue_width"])
    bmp = f32(cfg["branch_miss_penalty"])
    fusion = f32(1.0 if cfg["fusion"] else 0.0)
    cyc = f32(0.0)
    for r in range(6):
        count = f32(counts[r])
        lat = f32(OP_LATENCY[r])
        fused = count * f32(fusible if r == 0 else 0.0) * fusion
        slots = (count - fused) / issue_w
        stall_lat = np.maximum(lat - f32(1.0), f32(0.0))
        raw_st = count * f32(raw) * stall_lat
        struct_st = count * f32(DIV_STRUCT_RATE if r == 2 else 0.0) * stall_lat
        bmiss_st = count * f32(bmr if r == 5 else 0.0) * bmp
        mem_st = count * f32(mem if r == 4 else 0.0)
        cyc = cyc + slots + raw_st + struct_st + bmiss_st + mem_st
    return float(cyc) / float(f32(cfg["scalar_freq_ghz"]))


def answers(cells, warmup, measure, dtype="float32"):
    """``(steady_ns, runtime_ns, speedup)`` arrays for ``(app, cfg)``
    cells."""
    steady = steady_ns(cells, warmup, measure, dtype)
    runtime = np.asarray([runtime_ns(a, c, s)
                          for (a, c), s in zip(cells, steady)])
    speedup = np.asarray([scalar_ns(a, c) / r
                          for (a, c), r in zip(cells, runtime)])
    return steady, runtime, speedup
