"""Cycle-level decoupled vector-engine timing model (paper §3) as a lax.scan.

The gem5 event-driven model is reformulated as a *list-scheduler recurrence*:
every instruction's issue time is the max over its structural and data
constraints (scalar-core frontier, rename/ROB/queue slot availability, operand
readiness, FU availability, in-order gate), and its completion feeds those
same resources forward.  Ring buffers in the scan carry give ROB / physical-
register / issue-queue occupancy exactly, so the model reproduces the paper's
first-order effects:

  * start-up time = FU pipe depth + ceil(n_src / VRF read ports)  (§3.2.4)
  * one arithmetic instruction in flight across all lanes         (§3.2.3)
  * VMU serialization: one memory instruction at a time           (§3.2.5)
  * analytic cache/MSHR/DRAM model: miss rates derived from each
    access's stream footprint and the cache geometry, MSHR-gated
    gather concurrency, shared DRAM bandwidth (repro.core.memory)  (§3.2.5)
  * ring vs crossbar interconnect cost for slides/reductions      (§3.2.6)
  * decoupling: scalar core runs ahead, queues absorb slack       (§3.1)
  * vfirst/vpopc results stall the scalar core                    (§4.1.4)

Times are in vector-engine cycles (1 GHz -> 1 cycle = 1 ns); the scalar core
runs at 2 GHz dual-issue with latency-class costs.

All config knobs — including issue policy and interconnect topology — are
traced values, so one compiled scan serves every configuration and the whole
model vmaps over a config axis: ``simulate_batch`` runs a multi-config sweep
(e.g. the paper's 24-point Table 10 grid x 7 apps) as a handful of XLA
dispatches, with traces NOP-padded to power-of-two length buckets so repeat
sweeps hit the jit cache.
"""
from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass, fields

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import isa
from repro.core import memory
from repro.core import registry

MAX_RING = 64  # static ring-buffer capacity (>= max rob/queue/phys-in-flight)


@dataclass(frozen=True)
class VectorEngineConfig:
    """Every knob of Table 10 (and §3.2) is a field here."""
    mvl: int = 256                 # max vector length, 64-bit elements
    lanes: int = 8
    phys_regs: int = 40            # >= 32 architectural
    rob_entries: int = 64
    queue_entries: int = 16        # per queue (arith / memory)
    ooo_issue: bool = False
    vrf_read_ports: int = 1
    vrf_line_bits: int = 512
    interconnect: str = "ring"     # "ring" | "crossbar"
    mem_ports: int = 1
    cache_line_bits: int = 512
    lat_l1: float = 4.0
    lat_l2: float = 12.0
    lat_dram: float = 100.0
    mshrs: int = 16
    l1_kb: int = 32
    l2_kb: int = 256
    # shared DRAM stream bandwidth (B/cycle); default is the calibrated
    # constant in repro.core.memory (single source of truth)
    dram_bw_bytes_cycle: float = memory.DRAM_BW_BYTES_PER_CYCLE
    scalar_freq_ghz: float = 2.0
    vector_freq_ghz: float = 1.0
    # scalar-core pipeline knobs (§3.1, repro.core.scalar_pipeline): the
    # issue width of the in-order scalar core, its branch mispredict penalty
    # (scalar-core cycles) and macro-op fusion.  They drive the event-based
    # scalar-baseline model AND the residual scalar blocks inside vectorized
    # code, so they are live batch axes like every other knob here.
    issue_width: int = 2
    branch_miss_penalty: float = 6.0
    fusion: bool = False
    dispatch_latency: float = 5.0  # scalar commit -> vector engine dispatch

    def __post_init__(self):
        """The scan's occupancy ring buffers are statically sized MAX_RING;
        a capacity beyond that silently wraps and corrupts every timing
        result, so reject it at construction."""
        for name, cap in (("rob_entries", self.rob_entries),
                          ("queue_entries", self.queue_entries),
                          ("phys_regs - 32", self.phys_regs - 32)):
            if cap > MAX_RING:
                raise ValueError(
                    f"{name}={cap} exceeds the engine ring capacity "
                    f"MAX_RING={MAX_RING}; raise engine.MAX_RING to model it")
        if self.phys_regs < 33:
            raise ValueError(
                f"phys_regs={self.phys_regs}: need >= 33 (32 architectural "
                "+ at least one rename register)")

    def label(self) -> str:
        """Result key: ``mvl{m}_l{l}`` plus one suffix per knob that differs
        from the Table-10 defaults — derived from the dataclass fields, so
        configs differing in *any* swept axis (LLC, MSHRs, DRAM bandwidth,
        ports, latencies, interconnect, ...) never collide.

        The label keys the DSE result cache (``repro.core.dse``), so float
        knobs must render round-trip exactly: ``%g`` keeps 6 significant
        digits, which would alias e.g. two ``dram_bw_bytes_cycle`` values
        differing in the 7th — those fall back to full-precision ``repr``.
        ``tests/test_dse.py`` asserts label uniqueness over ``SPACE_FULL``.
        """
        s = f"mvl{self.mvl}_l{self.lanes}"
        for f in fields(self):
            v = getattr(self, f.name)
            if f.name in ("mvl", "lanes") or v == f.default:
                continue
            if f.name == "ooo_issue":
                s += "_ooo"
            elif f.name == "fusion":
                s += "_fusion"
            elif f.name == "interconnect":
                s += f"_{v}"
            else:
                r = f"{v:g}"
                if isinstance(v, float) and float(r) != v:
                    r = repr(v)
                s += f"_{f.name}{r}"
        return s


# Calibrated latency classes (fit against the paper's §5 speedup anchors; see
# benchmarks/calibrate.py provenance).  Scalar: effective dependent-chain
# cycles per instruction at 2 GHz.  Vector: FU pipe depth (start-up) and
# per-element throughput cost in cycles/element/lane.
SCALAR_CYCLES = np.array([1.1, 3.0, 20.0, 24.0], np.float32)   # per FU class
VEC_PIPE_DEPTH = np.array([2.0, 4.0, 8.0, 8.0], np.float32)
VEC_ELEM_CYCLES = np.array([1.0, 1.0, 2.0, 2.0], np.float32)

# Residual scalar blocks inside vectorized code run on the same scalar core
# the baseline does, so the scalar-pipeline knobs perturb them too.
# SCALAR_CYCLES are effective per-class costs at the DEFAULT core (6-cycle
# mispredict penalty, no fusion); the knobs contribute a *delta* around that
# default — exactly zero at the Table-10 defaults, so default-config vector
# timings are bitwise-unchanged by the knobs' existence.
SC_BLOCK_BRANCH_FRAC = 0.12    # branches per residual scalar instruction
SC_BLOCK_BMISS_RATE = 0.08     # mispredict rate of those branches
DEFAULT_BRANCH_MISS_PENALTY = 6.0
FUSION_SIMPLE_SAVE = 0.15      # simple-class cycles removed by macro-op fusion


# --------------------------------------------------------------------------
# cycle attribution (the collect_stats scan variant)
# --------------------------------------------------------------------------
# Every stall/execution cause the profiling scan attributes cycles to, in
# accumulator order.  The attribution is a *frontier decomposition*: the
# running completion frontier ``F = max(t_scalar, last_commit)`` is monotone,
# and each scan step's advance ``F_new - F_old`` is split into the wait that
# delayed issue (attributed to the binding constraint — the argmax of the
# issue equation), the execution time visible beyond the frontier (attributed
# to the executing module), and scalar-pipe work.  Summing the accumulators
# therefore reconstructs ``time`` exactly (float32 association aside) — the
# event-sum identity ``python -m repro.core.telemetry --smoke`` enforces.
STALL_KINDS = (
    "scalar_work",   # scalar-block work + scalar pipe carrying vector instrs
    "dep_scalar",    # visible cycles of scalar blocks consuming a vector->
                     # scalar result (coupling round-trips on critical path)
    "dispatch",      # issue gated by the scalar frontier + dispatch latency
    "rob_full",      # structural: no free ROB entry
    "phys_full",     # structural: no free physical (rename) register
    "aq_full",       # structural: arithmetic issue queue full
    "mq_full",       # structural: memory issue queue full
    "raw",           # RAW wait on a vector register operand
    "lane_wait",     # lane FU busy with an earlier arithmetic instruction
    "vmu_wait",      # VMU busy with an earlier memory instruction
    "inorder",       # in-order issue gate (older instr not yet issued)
    "exec_simple",   # visible execution: VARITH per FU class
    "exec_mul",
    "exec_div",
    "exec_trans",
    "exec_interconnect",  # visible execution: slides / reductions
    "exec_mask",          # visible execution: vfirst/vpopc mask->scalar
    "exec_move",          # visible execution: whole-register moves
    "exec_mem",           # visible execution: memory access (VMU) cycles
)
N_STALL = len(STALL_KINDS)
_S = {k: i for i, k in enumerate(STALL_KINDS)}


def _ring_read(ring, count, capacity):
    """Time at which the slot for the `count`-th allocation frees (0 if never
    yet full): value written `capacity` allocations ago."""
    idx = jnp.mod(count - capacity, MAX_RING)
    return jnp.where(count >= capacity, ring[idx], 0.0)


def _ring_write(ring, count, value):
    return ring.at[jnp.mod(count, MAX_RING)].set(value)


def _make_step(params, collect: bool = False):
    """Build the per-instruction scan step for one parameter vector.

    Everything configuration-dependent — including the formerly-static
    ``ooo``/``ring`` flags — is a traced value, so a single compiled
    executable serves every config and the step vmaps cleanly over a batch
    axis (``simulate_batch``).

    ``collect`` (a Python-level flag, resolved at trace time) appends the
    cycle-attribution accumulators (``STALL_KINDS`` vector + per-FU lane
    occupancy) to the carry and emits per-record ``(start, issue, complete,
    cause)`` outputs for timeline export.  With ``collect=False`` the traced
    jaxpr is the pre-profiler one — the default path stays bitwise-identical
    and keyed on the same executables.
    """
    (lanes, phys_extra, rob_entries, q_entries, read_ports, line_elems,
     mem_ports, lat_l1, lat_l2, lat_dram, scalar_scale, dispatch_lat,
     ooo_f, ring_f, l1_kb, l2_kb, mshrs_f, dram_line_cyc,
     bmiss_extra, fuse_save) = params
    sc_cost = jnp.asarray(SCALAR_CYCLES)
    pipe_depth = jnp.asarray(VEC_PIPE_DEPTH)
    elem_cost = jnp.asarray(VEC_ELEM_CYCLES)

    def step(carry, x):
        if collect:
            (reg_ready, rob_ring, n_rob, phys_ring, n_phys, aq_ring, n_aq,
             mq_ring, n_mq, t_scalar, lane_free, vmu_free, last_aq, last_mq,
             last_commit, scalar_res, busy_lane, busy_vmu,
             stall_acc, occ_fu) = carry
        else:
            (reg_ready, rob_ring, n_rob, phys_ring, n_phys, aq_ring, n_aq,
             mq_ring, n_mq, t_scalar, lane_free, vmu_free, last_aq, last_mq,
             last_commit, scalar_res, busy_lane, busy_vmu) = carry
        kind, vl, fu, n_src, src1, src2, dst, mpat, fp_kb, s_count, dep = x

        vlf = vl.astype(jnp.float32)
        # NOP padding rides the scalar path with s_count=0 / dep=False: it
        # advances no clock and writes no resource (padding invariance).
        is_scalar = (kind == isa.SCALAR_BLOCK) | (kind == isa.NOP)

        # ---- scalar block ---------------------------------------------------
        # per-class cost with the scalar-pipeline knob deltas: macro-op
        # fusion trims simple-class cycles, a non-default mispredict penalty
        # adds/removes branch-miss cycles per instruction.  Both deltas are
        # exactly 0.0 at the Table-10 defaults (bitwise-neutral).
        t_wait = jnp.where(dep, jnp.maximum(t_scalar, scalar_res), t_scalar)
        s_cf = s_count.astype(jnp.float32)
        eff_cost = sc_cost[fu] * (1.0 - fuse_save * (fu == 0))
        sc_time = s_cf * eff_cost * scalar_scale + s_cf * bmiss_extra
        t_scalar_s = t_wait + sc_time

        # ---- vector instruction --------------------------------------------
        # scalar pipe cost of carrying the vector instruction to commit
        t_scalar_v = t_scalar + sc_cost[0] * scalar_scale
        rob_slot = _ring_read(rob_ring, n_rob, rob_entries)
        phys_slot = _ring_read(phys_ring, n_phys, phys_extra)
        is_mem = (kind == isa.VLOAD) | (kind == isa.VSTORE)
        q_slot = jnp.where(is_mem,
                           _ring_read(mq_ring, n_mq, q_entries),
                           _ring_read(aq_ring, n_aq, q_entries))
        dispatch = jnp.maximum(jnp.maximum(t_scalar_v + dispatch_lat, rob_slot),
                               jnp.maximum(phys_slot, q_slot))

        r1 = jnp.where(src1 >= 0, reg_ready[jnp.maximum(src1, 0)], 0.0)
        r2 = jnp.where(src2 >= 0, reg_ready[jnp.maximum(src2, 0)], 0.0)
        ops_ready = jnp.maximum(r1, r2)

        fu_free = jnp.where(is_mem, vmu_free, lane_free)
        inorder = jnp.where(is_mem, last_mq, last_aq)
        issue = jnp.maximum(jnp.maximum(dispatch, ops_ready), fu_free)
        issue = jnp.where(ooo_f > 0, issue, jnp.maximum(issue, inorder))

        # start-up: pipe depth + VRF read-port serialization (§3.2.4)
        startup = pipe_depth[fu] + jnp.ceil(
            n_src.astype(jnp.float32) / read_ports)

        per_lane = jnp.ceil(vlf / lanes)
        exec_arith = per_lane * elem_cost[fu]
        # slides move each element one lane over: one extra hop either topology
        exec_slide = per_lane + 1.0
        hops = jnp.where(ring_f > 0, lanes - 1.0,
                         jnp.ceil(jnp.log2(jnp.maximum(lanes, 2.0))))
        exec_reduce = per_lane + hops + pipe_depth[fu]
        exec_move = per_lane
        exec_mask = per_lane + hops  # vfirst/vpopc reduce a mask to a scalar

        # analytic memory hierarchy (§3.2.5): miss probabilities derived from
        # the access's stream footprint x the cache geometry, MSHR-limited
        # miss overlap, and a shared DRAM bandwidth term — all traced, so the
        # LLC/MSHR knobs are live batch axes (repro.core.memory)
        exec_mem = memory.vector_access_cycles(
            vlf, mpat, fp_kb, line_elems, l1_kb, l2_kb, mshrs_f,
            lat_l1, lat_l2, lat_dram, dram_line_cyc, mem_ports)

        exec_c = jnp.select(
            [kind == isa.VARITH, kind == isa.VLOAD, kind == isa.VSTORE,
             kind == isa.VSLIDE, kind == isa.VREDUCE, kind == isa.VMASK_SCALAR,
             kind == isa.VMOVE],
            [exec_arith, exec_mem, exec_mem, exec_slide, exec_reduce,
             exec_mask, exec_move], 0.0)

        complete = issue + startup + exec_c
        commit = jnp.maximum(complete, last_commit)

        # ---- merge scalar/vector outcomes -----------------------------------
        t_scalar_n = jnp.where(is_scalar, t_scalar_s, t_scalar_v)
        upd = lambda old, new: jnp.where(is_scalar, old, new)

        reg_ready_n = jnp.where(
            is_scalar | (dst < 0), reg_ready,
            reg_ready.at[jnp.maximum(dst, 0)].set(complete))
        rob_ring_n = jnp.where(is_scalar, rob_ring,
                               _ring_write(rob_ring, n_rob, commit))
        phys_ring_n = jnp.where(is_scalar, phys_ring,
                                _ring_write(phys_ring, n_phys, commit))
        aq_ring_n = jnp.where(is_scalar | is_mem, aq_ring,
                              _ring_write(aq_ring, n_aq, issue))
        mq_ring_n = jnp.where(is_scalar | ~is_mem, mq_ring,
                              _ring_write(mq_ring, n_mq, issue))
        one = jnp.int32(1)
        carry_n = (
            reg_ready_n, rob_ring_n, upd(n_rob, n_rob + one),
            phys_ring_n, upd(n_phys, n_phys + one),
            aq_ring_n, upd(n_aq, jnp.where(is_mem, n_aq, n_aq + one)),
            mq_ring_n, upd(n_mq, jnp.where(is_mem, n_mq + one, n_mq)),
            t_scalar_n,
            upd(lane_free, jnp.where(is_mem, lane_free, complete)),
            upd(vmu_free, jnp.where(is_mem, complete, vmu_free)),
            upd(last_aq, jnp.where(is_mem, last_aq, issue)),
            upd(last_mq, jnp.where(is_mem, issue, last_mq)),
            upd(last_commit, commit),
            # vfirst/vpopc AND reductions deliver their result to the scalar
            # core (vfred* + vfmv.f.s): a later dep_scalar block waits on it
            upd(scalar_res,
                jnp.where((kind == isa.VMASK_SCALAR) | (kind == isa.VREDUCE),
                          complete, scalar_res)),
            busy_lane + jnp.where(is_scalar | is_mem, 0.0, startup + exec_c),
            busy_vmu + jnp.where(is_mem, startup + exec_c, 0.0),
        )
        if not collect:
            return carry_n, None

        # ---- cycle attribution (collect_stats only) -------------------------
        # Frontier decomposition: F = max(t_scalar, last_commit) is monotone;
        # this step advances it by delta = F_new - F_old, which is split
        # exactly (real arithmetic) into wait/exec/scalar pieces below — so
        # sum(stall_acc) == final time to float32 association tolerance.
        f_old = jnp.maximum(t_scalar, last_commit)
        # scalar block: the raw wait on a pending vector->scalar result is
        # always frontier-hidden (scalar_res <= last_commit <= F), so the
        # coupling cost surfaces as the dep block's *visible work* — route
        # it to dep_scalar instead of scalar_work when dep is set
        dep_vis = jnp.maximum(t_wait - f_old, 0.0)
        work_vis = jnp.maximum(t_scalar_s - jnp.maximum(t_wait, f_old), 0.0)
        sc_idx = jnp.where(dep, _S["dep_scalar"], _S["scalar_work"])
        # vector instruction: issue wait goes to the binding constraint of
        # the issue equation (structural fulls take precedence on ties, then
        # operand RAW, FU busy, the in-order gate; scalar-frontier dispatch
        # is the catch-all — issue is the max of exactly these candidates)
        cause = jnp.select(
            [issue == rob_slot, issue == phys_slot, issue == q_slot,
             issue == ops_ready, issue == fu_free,
             (ooo_f <= 0) & (issue == inorder)],
            [jnp.int32(_S["rob_full"]), jnp.int32(_S["phys_full"]),
             jnp.where(is_mem, _S["mq_full"], _S["aq_full"]),
             jnp.int32(_S["raw"]),
             jnp.where(is_mem, _S["vmu_wait"], _S["lane_wait"]),
             jnp.int32(_S["inorder"])],
            jnp.int32(_S["dispatch"]))
        exec_idx = jnp.select(
            [is_mem,
             (kind == isa.VSLIDE) | (kind == isa.VREDUCE),
             kind == isa.VMASK_SCALAR,
             kind == isa.VMOVE],
            [jnp.int32(_S["exec_mem"]), jnp.int32(_S["exec_interconnect"]),
             jnp.int32(_S["exec_mask"]), jnp.int32(_S["exec_move"])],
            jnp.int32(_S["exec_simple"]) + fu)
        wait_vis = jnp.maximum(issue - f_old, 0.0)
        exec_vis = jnp.maximum(complete - jnp.maximum(issue, f_old), 0.0)
        # scalar pipe running ahead of the engine: visible scalar work
        tail_vis = jnp.maximum(t_scalar_v - jnp.maximum(complete, f_old), 0.0)

        zero_vec = jnp.zeros((N_STALL,), jnp.float32)
        sc_delta = (zero_vec.at[_S["dep_scalar"]].add(dep_vis)
                    .at[sc_idx].add(work_vis))
        vec_delta = (zero_vec.at[cause].add(wait_vis)
                     .at[exec_idx].add(exec_vis)
                     .at[_S["scalar_work"]].add(tail_vis))
        stall_n = stall_acc + jnp.where(is_scalar, sc_delta, vec_delta)
        occ_n = occ_fu.at[fu].add(
            jnp.where(is_scalar | is_mem, 0.0, startup + exec_c))

        # per-record timeline spans: scalar (start, wait-end, work-end);
        # vector (scalar-commit, issue, complete)
        ys = (jnp.where(is_scalar, t_scalar, t_scalar_v),
              jnp.where(is_scalar, t_wait, issue),
              jnp.where(is_scalar, t_scalar_s, complete),
              jnp.where(is_scalar,
                        jnp.where(dep, _S["dep_scalar"], _S["scalar_work"]),
                        cause).astype(jnp.int32))
        return carry_n + (stall_n, occ_n), ys

    return step


def _init_carry():
    zero = jnp.float32(0.0)
    izero = jnp.int32(0)
    return (jnp.zeros(32, jnp.float32), jnp.zeros(MAX_RING, jnp.float32), izero,
            jnp.zeros(MAX_RING, jnp.float32), izero,
            jnp.zeros(MAX_RING, jnp.float32), izero,
            jnp.zeros(MAX_RING, jnp.float32), izero,
            zero, zero, zero, zero, zero, zero, zero, zero, zero)


def _metrics(carry) -> dict:
    t_scalar, last_commit = carry[9], carry[14]
    return {
        "time": jnp.maximum(t_scalar, last_commit),
        "t_scalar": t_scalar,
        "t_last_commit": last_commit,
        "lane_busy": carry[16],
        "vmu_busy": carry[17],
    }


def _init_carry_stats():
    return _init_carry() + (jnp.zeros(N_STALL, jnp.float32),
                            jnp.zeros(4, jnp.float32))


# Each scan program counts one ``engine.traces`` in its Python body, which
# runs once per jit cache miss (``jit_cache_size``).
def _scan_core(xs, params):
    """One trace x one config, full-length scan -> timing dict."""
    registry.count("engine.traces")
    carry, _ = jax.lax.scan(_make_step(params), _init_carry(), xs)
    return _metrics(carry)


def _profile_core(xs, params):
    """The collect_stats scan: same step arithmetic plus the attribution
    accumulators and per-record timeline outputs.  One extra jit key total
    (``_profile_jit``); pure jnp, so it vmaps like the default core."""
    registry.count("engine.traces")
    carry, ys = jax.lax.scan(_make_step(params, collect=True),
                             _init_carry_stats(), xs)
    out = _metrics(carry)
    out["stalls"] = carry[18]
    out["occ_lane_fu"] = carry[19]
    return out, ys


def _chunk_core(carry, xs, params):
    """One fixed-size chunk of the scan, resumable: threading the carry
    through repeated calls is exactly the full scan, but every trace length
    reuses the same (batch, CHUNK)-shaped executable instead of compiling
    per length — the jit-cache memoization that makes repeat sweeps cheap.
    The sharded program traces it too, so its count covers both."""
    registry.count("engine.traces")
    carry, _ = jax.lax.scan(_make_step(params), carry, xs)
    return carry


_simulate_jit = jax.jit(_scan_core)
_chunk_batch_jit = jax.jit(jax.vmap(_chunk_core))
_profile_jit = jax.jit(_profile_core)


# device count -> (sharded program, the sharding of its inputs)
_SHARDED_JITS: dict[int, tuple] = {}


def _sharded_chunk_program(mesh):
    """The batched chunk scan sharded over the ``cfg`` axis of ``mesh``:
    an SPMD wrapper around the same vmapped ``_chunk_core``, so each device
    scans its slice of the batch and results are indistinguishable from the
    single-device path (the per-lane scan arithmetic is shared)."""
    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(jax.vmap(_chunk_core), mesh=mesh,
                                 in_specs=P("cfg"), out_specs=P("cfg")))


def _sharded(batch_bucket: int):
    """The sharded program and where its inputs live (the batch axis split
    over the ``cfg`` axis of a mesh of the local devices), built once per
    device count.  ``None`` where the single-device program runs: on one
    device, or for a power-of-two batch bucket the device count does not
    divide."""
    ndev = jax.local_device_count()
    if ndev == 1 or batch_bucket % ndev:
        return None
    if ndev not in _SHARDED_JITS:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        # local_devices, not devices: in a multi-process job the mesh must
        # hold only this process's addressable devices
        mesh = Mesh(np.asarray(jax.local_devices()[:ndev]), ("cfg",))
        _SHARDED_JITS[ndev] = (_sharded_chunk_program(mesh),
                               NamedSharding(mesh, P("cfg")))
    return _SHARDED_JITS[ndev]


def _dispatch_chunk_batch(carry, xs, params, batch_bucket: int):
    """Dispatch one chunk of the batched scan, sharding the config axis
    across local devices when there is more than one (and the power-of-two
    batch bucket divides evenly); otherwise the single-device vmapped path.

    This is the DSE sharding contract (docs/architecture.md): the config
    axis is embarrassingly parallel — no collectives cross the shard
    boundary — so a many-config sweep scales with device count while the
    one-device fallback keeps every existing caller bitwise unchanged.
    """
    sharded = _sharded(batch_bucket)
    program = _chunk_batch_jit if sharded is None else sharded[0]
    return program(carry, xs, params)

# Batched traces are NOP-padded to multiples of CHUNK and scanned chunk by
# chunk; the compilation key is (batch bucket, CHUNK) only.
CHUNK = 1024

_TRACE_FIELDS = ("kind", "vl", "fu", "n_src", "src1", "src2", "dst",
                 "mem_pattern", "footprint_kb", "scalar_count",
                 "dep_scalar")


def _trace_xs(trace: isa.Trace) -> tuple:
    return tuple(jnp.asarray(getattr(trace, f)) for f in _TRACE_FIELDS)


def _cfg_params_np(cfg: VectorEngineConfig) -> tuple:
    """Per-config parameter vector (np scalars: stackable for the batch axis)."""
    freq_ratio = cfg.vector_freq_ghz / cfg.scalar_freq_ghz
    scalar_scale = freq_ratio / cfg.issue_width
    # knob deltas around the default core (zero at defaults; see the
    # SC_BLOCK_* constants): extra vector-cycles per residual scalar instr
    # from a non-default mispredict penalty, and the fused simple-class save
    bmiss_extra = (SC_BLOCK_BRANCH_FRAC * SC_BLOCK_BMISS_RATE
                   * (cfg.branch_miss_penalty - DEFAULT_BRANCH_MISS_PENALTY)
                   * freq_ratio)
    fuse_save = FUSION_SIMPLE_SAVE if cfg.fusion else 0.0
    return (
        np.float32(cfg.lanes), np.int32(cfg.phys_regs - 32),
        np.int32(cfg.rob_entries), np.int32(cfg.queue_entries),
        np.float32(cfg.vrf_read_ports), np.float32(cfg.cache_line_bits / 64),
        np.float32(cfg.mem_ports), np.float32(cfg.lat_l1),
        np.float32(cfg.lat_l2), np.float32(cfg.lat_dram),
        np.float32(scalar_scale), np.float32(cfg.dispatch_latency),
        np.float32(1.0 if cfg.ooo_issue else 0.0),
        np.float32(1.0 if cfg.interconnect == "ring" else 0.0),
        np.float32(cfg.l1_kb), np.float32(cfg.l2_kb), np.float32(cfg.mshrs),
        np.float32(memory.dram_line_cycles(cfg.cache_line_bits,
                                           cfg.dram_bw_bytes_cycle)),
        np.float32(bmiss_extra), np.float32(fuse_save),
    )


# Bump when the scan-step arithmetic changes in a way the calibration
# constants below don't capture (new resource model, different recurrence):
# it invalidates every persistent DSE cache entry.
# v2: scalar-pipeline knobs (issue_width / branch_miss_penalty / fusion)
# entered the parameter vector and the scalar-block cost expression.
MODEL_VERSION = 2


def model_fingerprint() -> str:
    """Hash of the timing model's calibration state: the latency-class
    constants here plus the memory-model constants.  Part of the DSE result
    cache key, so a recalibration (benchmarks/calibrate.py edits these
    arrays) can never be served stale cached timings — the cache just goes
    cold.  ``MODEL_VERSION`` covers structural model changes the constants
    don't express."""
    h = hashlib.sha1()
    h.update(f"v{MODEL_VERSION}".encode())
    for a in (SCALAR_CYCLES, VEC_PIPE_DEPTH, VEC_ELEM_CYCLES):
        h.update(np.asarray(a).tobytes())
    for c in (memory.DRAM_BW_BYTES_PER_CYCLE, memory.DRAM_MLP,
              memory.PREFETCH_DEPTH):
        h.update(np.float32(c).tobytes())
    return h.hexdigest()[:8]


def config_fingerprint(cfg: VectorEngineConfig) -> str:
    """Hash of everything about a config the *timing model* consumes: the
    engine parameter vector (``_cfg_params_np``), which excludes knobs that
    only shape the trace (``mvl`` beyond its effect on the body).

    This is the DSE result cache's config key half: two configs that differ
    only in a timing-irrelevant way (e.g. ``mvl=128`` vs ``mvl=256`` for an
    app whose ``max_vl`` caps both at 64, producing the same clamped body)
    share a fingerprint, so the cache dedups their dispatches within a run.
    """
    h = hashlib.sha1()
    for p in _cfg_params_np(cfg):
        h.update(np.asarray(p).tobytes())
    return h.hexdigest()[:16]


def simulate(trace: isa.Trace, cfg: VectorEngineConfig,
             collect_stats: bool = False) -> dict:
    """Run the timing model; returns times in vector-engine cycles (=ns).

    With ``collect_stats=True`` the profiling scan runs instead (same step
    arithmetic — ``tests/test_telemetry.py`` pins the timing bitwise-equal)
    and the result additionally carries:

    * ``stalls``: ``{cause: cycles}`` over ``STALL_KINDS`` — sums to
      ``time`` (the event-sum identity),
    * ``occ_lane_fu``: lane-busy cycles per arithmetic FU class,
    * ``records``: per-record ``start``/``issue``/``complete`` numpy arrays
      plus the binding-constraint ``cause`` index (timeline export feedstock
      for ``repro.core.telemetry``).
    """
    params = tuple(jnp.asarray(p) for p in _cfg_params_np(cfg))
    if not collect_stats:
        out = _simulate_jit(_trace_xs(trace), params)
        return {k: float(v) for k, v in out.items()}
    out, ys = _profile_jit(_trace_xs(trace), params)
    res = {k: float(v) for k, v in out.items()
           if k not in ("stalls", "occ_lane_fu")}
    res["stalls"] = {k: float(v) for k, v in
                     zip(STALL_KINDS, np.asarray(out["stalls"]))}
    res["occ_lane_fu"] = [float(v) for v in np.asarray(out["occ_lane_fu"])]
    res["records"] = {
        "start": np.asarray(ys[0]), "issue": np.asarray(ys[1]),
        "complete": np.asarray(ys[2]), "cause": np.asarray(ys[3]),
    }
    return res


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _len_bucket(n: int) -> int:
    """Batched traces are padded up to a multiple of CHUNK (the scan is
    dispatched chunk by chunk, so length buckets cost padded *run* steps but
    never a recompile)."""
    return max(CHUNK, -(-n // CHUNK) * CHUNK)


def batch_bucket(n: int) -> int:
    """The power-of-two batch bucket a batch of ``n`` (trace, config) pairs
    pads to.  Together with ``CHUNK`` this is the *only* jit-compilation key
    of the batched path — the contract the serve layer
    (``repro.serve.sim_service``) builds on: prewarm one executable per
    bucket up to the service's ``max_batch`` and steady-state serving never
    recompiles."""
    return _pow2_bucket(n)


def trace_len_bucket(n: int) -> int:
    """The CHUNK-multiple length bucket a trace of ``n`` entries pads to.
    A longer trace costs more chunk *dispatches* (bucket // CHUNK), never a
    recompile — which is why request coalescing only needs to group by batch
    bucket, not by workload."""
    return _len_bucket(n)


def jit_cache_size() -> int:
    """Number of engine executables compiled so far (sequential, batched,
    profiling and sharded): the ``engine.traces`` counter, which each scan
    program's Python body adds to once per jit cache miss.  An
    ahead-of-time ``.lower()`` of one of them counts as well.

    The batched path's compilation key is (batch bucket, CHUNK) only: flags
    are traced, lengths are chunked, batch sizes are padded to powers of two.
    """
    return int(registry.totals()["counters"].get("engine.traces", 0))


def _run_batch_group(traces: list[isa.Trace], cfgs: list[VectorEngineConfig],
                     length: int, collect_times: bool = False):
    """Pad to `length` (a CHUNK multiple), pad the batch to a power of two
    (repeating the first element), then scan chunk by chunk, carrying the
    engine state between dispatches.

    Lanes that hold the same ``Trace`` object share one row of a table of
    distinct traces (``engine.trace_rows`` counts its rows); each chunk's
    per-lane ``[B, CHUNK]`` arrays are gathered from that table just before
    their launch, so the host gathers chunk i+1 while the device scans
    chunk i.  The scan program sees the same values and shapes either way.

    With ``collect_times`` the running per-lane "time" plus the lane/VMU
    busy accumulators after every chunk are also returned (each
    [n_chunks, B]) — ``steady_state_time_batch`` reads the warmup checkpoint
    out of the middle of a single fused scan, and the busy checkpoints give
    marginal steady-state utilization for free (reads of the same carry the
    timing dispatch produces anyway, so timing stays bitwise-identical).
    """
    b = len(traces)
    bb = _pow2_bucket(b)
    n_chunks = length // CHUNK
    # the sharded program's inputs go from the host straight to their
    # shards; the single-device program's (sharding None) to the default
    # device
    sharded = _sharded(bb)
    sharding = None if sharded is None else sharded[1]
    with registry.span("engine.stack"):
        distinct = list({id(t): t for t in traces}.values())
        row_of = {id(t): r for r, t in enumerate(distinct)}
        lane_row = np.array([row_of[id(t)] for t in traces]
                            + [0] * (bb - b), dtype=np.intp)
        table = isa.stack_traces(distinct, length)
        table_np = [getattr(table, f) for f in _TRACE_FIELDS]
        cols = list(zip(*(_cfg_params_np(c)
                          for c in (cfgs + [cfgs[0]] * (bb - b)))))
        params = tuple(jax.device_put(np.stack(col), sharding)
                       for col in cols)
        carry = jax.tree.map(
            lambda a: jnp.zeros((bb,) + a.shape, a.dtype, device=sharding),
            _init_carry())
    registry.count("engine.trace_rows", len(distinct))
    times, busy_l, busy_v = [], [], []
    for i in range(n_chunks):
        # fresh arrays every chunk: an enqueued transfer may still read the
        # previous ones while the device scans
        with registry.span("engine.gather"):
            xs_np = [np.take(a[:, i * CHUNK:(i + 1) * CHUNK], lane_row,
                             axis=0) for a in table_np]
        with registry.span("engine.copy"):
            xs = tuple(jax.device_put(a, sharding) for a in xs_np)
        with registry.span("engine.launch"):
            carry = _dispatch_chunk_batch(carry, xs, params, bb)
            if collect_times:
                times.append(jnp.maximum(carry[9], carry[14]))
                busy_l.append(carry[16])
                busy_v.append(carry[17])
    # the reads below would wait anyway: this splits the wait from them
    with registry.span("engine.wait"):
        jax.block_until_ready((carry, times, busy_l, busy_v))
    registry.count("engine.launches", n_chunks)
    # counted on one device too, as 0: a fallback to one device inside a
    # many-device study reads as a share below 100 %, not as a gap
    registry.count("engine.sharded_launches",
                   0 if sharding is None else n_chunks)
    registry.count("engine.lane_steps_sharded",
                   0 if sharding is None else bb * length)
    registry.count("engine.lane_steps_scanned", bb * length)
    registry.count("engine.lane_steps_batch_pad", (bb - b) * length)
    with registry.span("engine.readback"):
        out = {k: np.asarray(v) for k, v in _metrics(carry).items()}
        rows = [{k: float(v[i]) for k, v in out.items()} for i in range(b)]
        if not collect_times:
            return rows
        return (rows,
                np.stack([np.asarray(t) for t in times]),
                np.stack([np.asarray(t) for t in busy_l]),
                np.stack([np.asarray(t) for t in busy_v]))


def _broadcast_pairs(traces, cfgs, noun: str = "traces"):
    """Pair up the two argument lists, broadcasting a length-1 list."""
    traces = list(traces)
    cfgs = list(cfgs)
    if len(traces) == 1 and len(cfgs) > 1:
        traces = traces * len(cfgs)
    if len(cfgs) == 1 and len(traces) > 1:
        cfgs = cfgs * len(traces)
    if len(traces) != len(cfgs):
        raise ValueError(f"{len(traces)} {noun} vs {len(cfgs)} configs")
    return traces, cfgs


def _group_by_length_bucket(traces) -> dict[int, list[int]]:
    groups: dict[int, list[int]] = {}
    for i, t in enumerate(traces):
        groups.setdefault(_len_bucket(len(t)), []).append(i)
    return groups


def simulate_batch(traces, cfgs) -> list[dict]:
    """Batched timing model: N (trace, config) pairs in a handful of
    XLA dispatches instead of N sequential ``simulate`` calls.

    ``traces`` and ``cfgs`` are lists; a length-1 list broadcasts against the
    other argument.  Pairs are grouped by bucketed trace length; each group
    is padded with timing-neutral NOPs, stacked, and run through the vmapped
    chunk scan.  Results match sequential ``simulate`` (same step arithmetic
    — the scan core is shared) and arrive in input order.
    """
    traces, cfgs = _broadcast_pairs(traces, cfgs)
    if not traces:
        return []
    results: list[dict] = [None] * len(traces)  # type: ignore[list-item]
    for length, idxs in sorted(_group_by_length_bucket(traces).items()):
        outs = _run_batch_group([traces[i] for i in idxs],
                                [cfgs[i] for i in idxs], length)
        registry.count("engine.lane_steps_real",
                       sum(len(traces[i]) for i in idxs))
        for i, r in zip(idxs, outs):
            results[i] = r
    return results


def steady_state_time(body: isa.Trace, cfg: VectorEngineConfig,
                      warmup: int = 8, measure: int = 24) -> float:
    """Marginal steady-state time of one loop body (warmup removed)."""
    t1 = simulate(body.tile(warmup), cfg)["time"]
    t2 = simulate(body.tile(warmup + measure), cfg)["time"]
    return (t2 - t1) / measure


def steady_state_time_batch(bodies, cfgs, warmup: int = 8,
                            measure: int = 24,
                            with_util: bool = False) -> list:
    """Batched ``steady_state_time``: every (body, config) pair in a handful
    of chunked dispatches.

    The warmup and measurement runs are fused into one scan per pair: the
    warmup tiles are NOP-padded to a chunk boundary (timing-neutral, so the
    carry at the boundary equals the carry after the bare warmup), the
    warmup time is read from the running per-chunk checkpoint, and the
    measurement tiles continue in the same scan — bitwise identical to the
    sequential two-simulation recipe at ~60% of the steps.

    With ``with_util`` each entry is a dict ``{"steady_ns", "lane_util",
    "vmu_util"}`` — the utilizations are *marginal* over the measurement
    window (busy cycles accumulated past the warmup checkpoint / wall
    cycles of the window), read from the same carry, so requesting them
    never perturbs the timing.
    """
    bodies, cfgs = _broadcast_pairs(bodies, cfgs, noun="bodies")
    if not bodies:
        return []
    traces, w_chunks = [], []
    fused: dict[int, tuple] = {}     # id(body) -> (trace, warm-up chunks)
    with registry.span("engine.build"):
        for body in bodies:
            ent = fused.get(id(body))
            if ent is None:
                warm = body.tile(warmup)
                wlen = _len_bucket(len(warm))
                ent = fused[id(body)] = (
                    warm.pad_to(wlen).concat(body.tile(measure)),
                    wlen // CHUNK)
            traces.append(ent[0])
            w_chunks.append(ent[1])
    out: list = [0.0] * len(traces)
    for length, idxs in sorted(_group_by_length_bucket(traces).items()):
        rows, times, busy_l, busy_v = _run_batch_group(
            [traces[i] for i in idxs], [cfgs[i] for i in idxs], length,
            collect_times=True)
        registry.count("engine.lane_steps_real",
                       (warmup + measure) * sum(len(bodies[i]) for i in idxs))
        with registry.span("engine.readback"):
            for lane, i in enumerate(idxs):
                t1 = float(times[w_chunks[i] - 1, lane])
                steady = (rows[lane]["time"] - t1) / measure
                if not with_util:
                    out[i] = steady
                    continue
                wall = max(rows[lane]["time"] - t1, 1e-9)
                out[i] = {
                    "steady_ns": steady,
                    "lane_util": (rows[lane]["lane_busy"]
                                  - float(busy_l[w_chunks[i] - 1, lane]))
                    / wall,
                    "vmu_util": (rows[lane]["vmu_busy"]
                                 - float(busy_v[w_chunks[i] - 1, lane]))
                    / wall,
                }
    return out


def scalar_time(trace: isa.Trace, cfg: VectorEngineConfig) -> float:
    """Latency-weighted scalar-core time for a pure-scalar trace (ns), with
    the same knob deltas the scan step applies to residual scalar blocks."""
    freq_ratio = cfg.vector_freq_ghz / cfg.scalar_freq_ghz
    scale = freq_ratio / cfg.issue_width
    bmiss_extra = (SC_BLOCK_BRANCH_FRAC * SC_BLOCK_BMISS_RATE
                   * (cfg.branch_miss_penalty - DEFAULT_BRANCH_MISS_PENALTY)
                   * freq_ratio)
    fuse_save = FUSION_SIMPLE_SAVE if cfg.fusion else 0.0
    mask = trace.kind == isa.SCALAR_BLOCK
    fu = trace.fu[mask]
    eff = SCALAR_CYCLES[fu] * (1.0 - fuse_save * (fu == 0))
    return float(np.sum(trace.scalar_count[mask] * eff * scale
                        + trace.scalar_count[mask] * bmiss_extra))
