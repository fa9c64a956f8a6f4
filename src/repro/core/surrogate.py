"""Learned surrogate cost model: predict the exact simulator from its own cache.

The DSE engine (``repro.core.dse``) evaluates (app, config) cells *exactly*,
but exhaustive simulation tops out around the 1536-point ``SPACE_FULL`` grid.
A real design shop wants 10^6-10^8 candidates.  This module trains a small
pure-``jnp`` MLP on the simulator's own ``ResultCache`` entries so a
candidate's runtime can be *predicted* in microseconds, and the search layer
(``repro.core.search``) re-simulates only the predicted-frontier survivors —
the learned-cost-model-over-exact-profiles pattern of the XLA op-timing
literature, applied to vector-architecture parameter sweeps.

The contract, in three parts:

* **Features** (:func:`row_features`): a per-(trace, config) vector — the
  app's trace-mix features (instruction-kind/FU/memory-pattern histograms,
  element counts, footprints, chunk count, scalar residue; built on
  ``isa.Trace`` and the ``characterize`` closed forms) crossed with every
  ``VectorEngineConfig`` knob, all ``log1p``-compressed then standardized.
* **Training** (:func:`fit`): rows mined from a ``ResultCache`` by
  ``ResultCache.export_training_rows`` (a pure join — no re-simulation),
  log-runtime targets, AdamW + cosine LR from the repo's own
  ``repro.core.optimizer``, the whole step loop fused into one jitted
  ``lax.scan``.
* **Inference** (:class:`SpaceScorer`): flat design-space indices are decoded
  (mixed radix, matching ``DesignSpace.config_at``), featurized and scored
  entirely inside jit — scoring 10^6 configs is a handful of vmapped
  dispatches, no per-candidate Python.

Accuracy is never assumed: :func:`scorecard` emits the pred-vs-true
relative-error CDF, per-app worst case and Spearman rank correlation (use a
held-out app for the honest generalization number), and the search layer
re-simulates every reported frontier point exactly — surrogate predictions
never appear in final results.

>>> spearman([1.0, 2.0, 3.0, 4.0], [10.0, 20.0, 30.0, 40.0])
1.0
>>> spearman([1.0, 2.0, 3.0], [3.0, 2.0, 1.0])
-1.0
>>> len(CONFIG_FEATURES) == len(_CFG_FIELDS)
True
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, fields as _dc_fields

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import isa, optimizer, tracegen

_CFG_FIELDS = _dc_fields(eng.VectorEngineConfig)

# --------------------------------------------------------------------------
# config features: every live VectorEngineConfig knob, numerically encoded
# --------------------------------------------------------------------------

CONFIG_FEATURES: tuple = tuple(f.name for f in _CFG_FIELDS)


def cfg_field_numeric(name: str, value) -> float:
    """Numeric encoding of one config field (bools 0/1, ``interconnect``:
    ring=1 / crossbar=0, everything else already a number)."""
    if name == "interconnect":
        return 1.0 if value == "ring" else 0.0
    if isinstance(value, bool):
        return 1.0 if value else 0.0
    return float(value)


def config_features(cfg: eng.VectorEngineConfig) -> np.ndarray:
    """The config half of a feature row: every field of the config,
    numerically encoded, in ``CONFIG_FEATURES`` order."""
    return np.asarray([cfg_field_numeric(n, getattr(cfg, n))
                       for n in CONFIG_FEATURES], np.float32)


CONFIG_FEATURE_DEFAULTS = config_features(eng.VectorEngineConfig())

# --------------------------------------------------------------------------
# trace features: the app side, a pure function of (app, cfg.mvl)
# --------------------------------------------------------------------------

TRACE_FEATURES = (
    # loop-body shape (counts per instruction kind)
    "body_len", "n_vector", "n_scalar_blocks",
    "kind_arith", "kind_load", "kind_store", "kind_slide",
    "kind_reduce", "kind_mask2s", "kind_move",
    # FU mix of the vector instructions
    "fu_simple", "fu_mul", "fu_div", "fu_trans",
    # memory access patterns
    "mem_unit", "mem_strided", "mem_indexed",
    # element-level work
    "elems_total", "elems_mem", "avg_vl_body",
    # scalar-core coupling
    "scalar_per_chunk", "dep_scalar_blocks",
    # working sets
    "footprint_max_kb", "footprint_mean_kb",
    # whole-app scale (the closed forms the runtime derivation uses)
    "chunks", "residual_scalar",
    # characterization-level mix (paper §4 definitions)
    "pct_vectorization", "avg_vl_counts", "eff_mvl",
)

# Every loop body in the registry consumes its config through cfg.mvl only
# (the clamp and canneal's full-MVL moves) — the invariant that lets the
# feature table key on (app, cfg.mvl) instead of the whole config, which is
# what makes million-point scoring a table lookup.  ``dse.cell_body`` keys
# its body memo the same way.
_TRACE_FEATS: dict[tuple, np.ndarray] = {}


def trace_features(app_name: str, mvl: int) -> np.ndarray:
    """The trace half of a feature row for one (app, configured MVL) pair."""
    key = (app_name, int(mvl))
    out = _TRACE_FEATS.get(key)
    if out is not None:
        return out
    from repro.core import suite
    cfg = eng.VectorEngineConfig(mvl=int(mvl))
    eff = suite.effective_mvl(app_name, cfg)
    body = tracegen.body_for(app_name, eff, cfg)
    chunks = tracegen.chunks_for(app_name, eff, cfg)
    counts = tracegen.app_for(app_name).counts(int(mvl))
    kinds = isa.kind_histogram(body)
    vec = body.kind != isa.SCALAR_BLOCK
    is_mem = (body.kind == isa.VLOAD) | (body.kind == isa.VSTORE)
    vls = body.vl[vec].astype(np.float64)
    n_vec = int(vec.sum())
    fu_hist = np.bincount(body.fu[vec], minlength=isa.N_FU_CLASSES)
    pat_hist = np.bincount(body.mem_pattern[is_mem], minlength=3)
    scalar_per_chunk = float(body.scalar_count.sum())
    residual = max(counts.scalar_instrs - scalar_per_chunk * chunks, 0.0)
    fp = body.footprint_kb[is_mem]
    vals = {
        "body_len": float(len(body)),
        "n_vector": float(n_vec),
        "n_scalar_blocks": float((body.kind == isa.SCALAR_BLOCK).sum()),
        "kind_arith": float(kinds[isa.VARITH]),
        "kind_load": float(kinds[isa.VLOAD]),
        "kind_store": float(kinds[isa.VSTORE]),
        "kind_slide": float(kinds[isa.VSLIDE]),
        "kind_reduce": float(kinds[isa.VREDUCE]),
        "kind_mask2s": float(kinds[isa.VMASK_SCALAR]),
        "kind_move": float(kinds[isa.VMOVE]),
        "fu_simple": float(fu_hist[isa.FU_SIMPLE]),
        "fu_mul": float(fu_hist[isa.FU_MUL]),
        "fu_div": float(fu_hist[isa.FU_DIV]),
        "fu_trans": float(fu_hist[isa.FU_TRANS]),
        "mem_unit": float(pat_hist[isa.MEM_UNIT]),
        "mem_strided": float(pat_hist[isa.MEM_STRIDED]),
        "mem_indexed": float(pat_hist[isa.MEM_INDEXED]),
        "elems_total": float(vls.sum()),
        "elems_mem": float(body.vl[is_mem].sum()),
        "avg_vl_body": float(vls.mean()) if n_vec else 0.0,
        "scalar_per_chunk": scalar_per_chunk,
        "dep_scalar_blocks": float(body.dep_scalar.sum()),
        "footprint_max_kb": float(fp.max()) if fp.size else 0.0,
        "footprint_mean_kb": float(fp.mean()) if fp.size else 0.0,
        "chunks": float(chunks),
        "residual_scalar": float(residual),
        "pct_vectorization":
            counts.vector_ops / (counts.scalar_instrs + counts.vector_ops),
        "avg_vl_counts": counts.vector_ops / max(counts.total_vector, 1),
        "eff_mvl": float(eff),
    }
    out = np.asarray([vals[n] for n in TRACE_FEATURES], np.float32)
    _TRACE_FEATS[key] = out
    return out


N_FEATURES = len(CONFIG_FEATURES) + len(TRACE_FEATURES)


def row_features(app_name: str, cfg: eng.VectorEngineConfig) -> np.ndarray:
    """One raw (un-standardized) feature row: config knobs ++ trace mix."""
    return np.concatenate([config_features(cfg),
                           trace_features(app_name, cfg.mvl)])


# --------------------------------------------------------------------------
# the model: log1p -> standardize -> 2-hidden-layer MLP -> log runtime
# --------------------------------------------------------------------------

@dataclass
class Surrogate:
    """A trained surrogate: standardization stats + MLP parameters + the
    provenance needed to trust (or distrust) it."""
    feat_mean: np.ndarray          # [F] mean of log1p features, train set
    feat_std: np.ndarray           # [F] std  of log1p features, train set
    params: dict                   # {"w1","b1","w2","b2","w3","b3"}
    apps: tuple                    # apps present in the training rows
    meta: dict                     # n_rows / steps / seed / final_loss / ...

    def predict_runtime_ns(self, rows) -> np.ndarray:
        """Predicted whole-app runtimes (ns) for export_training_rows-style
        rows — the row-at-a-time inference path (tests, scorecards).  The
        bulk path is :class:`SpaceScorer`."""
        X = np.stack([row_features(r["app"], r["cfg"]) for r in rows])
        out = np.asarray(_forward_jit(
            self.params, _standardize(X, self.feat_mean, self.feat_std)))
        return np.exp(np.clip(out, *_LOG_CLIP))


def _standardize(X, mean, std):
    # multiply by a host-side reciprocal: jit rewrites a divide by a
    # constant into exactly this, so eager and jitted callers (the row path
    # and SpaceScorer) round alike
    return (jnp.log1p(jnp.asarray(X)) - mean) * np.reciprocal(std)


# log-runtime predictions are clamped to a generous physical band before
# exponentiation (1 ns .. ~5e21 ns) so far-out-of-distribution candidates
# rank as "terrible", never as inf/nan
_LOG_CLIP = (0.0, 50.0)


def _forward(params, X):
    # float32 matmuls on every backend (the TPU's default is one bf16 pass)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    h = jax.nn.relu(mm(X, params["w1"]) + params["b1"])
    h = jax.nn.relu(mm(h, params["w2"]) + params["b2"])
    return (mm(h, params["w3"]) + params["b3"])[:, 0]


_forward_jit = jax.jit(_forward)


def _init_params(n_in: int, hidden: int, seed: int) -> dict:
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    he = lambda k, i, o: (jax.random.normal(k, (i, o), jnp.float32)
                          * np.sqrt(2.0 / i))
    return {
        "w1": he(k1, n_in, hidden), "b1": jnp.zeros(hidden, jnp.float32),
        "w2": he(k2, hidden, hidden), "b2": jnp.zeros(hidden, jnp.float32),
        "w3": he(k3, hidden, 1), "b3": jnp.zeros(1, jnp.float32),
    }


def fit(rows, hidden: int = 64, steps: int = 1500, lr: float = 3e-3,
        seed: int = 0) -> Surrogate:
    """Train a surrogate on ``ResultCache.export_training_rows`` rows.

    Targets are ``log(runtime_ns)`` (runtimes span ~4 decades across the
    suite; the log makes the MSE a *relative*-error objective).  The whole
    optimization — AdamW with global-norm clipping and warmup+cosine LR from
    ``repro.core.optimizer`` — runs as one jitted ``lax.scan`` over
    full-batch gradient steps, so training ~15k rows takes seconds.
    Deterministic in (rows, hyperparameters, seed).
    """
    if not rows:
        raise ValueError("fit() needs at least one training row")
    X = np.stack([row_features(r["app"], r["cfg"]) for r in rows])
    y = np.log(np.asarray([r["runtime_ns"] for r in rows], np.float32))
    Xl = np.log1p(X.astype(np.float64))
    mean = Xl.mean(axis=0)
    # Features constant across the training rows (a knob the mined sweep
    # never varied) get std=1, NOT a tiny floor: they standardize to ~0 in
    # training so the model ignores them, and stay bounded when the search
    # space later sweeps them — a 1e-6 floor would turn any unseen choice
    # into a +-10^5 activation and a nonsense (inf) prediction.  The stats
    # are float64, where a constant column's std is exactly 0: in float32
    # its mean rounds, its std lands just above 1e-6, and the column then
    # multiplies a one-ulp difference between the host's log1p and the
    # device's (the TPU's) by ~10^6.
    std = Xl.std(axis=0)
    std = np.where(std < 1e-6, 1.0, std)
    Xn = jnp.asarray(((Xl - mean) / std).astype(np.float32))
    yj = jnp.asarray(y)

    opt_cfg = optimizer.OptConfig(
        lr=lr, b1=0.9, b2=0.95, weight_decay=1e-4, clip_norm=1.0,
        warmup_steps=min(100, steps // 10 + 1), total_steps=steps,
        min_lr_frac=0.02)
    params = _init_params(Xn.shape[1], hidden, seed)
    state = optimizer.init(params)

    def loss_fn(p):
        return jnp.mean((_forward(p, Xn) - yj) ** 2)

    def step(carry, _):
        p, s = carry
        loss, g = jax.value_and_grad(loss_fn)(p)
        p, s, _ = optimizer.apply(opt_cfg, p, g, s)
        return (p, s), loss

    @jax.jit
    def run(params, state):
        (p, _), losses = jax.lax.scan(step, (params, state), None,
                                      length=steps)
        return p, losses

    params, losses = run(params, state)
    params = {k: np.asarray(v) for k, v in params.items()}
    return Surrogate(
        feat_mean=mean.astype(np.float32), feat_std=std.astype(np.float32),
        params={k: jnp.asarray(v) for k, v in params.items()},
        apps=tuple(sorted({r["app"] for r in rows})),
        meta={"n_rows": len(rows), "hidden": hidden, "steps": steps,
              "lr": lr, "seed": seed,
              "final_loss": float(losses[-1]),
              "model_fp": eng.model_fingerprint()})


# --------------------------------------------------------------------------
# bulk inference: score flat DesignSpace indices entirely inside jit
# --------------------------------------------------------------------------

SCORE_BATCH = 1 << 17     # fixed batch: one compiled executable per scorer


class SpaceScorer:
    """Batched surrogate inference over a ``DesignSpace`` for one app.

    ``score(idx)`` takes *flat candidate indices* and returns
    ``(predicted runtime_ns, exact area_kb)``.  Indices are decoded to axis
    digits by the same mixed-radix rule as ``DesignSpace.config_at`` (last
    axis fastest), feature columns are assembled from per-axis choice tables
    (unlisted knobs sit at their defaults), the app's trace features are a
    per-MVL-choice table lookup, and the area proxy is ``dse.area_proxy_kb``
    spelled in ``jnp`` — so no ``VectorEngineConfig`` object is ever built
    on the scoring path.  Work is dispatched in fixed ``SCORE_BATCH`` chunks
    (pad + mask), so a million-point space is ~8 dispatches of one compiled
    executable.
    """

    def __init__(self, model: Surrogate, space, app: str):
        self.model = model
        self.space = space
        self.app = app
        axes = list(space.axes)
        self._radices = [len(c) for _, c in axes]
        # per-axis numeric choice tables + their CONFIG_FEATURES column
        self._axis_cols = [CONFIG_FEATURES.index(n) for n, _ in axes]
        self._axis_vals = [
            jnp.asarray([cfg_field_numeric(n, v) for v in choices],
                        np.float32)
            for n, choices in axes]
        # the app's trace features per mvl choice (one row if mvl not swept)
        mvl_axis = [i for i, (n, _) in enumerate(axes) if n == "mvl"]
        self._mvl_axis = mvl_axis[0] if mvl_axis else None
        mvls = (axes[self._mvl_axis][1] if self._mvl_axis is not None
                else (eng.VectorEngineConfig().mvl,))
        self._trace_tab = jnp.asarray(
            np.stack([trace_features(app, m) for m in mvls]))
        self._score_jit = jax.jit(self._score_batch)

    def _score_batch(self, idx):
        """idx: [SCORE_BATCH] int32 -> (pred runtime_ns, area_kb)."""
        n_axes = len(self._radices)
        rem = idx
        digits = [None] * n_axes
        for a in range(n_axes - 1, -1, -1):     # last axis fastest
            rem, r = jnp.divmod(rem, self._radices[a])
            digits[a] = r
        # config feature matrix: defaults, overridden per swept axis
        cols = {c: jnp.full(idx.shape, CONFIG_FEATURE_DEFAULTS[c])
                for c in range(len(CONFIG_FEATURES))}
        for a in range(n_axes):
            cols[self._axis_cols[a]] = jnp.take(self._axis_vals[a],
                                                digits[a])
        cfg_mat = jnp.stack([cols[c] for c in range(len(CONFIG_FEATURES))],
                            axis=1)
        trace_mat = (self._trace_tab[digits[self._mvl_axis]]
                     if self._mvl_axis is not None
                     else jnp.broadcast_to(self._trace_tab[0],
                                           idx.shape + self._trace_tab[0].shape))
        X = jnp.concatenate([cfg_mat, trace_mat], axis=1)
        pred = jnp.exp(jnp.clip(_forward(
            self.model.params,
            _standardize(X, self.model.feat_mean, self.model.feat_std)),
            *_LOG_CLIP))
        # dse.area_proxy_kb, spelled over the feature columns
        from repro.core import dse
        g = lambda name: cols[CONFIG_FEATURES.index(name)]
        area = (g("phys_regs") * g("mvl") * 8.0 / 1024.0
                + dse.LANE_AREA_KB * g("lanes")
                + g("l1_kb") + dse.L2_SHARED_FRACTION * g("l2_kb")
                + dse.ENTRY_AREA_KB * (g("rob_entries")
                                       + 2.0 * g("queue_entries")
                                       + g("mshrs")))
        return pred, area

    def score(self, idx) -> tuple[np.ndarray, np.ndarray]:
        """Score any number of flat indices (padded to ``SCORE_BATCH``
        multiples internally); returns ``(pred_runtime_ns, area_kb)``."""
        idx = np.asarray(idx, np.int32)
        preds = np.empty(len(idx), np.float32)
        areas = np.empty(len(idx), np.float32)
        for lo in range(0, len(idx), SCORE_BATCH):
            part = idx[lo:lo + SCORE_BATCH]
            padded = np.zeros(SCORE_BATCH, np.int32)
            padded[:len(part)] = part
            p, a = self._score_jit(jnp.asarray(padded))
            preds[lo:lo + SCORE_BATCH] = np.asarray(p)[:len(part)]
            areas[lo:lo + SCORE_BATCH] = np.asarray(a)[:len(part)]
        return preds, areas


# --------------------------------------------------------------------------
# the accuracy scorecard: every speed claim carries a trust number
# --------------------------------------------------------------------------

def _ranks(x) -> np.ndarray:
    """Average ranks (ties share their mean rank), scipy-free."""
    x = np.asarray(x, np.float64)
    order = np.argsort(x, kind="mergesort")
    r = np.empty(len(x), np.float64)
    r[order] = np.arange(len(x), dtype=np.float64)
    _, inv, cnt = np.unique(x, return_inverse=True, return_counts=True)
    sums = np.zeros(len(cnt))
    np.add.at(sums, inv, r)
    return sums[inv] / cnt[inv]


def spearman(a, b) -> float:
    """Spearman rank correlation (average-rank tie handling)."""
    ra, rb = _ranks(a), _ranks(b)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = np.sqrt((ra * ra).sum() * (rb * rb).sum())
    return float((ra * rb).sum() / denom) if denom else 0.0


def scorecard(model: Surrogate, rows, holdout_app: str | None = None) -> dict:
    """Pred-vs-true accuracy report over labeled rows.

    Emits the relative-error CDF percentiles (p50/p90/p99/max on the natural
    runtime scale), per-app mean/worst error and Spearman rank correlation.
    When ``holdout_app`` names an app in ``rows``, its block is additionally
    surfaced as ``holdout`` — train the model *without* that app and this is
    the honest unseen-workload generalization number.
    """
    pred = model.predict_runtime_ns(rows)
    true = np.asarray([r["runtime_ns"] for r in rows], np.float64)
    rel = np.abs(pred - true) / true
    apps = sorted({r["app"] for r in rows})
    per_app = {}
    for app in apps:
        m = np.asarray([r["app"] == app for r in rows])
        per_app[app] = {
            "n": int(m.sum()),
            "mean_rel_err": float(rel[m].mean()),
            "worst_rel_err": float(rel[m].max()),
            "spearman": spearman(pred[m], true[m]),
            "trained_on": app in model.apps,
        }
    card = {
        "n_rows": len(rows),
        "rel_err_p50": float(np.percentile(rel, 50)),
        "rel_err_p90": float(np.percentile(rel, 90)),
        "rel_err_p99": float(np.percentile(rel, 99)),
        "rel_err_max": float(rel.max()),
        "spearman_all": spearman(pred, true),
        "per_app": per_app,
    }
    if holdout_app is not None and holdout_app in per_app:
        card["holdout"] = dict(per_app[holdout_app], app=holdout_app)
    return card
