#!/usr/bin/env bash
# One-command regression gate: tier-1 tests + docs gate + quick benchmark.
#   scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
# -p no:randomly: the property tier (tests/test_properties.py) must run with
# its fixed seeds — the hypothesis shim seeds its own RNG and real hypothesis
# runs derandomized, but pytest-randomly (if ever installed) would still
# reorder/reseed; disabling an absent plugin is a no-op.
python -m pytest -x -q -p no:randomly

echo "== docs gate: doctests =="
python -m pytest --doctest-modules -q -p no:randomly \
  src/repro/core/memory.py src/repro/core/suite.py src/repro/core/dse.py \
  src/repro/core/codegen.py src/repro/serve/sim_service.py \
  src/repro/core/surrogate.py src/repro/core/search.py \
  src/repro/core/scalar_pipeline.py src/repro/core/telemetry.py \
  src/repro/core/registry.py

echo "== docs gate: README snippets =="
# extract EVERY ```python fenced block from the README and execute them in
# order as one script, so no documented example can rot
snippet="$(mktemp --suffix=.py)"
trap 'rm -f "$snippet"' EXIT
awk '/^```python/{f=1;next} /^```/{f=0} f' README.md > "$snippet"
python "$snippet"

echo "== scalar-scorecard gate =="
# the event-based scalar-pipeline baseline vs all 11 paper §5 anchors, plus
# batched-vs-sequential bitwise equivalence, knob monotonicity and the
# physical-CPI floor (no app's baseline may imply scalar CPI < 0.5)
python -m repro.core.scalar_pipeline --check

echo "== frontend cross-validation gate =="
# derived (jaxpr-lowered) bodies vs hand-coded tracegen bodies: exact
# kind/FU/pattern/element/scalar mixes, steady-state time within 5%
python -m repro.core.frontend

echo "== rvv-crossval gate =="
# the RVV assembly corpus (src/repro/asm) decoded back through
# repro.core.rvv vs the hand-coded bodies, at EVERY mvl in {8..256}:
# static mixes exact, steady-state time within 5%, decoder-derived chunk
# counts against the characterized closed forms, body invariants clean
python -m repro.core.rvv --check-all

echo "== codegen-roundtrip gate =="
# the closed loop: every app with a jaxpr kernel= spec is emitted to RVV
# assembly (repro.core.codegen) and decoded back (repro.core.rvv) at EVERY
# mvl in {8..256} — the decoded chunk body must be bitwise
# fingerprint-equal to the direct jaxpr lowering, with the characterized
# chunk count and clean trace invariants
python -m repro.core.codegen --check-all

echo "== corpus-drift gate =="
# the checked-in src/repro/asm/*.s corpus must byte-match what the
# emitter produces from the kernel specs (no hand edits, no stale files)
python scripts/gen_rvv_corpus.py --check

echo "== dse-smoke gate =="
# 64-point space, single device: explore twice through a fresh on-disk
# cache; the second pass must be 100% hits with a bitwise-identical
# Pareto frontier (the DSE determinism contract)
dse_tmp="$(mktemp -d)"
trap 'rm -f "$snippet"; rm -rf "$dse_tmp"' EXIT
python -m repro.core.dse --space smoke --cache "$dse_tmp/cache.jsonl" --smoke

echo "== surrogate-smoke gate =="
# learned-cost-model search: train the MLP surrogate on a 64-point explore,
# search the 18k-point SPACE_10K; every frontier point must be backed by an
# exact cached engine result (runtime re-derives bitwise) and repeat runs —
# exhaustive-scoring AND evolutionary modes — must be bitwise-identical
python -m repro.core.search --smoke

echo "== serve-smoke gate =="
# simulation service: short Poisson request stream through a fresh on-disk
# cache — prewarmed pass must not recompile at steady state; the repeated
# identical stream must be >=99% ResultCache hits with bitwise-identical
# times (the serving determinism contract)
serve_tmp="$(mktemp -d)"
trap 'rm -f "$snippet"; rm -rf "$dse_tmp" "$serve_tmp"' EXIT
python -m repro.serve.sim_service --smoke --cache "$serve_tmp/cache.jsonl"

echo "== profile-smoke gate =="
# mechanistic cycle attribution: event-sum identity (attributed cycles
# reconstruct total runtime) on all 10 apps x 2 configs, collect_stats
# timing bitwise-identical to the default scan, timeline JSON validity,
# latency-histogram sanity
python -m repro.core.telemetry --smoke

echo "== module-stress gate =="
# paper Table 2 two independent ways: the differential checkmark matrix
# (static shares + knob ablation) must agree with the mechanistic
# cycle attribution for all 10 apps — any mismatch prints the per-module
# breakdown and fails
python benchmarks/module_stress.py

echo "== quick benchmark smoke =="
python benchmarks/run.py --quick
