#!/usr/bin/env bash
# CI gate, in dependency order: TPU-hazard lint (fails on findings not in
# the baseline), perf-trajectory regression check over whatever
# BENCH_r0*.json history is present (none is committed any more: the step
# reports "skipped" until a ledger exists), then the counter invariants —
# including the disagg phase (block-granular migration economics: copied
# == owned non-shared blocks, prefix blocks never moved twice, zero
# retraces across the prefill/decode split, token identity vs unified)
# and the tiering phase (host-RAM KV tier under an oversubscribed pool:
# spill/restore token identity for greedy AND seeded sampling, zero
# steady-state retraces/syncs, flat host arena once the buffer reuse
# pool is warm, and kv_spill_drop chaos degrading to a cache miss),
# and the devicetime phase (sample=0 byte-identical OFF parity;
# sample=4 pays exactly ceil(dispatches/4) fences with token identity
# and a ledger whose MFU/roofline gauges survive GET /programs and
# bench_compare --attribute), and the mesh-serving phase (mp2 paged
# decode over the StateArena: token identity vs single-device, zero
# steady retraces/hydrates/host-syncs with dispatch counts unchanged,
# the KV pool genuinely head-sharded per chip, and the audit census
# proving in-graph collectives only — zero host launches), and the
# adapters phase (multi-tenant LoRA serving: a heterogeneous batch of
# three tenants + base rows token-identical to per-tenant sequential
# through ONE compiled decode program, base rows bitwise passthrough,
# zero steady retraces/loads with dispatch counts equal to the
# adapter-free twin, and eviction-then-reuse paging tenants back in
# warm — loads move, programs never retrace).
#
# Usage: scripts/ci_gate.sh        (from anywhere; cd's to the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ci_gate: TPU-hazard lint (PT001-PT006, baseline-checked) =="
python scripts/lint_tpu.py --check

echo "== ci_gate: bench perf-trajectory regression =="
# rc 2 means not enough parseable history (fresh clone / bootstrap run):
# nothing to compare against is not a regression.
rc=0
python scripts/bench_compare.py --glob 'BENCH_r0*.json' || rc=$?
if [ "$rc" -eq 1 ]; then
    exit 1
elif [ "$rc" -eq 2 ]; then
    echo "(not enough bench history yet -- comparison skipped)"
elif [ "$rc" -ne 0 ]; then
    exit "$rc"
fi

echo "== ci_gate: steady-state counter invariants (incl. disagg, tiering, devicetime, mesh-serving, adapters) =="
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" PYTHONPATH=. \
    python scripts/check_counters.py

echo "ci_gate: OK"
