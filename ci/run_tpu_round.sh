#!/bin/bash
# One-shot TPU measurement series for an end-of-round artifact drop.
#
# Runs every chip-dependent benchmark exactly once, SERIALIZED (a
# chip belongs to one process at a time -- see
# .claude/skills/verify/SKILL.md), with per-step timeouts so a hung
# backend cannot wedge the whole series.  Results land in
# benchmarks/results/ for commit; bench JSON lines are echoed.
#
# RESUMABLE (round 5; VERDICT r4 weak #4): a step whose .out already
# passes its banked-predicate (good bench JSON row / all-good jsonl /
# green pytest / completion trailer) is SKIPPED, so re-firing the
# series after a mid-run backend death resumes the un-banked
# remainder instead of restarting from scratch.  FORCE=1 reruns
# everything.
#
# Usage: bash ci/run_tpu_round.sh [round_tag]    (default r3)
set -u
cd "$(dirname "$0")/.."
TAG=${1:-r3}
RES=benchmarks/results
mkdir -p "$RES"
# per-run completion marker (ADVICE r4 #2): removed at series start,
# written only when the series reaches its end, so a babysitter can
# test completion without grepping a shared append-mode log.
rm -f "$RES/series_${TAG}.done"

# preflight: one bounded probe so a dead backend fails the series in
# ~2 minutes instead of burning every step's own probe window
if ! timeout 120 python -c "
import jax, jax.numpy as jnp
assert jax.default_backend() == 'tpu', (
    'not a TPU backend: %s -- a silent CPU fallback would record '
    'bogus artifacts as TPU data' % jax.default_backend())
y = jax.jit(lambda a: a @ a)(jnp.ones((256, 256), jnp.bfloat16))
jax.device_get(y[:1, :1])
print('preflight ok:', jax.default_backend())
" >&2; then
  echo "preflight FAILED: TPU backend unreachable; aborting series" >&2
  exit 2
fi

# --- banked predicates (each: <outfile> -> 0 if already good) --------
pred_json_row() {  # last line is bench JSON: no error/suspect, value>0
  python - "$1" <<'EOF'
import json, sys
try:
    lines = [ln for ln in open(sys.argv[1]).read().splitlines()
             if ln.strip()]
    row = json.loads(lines[-1])
except Exception:
    sys.exit(1)
ok = (not row.get('error') and not row.get('suspect')
      and float(row.get('value', 0)) > 0)
sys.exit(0 if ok else 1)
EOF
}
pred_jsonl() {  # sweep banked: substantial row count, no error rows,
  # majority non-suspect.  Individual suspect rows are a DESIGNED-FOR
  # outcome on a noisy backend (emitted, not retried) -- requiring
  # zero of them would permanently un-bank the step and burn a
  # multi-minute rerun every resume.
  python - "$1" <<'EOF'
import json, sys
rows = []
for ln in open(sys.argv[1]).read().splitlines():
    try:
        rows.append(json.loads(ln))
    except ValueError:
        pass
good = sum(1 for r in rows if not r.get('suspect'))
ok = (len(rows) >= 10 and 2 * good > len(rows)
      and not any(r.get('error') for r in rows))
sys.exit(0 if ok else 1)
EOF
}
pred_best_row() {  # good bench row AND still the config adoption
  # would pick from today's banked sweep -- a resumed sweep step that
  # crowns a new winner must un-bank the best-config artifact so the
  # official row (and the warmed compile cache) track the freshest
  # winner (the banked row itself is a candidate, so a rerun that
  # measures the winner directly re-banks)
  pred_json_row "$1" || return 1
  python - "$1" <<'EOF'
import json, os, sys
sys.path.insert(0, os.getcwd())
import bench
lines = [ln for ln in open(sys.argv[1]).read().splitlines()
         if ln.strip()]
row = json.loads(lines[-1])
argv = bench.adopt_tuned_config([], 'resnet50')
want_batch = (int(argv[argv.index('--batch') + 1])
              if '--batch' in argv else None)
have_batch = row.get('per_device_batch_override') or None
want_s2d = '--s2d' in argv
have_s2d = row.get('stem') == 'space_to_depth'
sys.exit(0 if (have_batch == want_batch and have_s2d == want_s2d)
         else 1)
EOF
}
pred_pytest_green() {  # green summary, no failed/error counts
  grep -q ' passed' "$1" && ! grep -Eq '[0-9]+ (failed|error)' "$1"
}
pred_wrote() {  # completion trailer from sweep/trace scripts
  grep -q '^wrote ' "$1"
}

# Dead-backend circuit breaker: when the backend dies mid-window, each
# remaining bench step burns ~13 min of probe retries before writing
# its backend_unavailable row -- a dozen queued steps would waste
# hours of window-less probing at the series' own glacial cadence.
# After TWO consecutive dead-looking steps the series aborts (exit 4);
# re-firing the resumable series starts at the first un-banked step.
# The projection regen is pure host-side arithmetic over whatever is
# banked; run it on EVERY exit path (including the circuit-breaker
# abort below) so the freshest measured inputs are always reflected.
regen_projection() {
  python benchmarks/scaling_projection.py --tag "$TAG" \
    > "$RES/scaling_projection_${TAG}.log" 2>&1 || true
}
trap regen_projection EXIT

DEAD=0
note_outcome() {  # note_outcome <rc> <outfile>
  local rc=$1 out=$2 err
  if [ "$rc" -eq 0 ]; then
    DEAD=0
    return 0
  fi
  # last-JSON-line error field (same one-JSON-line-last contract as
  # pred_json_row; this extracts the error string, that one judges
  # bankability)
  err=$(python - "$out" <<'EOF'
import json, sys
try:
    lines = [ln for ln in open(sys.argv[1]).read().splitlines()
             if ln.strip()]
    print(json.loads(lines[-1]).get('error', ''))
except Exception:
    print('')
EOF
)
  if [ "$err" = backend_unavailable ] || [ "$err" = bench_timeout ] \
      || { [ "$rc" -eq 124 ] && [ -z "$err" ]; }; then
    DEAD=$((DEAD + 1))
    if [ "$DEAD" -ge 2 ]; then
      echo "=== backend dead for $DEAD consecutive steps; aborting" \
           "series (re-fire it to resume the remainder)" >&2
      exit 4
    fi
  else
    # the step FAILED but not in a dead-backend way (the backend
    # answered and produced a real error row): that breaks the
    # consecutive-dead run, otherwise two dead steps separated by a
    # live failure would abort a live window
    DEAD=0
  fi
}

run_with() {  # run_with <pred> <name> <timeout_s> <cmd...>
  local pred=$1 name=$2 tmo=$3; shift 3
  local out="$RES/${name}_${TAG}.out"
  if [ "${FORCE:-0}" != 1 ] && [ -s "$out" ] && "$pred" "$out"; then
    echo "=== [$name] already banked; skipping (FORCE=1 reruns)" >&2
    return 0
  fi
  echo "=== [$name] $*" >&2
  timeout "$tmo" "$@" > "$out" 2> "$RES/${name}_${TAG}.err"
  local rc=$?
  echo "=== [$name] rc=$rc" >&2
  tail -2 "$out" >&2 || true
  note_outcome "$rc" "$out"
  return $rc
}
run() { run_with pred_json_row "$@"; }

# Queue-staleness purge (PERF.md: window 2 closed MID-SWEEP at the
# b128 rung, leaving backend_unavailable/bench_timeout rows banked
# under this round's tag).  Such rows already fail the banked
# predicates -- the rungs WILL rerun -- but their presence makes the
# end-of-series JSON listing and any human skim of $RES read dead
# rows as data; delete them up front so the resumable queue state is
# honest and the interrupted b128/b256/best rungs are visibly
# RE-QUEUED (they run in tier 3, ahead of the serve arms below).
for f in "$RES"/bench_*_"$TAG".out; do
  [ -s "$f" ] || continue
  err=$(python - "$f" <<'EOF'
import json, sys
try:
    lines = [ln for ln in open(sys.argv[1]).read().splitlines()
             if ln.strip()]
    print(json.loads(lines[-1]).get('error', ''))
except Exception:
    print('')
EOF
)
  if [ "$err" = backend_unavailable ] || [ "$err" = bench_timeout ]; then
    echo "=== purging stale dead-window row: $f ($err)" >&2
    rm -f "$f"
  fi
done

# Steps are ordered by VALUE-PER-MINUTE, not by headline order: the
# round-3 backend answered for ~10 minutes total, so the series must
# bank SOMETHING real in the first minutes of a window.  Tier 1 takes
# ~2-4 min cold and yields suspect-gated TPU data points (mlp model
# line + allreduce staging sweep); tier 2 is the headline ResNet-50;
# tier 3 widens; tier 4 is the MFU chase.

# Quick-step timeout: bench.py's probe retries can eat ~780s on a
# flaky backend before the 1800s-watchdogged child starts, so the
# outer bound must exceed 780+1800 for the child's diagnostic-JSON
# guarantee to hold (ADVICE r4 #1).
QT=2700

# --- tier 1: fast real data ------------------------------------------
run bench_mlp $QT python bench.py --model mlp --quick
run_with pred_jsonl allreduce_tpu 1800 \
    python benchmarks/allreduce_payload_sweep.py

# --- tier 2: the headline (compile ~4-6 min/scan-length uncached) ----
# --no-adopt: this artifact IS the default-config (batch 32) row that
# PERF.md and scaling_projection.py consume, and the incumbent the
# adoption policy compares sweep winners against -- letting a prior
# round's winner steer it would make adoption sticky forever (the
# default could never be re-crowned).  bench_resnet50_best below is
# the adoption consumer.
run bench_resnet50 3900 python bench.py --no-adopt

# --- tier 3: the MFU chase (VERDICT r4 next #2) ----------------------
# Promoted ABOVE the remaining workloads after the first r5 window:
# the big cold compiles (vgg16, googlenetbn) repeatedly killed that
# window's compile service, and anything ordered after them never
# ran.  ResNet-50 variants reuse a proven-compilable graph family,
# so the MFU sweep is cheap-risk, high-value (VERDICT ranks it #2).
for B in 64 128 256; do
  run "bench_resnet50_b${B}" $QT python bench.py --quick --batch "$B"
done
# MXU-friendly space-to-depth stem (exact equivalent; models/resnet50.py)
run bench_resnet50_s2d $QT python bench.py --quick --s2d
run bench_resnet50_s2d_b128 $QT python bench.py --quick --s2d --batch 128
# mixed-precision A/B: bf16 compute + bf16 gradient reduction with
# f32 master weights (chainermn_tpu/precision.py) against the tier-2
# f32-master headline -- rows carry the policy dtypes, so the pair is
# self-describing in the banked artifacts (docs/mixed_precision.md)
run bench_resnet50_bf16 $QT python bench.py --quick --policy bf16
# fused BN+relu+add Pallas arm (docs/kernels.md): the direct attack
# on the HBM-bandwidth wall the r5 batch sweep diagnosed -- rows
# carry fused_norm/hbm_bytes_per_image/pct_of_hbm_peak, so the A/B
# against bench_resnet50_bf16 is self-describing in the artifacts
run bench_resnet50_fused $QT python bench.py --quick --policy bf16 --fused-norm
# donation + remat headline arm (PERF.md knob #6): the default rows
# replay with donate=False, which understates real training -- this
# row measures with buffers donated into the step and the backward
# rematerializing the forward (rows carry donate/remat)
run bench_resnet50_donate $QT python bench.py --quick --donate

# end-of-sweep headline rerun: a PLAIN bench.py invocation adopts the
# sweep winner just banked above (bench.py:adopt_tuned_config), so the
# official-config artifact reflects THIS round's best measured config
# and the exact compile cache the driver's end-of-round BENCH run will
# hit is warmed here.  Runs non-quick (the driver's scan lengths).
# Short-circuited when adoption crowns nothing: the step would only
# duplicate tier-2's default-config measurement at full non-quick
# cost (the tier-2 run already warmed that cache).  Exit codes keep
# a crashed gate distinct from a legitimate no-winner (a crash falls
# through to MEASURING, the conservative default).
python -c "
import sys
sys.path.insert(0, '.')
import bench
sys.exit(0 if bench.adopt_tuned_config([], 'resnet50') else 3)
"
gate_rc=$?
if [ "$gate_rc" -eq 3 ]; then
  echo "=== [bench_resnet50_best] no tuned winner beats the default;" \
       "tier-2's --no-adopt row IS the best measured config" >&2
  # a best row banked EARLIER in the round under a since-dethroned
  # winner must not survive as the official artifact (it matches the
  # adoption glob and would be committed as if current)
  stale="$RES/bench_resnet50_best_${TAG}.out"
  if [ -s "$stale" ] && ! pred_best_row "$stale"; then
    echo "=== [bench_resnet50_best] removing stale dethroned row" >&2
    rm -f "$stale" "$RES/bench_resnet50_best_${TAG}.err"
  fi
else
  [ "$gate_rc" -ne 0 ] && echo "=== [bench_resnet50_best] adoption" \
    "gate crashed (rc=$gate_rc); measuring anyway" >&2
  run_with pred_best_row bench_resnet50_best 3900 python bench.py
fi

# composed dp x tp transformer (docs/mesh_parallelism.md), queued
# right after the resnet sweep: rows carry tokens/s/chip, analytic
# MFU vs the PERF.md 90-115k tok/s/chip anchor, and per-axis
# collective bytes (data vs model wire traffic)
run bench_transformer_tp $QT python bench.py --model transformer --quick --tp 2
# 3-D dp x pp pipeline arm (ISSUE 14): the stage-sliced transformer
# trained 1F1B through the unified MeshPipelineUpdater; rows add
# pp / n_microbatches / bubble_fraction (banked-sidecar conventions
# apply through outages like every transformer row)
run bench_transformer_pp $QT python bench.py --model transformer --quick --pp 2

# --- streaming input pipeline (docs/data_pipeline.md) ----------------
# streamed-vs-device-resident A/B on the resnet50 step: the value is
# streamed samples/s/chip, with the resident twin, the
# loader_efficiency ratio (1.0 = decode + H2D fully hidden under the
# step), the telemetry-measured h2d_overlap_fraction and the
# queue-depth p50 as sidecars -- every other row in this round feeds
# device-resident arrays; this one prices the production feed path.
run bench_resnet50_loader $QT python bench.py --loader --model resnet50 --quick

# --- serving arms (docs/serving.md) ----------------------------------
# AFTER the training headline + the re-queued b128/b256/best rungs on
# purpose: the training MFU chase is the round's primary unbanked
# claim (window 2 died mid-sweep and those rungs have waited two
# rounds), while the serve arms are a NEW metric family with no
# banked baseline to regress -- first-window minutes go to the data
# the projections already consume.  Rows carry req/s/chip, p50/p99
# latency from telemetry histograms, pad-waste fraction, bucket
# hit-rate and AOT/cache provenance; the int8 arm pairs with the
# bf16 one as a self-describing quantization A/B.
run bench_serve_mlp $QT python bench.py --serve --model mlp --quick
run bench_serve_resnet50 $QT python bench.py --serve --quick
run bench_serve_resnet50_int8 $QT python bench.py --serve --quick --int8
# autoregressive arm (docs/serving.md "Autoregressive generation"):
# tokens/s/chip + TTFT + inter-token p50/p99 through continuous
# batching over the prefill/decode AOT split, anchored against the
# PERF.md ~290k tok/s/chip perfect-MXU number; the --int8-kv arm
# pairs with it as the KV-cache-bandwidth A/B (decode is HBM-bound,
# so halving cache bytes is the knob that should move tokens/s).
# Queued here -- after the training headline and the re-queued
# b128/b256/best MFU rungs -- for the same reason as the serve arms
# above: a new metric family with no banked baseline must not starve
# the round's primary unbanked claim.
run bench_serve_generate $QT python bench.py --serve --generate --quick
run bench_serve_generate_int8kv $QT python bench.py --serve --generate --quick --int8-kv

# paged KV cache + chunked prefill (ISSUE 17): the serving
# memory-economy A/B against the slot-cache rows above -- same
# model, same offered load, but the KV lives in a shared page pool
# behind a radix prefix index.  The rows carry prefix_hit_rate /
# pages_per_request / kv_bytes_per_token sidecars; the slot rows
# carry the same columns (None for the page-economy pair) so the
# diff is column-wise.
run bench_serve_generate_paged $QT python bench.py --serve --generate --quick --paged --prefill-chunk 8
run bench_serve_generate_paged_int8kv $QT python bench.py --serve --generate --quick --paged --prefill-chunk 8 --int8-kv

# speculative decoding (ISSUE 19): the last serving-memory-economy
# lever -- a half-depth draft proposes k tokens, the target verifies
# the window in ONE pass, so accepted tokens amortize the HBM-bound
# cache read.  The row's in-bench probe pins exact greedy equivalence
# vs the non-speculative oracle twin (spec_equivalent=true or the arm
# fails), and accepted_draft_rate / verify_per_token ride as the
# amortization sidecars; the paged twin composes with prefix sharing
# + chunked prefill, pairing column-wise with the arms above.
run bench_serve_generate_spec $QT python bench.py --serve --generate --quick --speculative
run bench_serve_generate_paged_spec $QT python bench.py --serve --generate --quick --speculative --paged --prefill-chunk 8

# continuous deployment (ISSUE 13): how fast weights roll through a
# 2-replica serving fleet under live traffic -- rolls/minute with
# the contract sidecars (dropped_during_swap MUST be 0, per-replica
# out-of-rotation downtime p50/p99, promote/rollback outcomes from
# fleet_ledger.jsonl).  Queued after the generate arms: same
# new-family-never-starves-the-headline reasoning.
run bench_serve_fleet $QT python bench.py --serve --fleet --quick

# serving self-healing (ISSUE 20): MTTR from a hard replica kill
# mid-decode to the first recovered continuation token on a
# survivor, with lost_requests as a HARD rc-1 gate (a journal left
# with open entries breaks the contract whatever the MTTR says);
# detection latency, requeue/respawn counts and degradation-rung
# occupancy ride as sidecars.  Queued right after the fleet arm it
# degrades from.
run bench_serve_fleet_recovery $QT python bench.py --serve --fleet --recovery --quick

# --- tier 4: the remaining BASELINE workloads ------------------------
# seq2seq FIRST: it is the variable-shape allreduce configuration
# (VERDICT #4) -- the datum no other workload stands in for -- and
# must not starve behind the transformer pair when a window closes
# mid-tier.  Then the two giant compiles LAST, with a smaller-batch
# vgg16 attempt (smaller program) before the standard one so SOME
# vgg16 datum banks even if the full config kills the compile
# service again (per_device_batch_override is recorded in the row,
# so the config is honest)
run bench_seq2seq $QT python bench.py --model seq2seq --quick
run bench_transformer $QT python bench.py --model transformer --quick
run bench_transformer_check $QT python bench.py --model transformer --quick --check

# flash-attention kernel vs XLA attention + the per-kernel tile sweep
# (beside the tiles _flash_blocks derives)
run_with pred_wrote flash_attn 3000 \
    python benchmarks/flash_attention_bench.py --sweep

# measured strategy comparison + profiler traces (VERDICT r3 item 9)
run_with pred_wrote strategy_trace $QT \
    python benchmarks/strategy_trace.py

# Mosaic kernel gate (fast when compile cache is warm); with
# JAX_PLATFORMS unset conftest leaves JAX on the chip
run_with pred_pytest_green mosaic_gate 1200 \
    env -u JAX_PLATFORMS \
    python -m pytest tests/test_tpu_mosaic.py -v

# --- tier 5: the giant compiles, LAST --------------------------------
run bench_googlenetbn $QT python bench.py --model googlenetbn --quick
run bench_vgg16_b16 $QT python bench.py --model vgg16 --quick --batch 16
run bench_vgg16 $QT python bench.py --model vgg16 --quick

# (the 8->256 scaling projection regen runs in the EXIT trap above,
# so it also covers the circuit-breaker abort path)

echo "=== series done; JSON lines:" >&2
for f in "$RES"/bench_*_"$TAG".out; do
  tail -1 "$f"
done
date -u +%Y-%m-%dT%H:%M:%SZ > "$RES/series_${TAG}.done"
