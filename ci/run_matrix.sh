#!/usr/bin/env bash
# Device-count matrix, mirroring the reference CI's
#   for NP in 1 2 3; do mpiexec -n ${NP} nosetests ...; done
# (.travis.yml:55) with XLA's virtual host devices in place of MPI
# processes.  The full suite runs at 8; the device-agnostic
# distributed tests run additionally at 1, 2 and 3.
set -euo pipefail
cd "$(dirname "$0")/.."
# the suite is CPU-only (tests/conftest.py adds the virtual devices)
export JAX_PLATFORMS=cpu

for N in 1 2 3; do
  echo "=== device matrix: ${N} virtual device(s) ==="
  XLA_FLAGS="--xla_force_host_platform_device_count=${N}" \
    python -m pytest tests/test_device_matrix.py -q
done

# fast set (default: @pytest.mark.slow excluded) is the edit-test
# loop; the FULL set runs once here so no coverage is lost
echo "=== fast suite: 8 virtual devices ==="
python -m pytest tests/ -q

echo "=== slow tail: 8 virtual devices ==="
python -m pytest tests/ -q --runslow -m slow \
  --ignore=tests/test_multiprocess.py \
  --ignore=tests/test_supervisor_mp.py

# ELASTIC + CORRUPTION LEG (ISSUE 5): 3 real jax.distributed
# processes train ZeRO-1, get SIGTERMed into a manifest-tagged
# regathered npz checkpoint, and RESUME AT 2 PROCESSES with the
# optimizer partitions re-split 6->4 devices, matching the
# fixed-topology oracle trajectory; plus corrupt-newest ->
# fallback-to-previous-valid (bit-rotted snapshot skipped with the
# typed CheckpointSkippedWarning, never loaded silently).  Runs
# here, in the full-coverage pass -- the fast (tier-1) halves of the
# integrity layer live in tests/test_chaos.py, so tier-1 wall time
# stays inside its budget.
echo "=== elastic topology-change + checkpoint-corruption leg ==="
python -m pytest tests/test_multiprocess.py -q --runslow \
  -k 'elastic or corrupt'

# MULTI-CONTROLLER CHAOS LEG (VERDICT r5 items 5-6): 2-3 REAL
# jax.distributed CPU processes (gloo collectives, one coordination
# service) run the multiprocess suite once CLEAN and once UNDER
# INJECTED FAULTS (chainermn_tpu.utils.chaos): dropped p2p publishes
# retried through, a killed peer surfacing as a typed PeerDeadError
# within its deadline, dead-receiver GC + cursor rewind, NaN-burst
# divergence checkpoints, and a SIGTERM mid-step producing a
# collective orbax checkpoint that auto-resumes to the exact
# uninterrupted loss trajectory.  See docs/fault_tolerance.md.
echo "=== multi-controller chaos leg: real jax.distributed CPU processes ==="
python -m pytest tests/test_multiprocess.py -q --runslow \
  -k 'not elastic and not corrupt and not doctor and not protocol'

# TELEMETRY DOCTOR LEG (ISSUE 8 acceptance): the cross-rank
# diagnosis proved end-to-end over real jax.distributed processes.
# (1) chaos-delay variant: a rank-restricted fixed p2p delay
# (rank=1;delay_send=*:0.05) -- `telemetry doctor` must name rank 1
# as the chronic straggler with the lagging phase send_obj;
# (2) chaos-kill post-mortem: rank 1 dies at a kill_recv site and
# the doctor -- from the flight record flushed across os._exit, the
# event-log tail and the heartbeat files, all written BEFORE the
# death -- must report the dead rank, its last completed collective
# seq, and the open recv_obj span the survivor was blocked in.
echo "=== telemetry doctor leg: straggler attribution + crash post-mortem ==="
python -m pytest tests/test_multiprocess.py -q --runslow -k 'doctor'

# PROTOCOL-DIVERGENCE LEG (ISSUE 16 acceptance): the commcheck
# dynamic twin proved over real jax.distributed processes.  Two
# 2-proc runs of an interleaved allreduce_obj/barrier protocol:
# (1) CLEAN -- the doctor's protocol-divergence verdict must be
# silent and the capture healthy; (2) chaos-injected
# (rank=1;extra_collective=@1) -- rank 1 records one phantom
# collective span mid-protocol, and `telemetry doctor` must name the
# first divergent position with each rank's surrounding ops (the
# same commcheck.verify_streams core the static gate runs, fed from
# the replayed per-rank seq streams).  See docs/observability.md.
echo "=== protocol-divergence leg: commcheck replay over real processes ==="
python -m pytest tests/test_multiprocess.py -q --runslow -k 'protocol'

# SUPERVISOR LEG (ISSUE 9): the self-healing loop proved unattended
# over real jax.distributed CPU procs -- one `python -m
# chainermn_tpu.supervisor` invocation per scenario, the ledger's
# machine-readable verdicts asserted.  (1) chaos kill_step mid-train:
# classified 'killed' to the same rank the doctor accuses, elastic
# shrink 3->2, resume from the periodic checkpoint, finished run
# matches the fixed-topology oracle; (2) hang_step wedge (heartbeat
# fresh, iteration frozen): progress-watch detection, SIGTERM-grace-
# SIGKILL escalation, culprit named from the chaos-event history,
# pod shrinks and finishes; (3) checkpoint corrupted on every restart:
# typed EXIT_CKPT_CORRUPT relaunch deaths -> crash-loop abort inside
# the restart budget with a non-zero supervisor exit.  Slow-marked,
# tier-1 budget untouched (fast policy units: tests/test_supervisor.py).
echo "=== supervisor leg: kill->shrink->resume, hang->escalation, crash-loop abort ==="
python -m pytest tests/test_supervisor_mp.py -q --runslow

# SLICE-LOSS GOODPUT LEG (ISSUE 18 acceptance): slice-level failure
# domains + async checkpointing + the unified goodput report, end to
# end over real jax.distributed CPU procs.  4 workers run as 2
# slices of 2 (--slices 2; each rank's CHAINERMN_TPU_SLICE names its
# domain); chaos slice_loss hard-kills EVERY rank of slice 1
# mid-train.  The supervisor must classify the whole-slice death
# (granularity=slice, both member ranks named, counted as ONE
# failure), shrink by the whole slice 4 -> 2 -- never splitting one
# -- resume from the async npz checkpoint, and complete.  Then
# `telemetry goodput` joins the ledger with every attempt's capture:
# the decomposition must sum to the wall clock (+-1%), bank a
# NONZERO restart-downtime bucket, and keep goodput_fraction inside
# (0, 1) and above the chaos floor.  See docs/fault_tolerance.md
# ("Goodput").
echo "=== slice-loss goodput leg: 2x2 slices, whole-slice kill -> shrink -> goodput report ==="
SLICE_DIR=$(mktemp -d /tmp/slice_goodput.XXXXXX)
CHAINERMN_TPU_CHAOS='slice_loss=@2:1' \
  python -m chainermn_tpu.supervisor -n 4 --slices 2 \
  --out "${SLICE_DIR}" --steps 6 --ckpt-every 2 --local-devices 2 \
  --stall-timeout 30 --startup-grace 120 --attempt-timeout 420 \
  --no-oracle
python -m chainermn_tpu.telemetry goodput "${SLICE_DIR}" --floor 0.02
python - "${SLICE_DIR}" <<'PY'
import json, sys
d = sys.argv[1]
ledger = [json.loads(l) for l in open(d + '/supervisor_ledger.jsonl')]
fails = [e for e in ledger if e['event'] == 'failure']
assert len(fails) == 1, [e['event'] for e in ledger]
assert fails[0]['granularity'] == 'slice', fails[0]
assert sorted(fails[0]['dead_ranks']) == [2, 3], fails[0]
dec = [e for e in ledger if e['event'] == 'decision'][0]
assert dec['action'] == 'shrink' and dec['granularity'] == 'slice', dec
assert (dec['world_before'], dec['world_after']) == (4, 2), dec
assert any(e['event'] == 'complete' for e in ledger), \
    [e['event'] for e in ledger]
gp = json.load(open(d + '/goodput_report.json'))
assert 0.0 < gp['goodput_fraction'] < 1.0, gp['goodput_fraction']
assert gp['buckets_s']['restart_downtime'] > 0.0, gp['buckets_s']
total = sum(gp['buckets_s'].values())
assert abs(total - gp['wall_s']) <= 0.01 * gp['wall_s'], \
    (total, gp['wall_s'])
print('slice goodput OK: fraction=%.4f, downtime=%.3fs of %.3fs '
      'wall, slice shrink 4->2'
      % (gp['goodput_fraction'],
         gp['buckets_s']['restart_downtime'], gp['wall_s']))
PY
rm -rf "${SLICE_DIR}"

# TELEMETRY SMOKE LEG (ISSUE 6): capture -> merge -> report on the
# mnist example.  The env var is the ONLY switch (zero-cost-off
# contract): the run records step phases, collective/trace marks and
# metrics per rank; the report CLI merges them, prints the step
# timeline + overlap fraction, exits 2 on an empty capture, and the
# asserts below pin a non-empty timeline and a valid Prometheus
# export.
echo "=== telemetry smoke: mnist capture -> merge -> report ==="
TELEMETRY_DIR=$(mktemp -d /tmp/telemetry_smoke.XXXXXX)
CHAINERMN_TPU_TELEMETRY="${TELEMETRY_DIR}" \
  python examples/mnist/train_mnist.py --quick --cpu -b 96 \
  --out "${TELEMETRY_DIR}/result"
python -m chainermn_tpu.telemetry report "${TELEMETRY_DIR}"
# the doctor must also accept the capture: exit 0 and a parseable
# verdict JSON (single-controller, so skew fields are honest Nones)
python -m chainermn_tpu.telemetry doctor "${TELEMETRY_DIR}"
python - "${TELEMETRY_DIR}" <<'PY'
import json, sys
from chainermn_tpu.telemetry import report as trep
d = sys.argv[1]
rep = json.load(open(d + '/merged_report.json'))
assert rep['n_spans'] > 0, 'empty telemetry timeline'
assert rep['steps'], 'no per-step rows in merged timeline'
assert rep['step_time_ms'].get('p50') is not None, rep['step_time_ms']
ov = rep['overlap']['overlap_fraction']
assert ov is None or 0.0 <= ov <= 1.0, rep['overlap']
prom = open(d + '/metrics.prom').read()
bad = trep.validate_prometheus(prom)
assert not bad, 'malformed Prometheus lines: %r' % bad[:3]
doc = json.load(open(d + '/doctor_report.json'))
assert 'verdict' in doc and 'healthy' in doc['verdict'], doc.keys()
assert doc['verdict']['dead_ranks'] == [], doc['verdict']
print('telemetry smoke OK: %d spans, %d step rows, overlap=%r, '
      '%d prom lines, doctor verdict healthy=%r'
      % (rep['n_spans'], len(rep['steps']), ov,
         len(prom.splitlines()), doc['verdict']['healthy']))
PY
rm -rf "${TELEMETRY_DIR}"

# SERVING SLO SMOKE LEG (ISSUE 12): a short autoregressive serve
# window recorded as a full telemetry capture (per-request trace
# spans + serve metrics + the live monitor's slo_snapshot.json),
# then replayed offline: `telemetry slo` must return a parseable
# ok/warn/breach verdict (exit 0), and `telemetry report` must
# reconstruct at least one request timeline with every stage present
# (queue_wait -> bucket_pack -> prefill -> decode) and stage budgets
# summing to the end-to-end latency (+-1 ms) -- the ISSUE 12
# acceptance observable, end to end over real executables.
echo "=== serving slo smoke: generate capture -> slo verdict + request timeline ==="
# the smoke window runs the PAGED engine with chunked prefill
# (ISSUE 17): the capture must still tile every request's stage
# spans (queue_wait -> bucket_pack -> prefill_chunk* -> prefill ->
# decode) and the paged sidecars must land on the bench row.
SLO_DIR=$(mktemp -d /tmp/slo_smoke.XXXXXX)
python bench.py --serve --generate --quick --cpu --paged \
  --prefill-chunk 8 --serve-requests 24 --capture "${SLO_DIR}" \
  > "${SLO_DIR}/bench_row.json"
python -m chainermn_tpu.telemetry slo "${SLO_DIR}"
python -m chainermn_tpu.telemetry report "${SLO_DIR}" > /dev/null
python - "${SLO_DIR}" <<'PY'
import json, sys
d = sys.argv[1]
slo = json.load(open(d + '/slo_report.json'))
v = slo['verdict']['overall']
assert v in ('ok', 'warn', 'breach'), slo['verdict']
assert slo['n_request_records'] > 0, 'slo replay saw no records'
snap = json.load(open(d + '/slo_snapshot.json'))
assert snap['verdict']['overall'] in ('ok', 'warn', 'breach'), snap
rep = json.load(open(d + '/merged_report.json'))
reqs = rep['requests']
assert reqs and reqs['completed'] > 0, reqs
worst = reqs['worst']
stages = set(worst['stage_ms'])
assert {'queue_wait', 'bucket_pack', 'prefill', 'decode'} <= stages, \
    stages
assert abs(worst['stage_sum_ms'] - worst['e2e_ms']) <= 1.0, worst
row = json.load(open(d + '/bench_row.json'))
assert row.get('slo_verdict') in ('ok', 'warn', 'breach'), \
    row.get('slo_verdict')
assert row.get('paged') is True and row.get('paged_kv'), 'paged row'
assert row['paged_kv']['prefill_chunks'] > 0, row['paged_kv']
assert row.get('kv_bytes_per_token'), 'kv_bytes_per_token sidecar'
assert row.get('pages_per_request') is not None, 'pages sidecar'
print('slo smoke OK: verdict=%s (row %s), %d requests traced, worst '
      '%s e2e %.3f ms (stage sum %.3f ms)'
      % (v, row['slo_verdict'], reqs['count'], worst['request_id'],
         worst['e2e_ms'], worst['stage_sum_ms']))
PY
rm -rf "${SLO_DIR}"

# SPECULATIVE DECODING SMOKE LEG (ISSUE 19): the paged speculative
# engine under a real open-loop window, with the two acceptance
# observables asserted straight off the bench row: (1) the in-bench
# equivalence probe -- the speculative engine's outputs are
# token-for-token identical to a non-speculative oracle twin's
# (spec_equivalent, the exact-greedy pin, not a similarity bound);
# (2) amortization accounting -- draft proposals flowed
# (accepted_draft_rate is a number, possibly 0.0 with an untrained
# draft) and verify_per_token < 1 (strictly fewer target passes than
# tokens whenever anything was accepted; <= 1 always).  The capture
# replay must also carry the serve_draft/serve_verify phases and the
# accepted-draft-rate block in serve_summary.
echo "=== speculative smoke: draft-propose / target-verify equivalence + accepted rate ==="
SPEC_DIR=$(mktemp -d /tmp/spec_smoke.XXXXXX)
python bench.py --serve --generate --speculative --quick --cpu \
  --paged --serve-requests 24 --capture "${SPEC_DIR}" \
  > "${SPEC_DIR}/bench_row.json"
python -m chainermn_tpu.telemetry report "${SPEC_DIR}" > /dev/null
python - "${SPEC_DIR}" <<'PY'
import json, sys
d = sys.argv[1]
row = json.load(open(d + '/bench_row.json'))
assert row.get('spec_equivalent') is True, (
    'speculative output diverged from the oracle: %r'
    % row.get('spec_equivalent'))
spec = row.get('speculative')
assert spec, 'speculative block missing from the generate row'
assert spec['draft_proposed'] > 0, spec
rate = row.get('accepted_draft_rate')
assert rate is not None and 0.0 <= rate <= 1.0, rate
vpt = row.get('verify_per_token')
assert vpt is not None and vpt <= 1.0, vpt
assert spec['verify_steps'] > 0, spec
from chainermn_tpu.telemetry import report as trep
assert 'serve_draft' in trep.SERVE_PHASES
assert 'serve_verify' in trep.SERVE_PHASES
rep = json.load(open(d + '/merged_report.json'))
gen = ((rep.get('serve') or {}).get('generate')) or {}
sb = gen.get('speculative')
assert sb and sb['draft_proposed'] > 0, sb
print('speculative smoke OK: equivalent=EXACT rate=%.3f '
      'verify/token=%.3f (%d drafts proposed)'
      % (rate, vpt, spec['draft_proposed']))
PY
rm -rf "${SPEC_DIR}"

# FLEET LEG (ISSUE 13 acceptance): train-to-serve continuous
# deployment proved end to end over REAL subprocess replicas -- one
# `python -m chainermn_tpu.serving.fleet` invocation per scenario,
# every verdict asserted from fleet_ledger.jsonl.  (1) promote: a
# few real CPU sgd steps -> manifest-tagged snapshot -> a 2-replica
# fleet picks it up and rolls it under open-loop traffic, canary ok,
# promote -- with ZERO requests shed (per-swap shed counters AND the
# traffic totals both zero: the roll is invisible to clients);
# (2) canary breach -> rollback: the replica chaos handout ships a
# serve_slow latency regression that bites only on a hot-swapped
# version, the judge breaches on the inter-token delta vs the
# incumbent's matched window, the canary swaps back, the fleet
# converges on the incumbent; (3) swap_kill mid-roll: the controller
# dies at a swap point with replicas on MIXED versions, and a
# relaunch over the same --out converges every replica to one
# consistent version, recording `converged` with the recovered roll
# named.  Slow-marked; the fast in-process halves run in tier-1
# (tests/test_fleet.py).  See docs/serving.md "Continuous
# deployment".
echo "=== fleet leg: roll->promote, canary breach->rollback, swap_kill convergence ==="
python -m pytest tests/test_fleet_mp.py -q --runslow

# SERVING SELF-HEALING LEG (ISSUE 20 acceptance): a replica worker
# process is chaos hard-killed mid-decode (replica_kill=@2:1 --
# os._exit(46) at replica 1's 2nd decode tick, generations in
# flight) under open-loop traffic with the crash-safe request
# journal armed (--recover).  The ledger must prove: every in-flight
# request requeued onto the survivor as an exact continuation and
# attributed by id in `recovered`; a replacement worker respawned
# FROM THE INCUMBENT snapshot and spliced back into the front; zero
# lost requests, zero client-visible errors.  Then the crash-loop
# twin: replica_kill=* survives the one-shot strip by design, the
# respawned worker dies right back, and the shared restart policy
# aborts rc 1 within the crash window.  See docs/fault_tolerance.md
# ("Serving self-healing").
echo "=== serving self-healing leg: replica kill -> requeue -> respawn; crash-loop abort ==="
HEAL_DIR=$(mktemp -d /tmp/fleet_heal.XXXXXX)
CHAINERMN_TPU_CHAOS= \
  python -m chainermn_tpu.serving.fleet --out "${HEAL_DIR}" \
  --rolls 0 --duration 8 --replicas 2 --rate 20 \
  --max-new-tokens 8 --max-prompt-len 16 --traffic-prompt-max 4 \
  --recover --replica-chaos 'replica_kill=@2:1' \
  > "${HEAL_DIR}/summary.json"
python - "${HEAL_DIR}" <<'PY'
import json, sys
d = sys.argv[1]
ledger = [json.loads(l) for l in open(d + '/fleet_ledger.jsonl')]
dead = [e for e in ledger if e['event'] == 'replica_dead']
assert len(dead) == 1 and dead[0]['replica'] == 'replica-1', dead
assert dead[0]['returncode'] == 46 and dead[0]['exit'] == 'crash', \
    dead[0]
requeues = [e['request_id'] for e in ledger
            if e['event'] == 'requeue']
rec = [e for e in ledger if e['event'] == 'recovered'][0]
assert rec['request_ids'] == requeues, (rec, requeues)
assert rec['shed'] == [], rec
respawn = [e for e in ledger if e['event'] == 'respawn'][0]
assert respawn['replica'] == 'replica-1r1', respawn
summary = json.loads(open(d + '/summary.json').read().strip()
                     .splitlines()[-1])
assert respawn['version'] == summary['version'], \
    (respawn, summary['version'])   # incumbent weights
r = summary['recovery']
assert r['deaths'] == 1 and r['respawns'] == 1, r
assert r['lost_requests'] == 0 and not r['aborted'], r
t = summary['traffic']
assert t['errors'] == 0 and t['served'] == t['offered'] > 0, t
print('self-healing OK: %d requeued (%s), respawned at v%d, '
      '%d/%d served, 0 lost'
      % (len(requeues), ','.join(requeues) or '-',
         respawn['version'], t['served'], t['offered']))
PY
if CHAINERMN_TPU_CHAOS= \
  python -m chainermn_tpu.serving.fleet --out "${HEAL_DIR}/loop" \
  --rolls 0 --duration 60 --replicas 2 --rate 20 \
  --max-new-tokens 8 --max-prompt-len 16 --traffic-prompt-max 4 \
  --recover --replica-chaos 'replica_kill=*' \
  > "${HEAL_DIR}/loop_summary.json"; then
  echo "crash loop did NOT abort rc 1" >&2; exit 1
fi
python - "${HEAL_DIR}" <<'PY'
import json, sys
d = sys.argv[1]
ledger = [json.loads(l) for l in open(d + '/loop/fleet_ledger.jsonl')]
aborts = [e for e in ledger if e['event'] == 'abort']
assert len(aborts) == 1 and 'crash_loop' in aborts[0]['reason'], \
    aborts
deaths = [e for e in ledger if e['event'] == 'replica_dead']
assert len(deaths) == 3, deaths   # threshold, inside the budget
print('crash-loop abort OK: 3 deaths -> %r' % aborts[0]['reason'])
PY
rm -rf "${HEAL_DIR}"

# CONVERGENCE-UNDER-CHAOS LEG (ISSUE 15 acceptance): the streaming
# input pipeline proved end to end over REAL jax.distributed CPU
# processes.  (1) stream_elastic: training on streamed record shards
# at 3 procs is SIGTERMed MID-EPOCH (the npz checkpoint carries the
# exact stream cursor), resumed at 2 procs, and the concatenated
# per-rank sample-id ledgers equal the uninterrupted fixed-topology
# oracle's stream EXACTLY -- every (epoch, position) consumed once
# with the oracle's id, no repeats, no drops -- while the combined
# loss trajectory matches the oracle (atol 1e-4).  (2) the payoff
# scenario: one `python -m chainermn_tpu.supervisor` invocation
# trains the learnable streamed dataset to its target loss while
# chaos hard-kills rank 1; the supervisor classifies, shrinks 3 -> 2
# and resumes, and the union of consumed sample ids over ALL
# attempts is exactly epoch 0's id set, position-consistent with the
# deterministic oracle stream.  Slow-marked; the fast halves
# (determinism pin, typed corruption, cursor edges) run in tier-1
# via tests/test_data.py.  See docs/data_pipeline.md.
echo "=== convergence-under-chaos leg: streamed shards + supervisor healing ==="
python -m pytest tests/test_data_mp.py -q --runslow

# REAL-DATA convergence gate (VERDICT r4 next #8): the same positive
# gate, fed genuine handwritten digits (sklearn's vendored UCI scans,
# no egress) through the CHAINERMN_TPU_MNIST hook -- the reference's
# actual >=0.95 bar on real data, alongside the antipodal synthetic
# run above.  -s so the test's data-source line lands in the CI log.
echo "=== real-data convergence gate ==="
python ci/make_digits_npz.py /tmp/digits_mnist.npz
CHAINERMN_TPU_MNIST=/tmp/digits_mnist.npz \
  python -m pytest "tests/test_mnist.py::test_mnist_convergence" -q -s
