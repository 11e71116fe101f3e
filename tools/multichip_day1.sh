#!/usr/bin/env bash
# Hardware-day runbook — the EXACT ordered commands for the first session
# with a real multi-chip TPU slice.  Each step names the artifact it must
# produce so real hardware time burns zero minutes on rediscovery.
# (`python chip_smoke.py` / `--chips 4` at the repo root is the quick proof
# that the main path starts on the chip; run it first.)
#
# A chip belongs to one process at a time.  This shell never touches JAX and
# runs every step as its own Python process, one after another, so each has
# the chip to itself; steps that spawn workers spawn CPU-only ones
# (utils/proc_world.py).
#
#   ./tools/multichip_day1.sh            # run everything possible here
#   DRY_RUN=1 ./tools/multichip_day1.sh  # print the plan, run nothing
#
# On a host WITHOUT a multi-chip slice every multi-chip step prints
# "SKIPPED (no hardware)" and the single-chip steps still run, so the
# script itself is exercised (and CI-checkable) before the day arrives.
set -u
cd "$(dirname "$0")/.."
REPO="$PWD"
TS="$(date -u +%Y%m%dT%H%M%S)"
OUT="${OUT:-$REPO/hwday_$TS}"
ROUND="${ROUND:-r05}"
PY_TPU="env PYTHONPATH=$REPO python"
DRY="${DRY_RUN:-0}"

# How many TPU devices does this host actually see?
NDEV=$($PY_TPU -c 'import jax; print(sum(1 for d in jax.devices() if d.platform != "cpu"))' 2>/dev/null || echo 0)
echo "== multichip day-1 runbook: $NDEV TPU device(s) visible =="
[ "$DRY" = 1 ] || mkdir -p "$OUT"

run() {  # run <min_devices> <artifact> <desc> -- cmd...
    local need="$1" artifact="$2" desc="$3"; shift 3; shift  # drop '--'
    echo
    echo "== $desc"
    echo "   artifact: $artifact"
    echo "   command:  $*"
    if [ "$DRY" = 1 ]; then echo "   DRY_RUN: not executed"; return 0; fi
    if [ "$NDEV" -lt "$need" ]; then
        echo "   SKIPPED (no hardware: need >= $need TPU devices, have $NDEV)"
        return 0
    fi
    if "$@"; then echo "   OK"; else echo "   FAILED (continuing — record it)"; fi
}

# ---- preflight: watchdog/flight-recorder knob round-trip --------------
# The hang watchdog (docs/observability.md) is the safety net for every
# multi-chip step below: a wedged collective dumps flight_<rank>.json
# NEXT TO that step's artifact (CHAINERMN_TPU_FLIGHT_DIR, default the
# process cwd) — merge them with `tools/obs_report.py --flight <dir>`.
# The env knobs must survive a from_env/to_env round-trip before a
# hardware day depends on them; this check is cheap and hardware-free, so
# it runs even under DRY_RUN.
echo
echo "== watchdog env knob round-trip (flight dumps land next to each step's artifact)"
if $PY_TPU - <<'PYEOF'
from chainermn_tpu.observability import WatchdogConfig

cfg = WatchdogConfig.from_env({
    "CHAINERMN_TPU_WATCHDOG_DEADLINE": "120",
    "CHAINERMN_TPU_WATCHDOG_STEP_K": "6",
    "CHAINERMN_TPU_FLIGHT_DIR": "hwday_out",
})
assert cfg.deadline_s == 120.0 and cfg.step_stall_factor == 6.0, cfg
again = WatchdogConfig.from_env(cfg.to_env())
assert again == cfg, (cfg, again)
print("   knobs round-trip OK: " + " ".join(sorted(cfg.to_env())))
PYEOF
then echo "   OK"; else echo "   FAILED (continuing — record it)"; fi

# ---- preflight: cmn-lint static schedule analysis ---------------------
# Every hang class the watchdog above diagnoses at runtime is statically
# visible before a step runs: lint the example entry points' collective
# schedules (schedule-desync, census-drift, unpinned-transpose, ... —
# docs/static_analysis.md) so a schedule bug fails HERE, on this host,
# not at step 40k on the slice.  Needs zero TPU devices; the findings
# JSON renders next to the flight timeline via `obs_report --lint`.
run 0 "$OUT/CMN_LINT_$ROUND.json" \
    "cmn-lint static preflight: prove every flavor's collective schedule safe before burning chip time" -- \
    bash -c "$PY_TPU tools/cmn_lint.py examples/mnist --json \
        --out '$OUT/CMN_LINT_$ROUND.json' > /dev/null"

run 0 "$OUT/CMN_LINT_SERVING_$ROUND.json" \
    "cmn-lint the serving decode step (tp=2 Megatron shard_map): the same schedule every lockstep controller must trace from the broadcast plan" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU tools/cmn_lint.py serving/decode --json \
        --out '$OUT/CMN_LINT_SERVING_$ROUND.json' > /dev/null"

# ---- preflight: control-plane protocol sweep --------------------------
# The data-plane lint above says nothing about the DCN object plane the
# hot-swap broadcast / telemetry gathers / supervisor choreography ride.
# Sweep the static protocol model (tag-band-collision,
# lockstep-divergence, unmatched-send-recv, wrapper-surface-drift —
# docs/static_analysis.md) so a rank-guarded bcast_obj or a tag crossing
# wires fails HERE, not as a watchdog flight dump at step 40k.  Exit is
# nonzero on any error finding; hardware-free (pure AST).
run 0 "$OUT/PROTOCOL_LINT_$ROUND.json" \
    "cmn-lint --protocol: static lockstep/tag-band/wrapper-drift sweep of the host object plane" -- \
    bash -c "env JAX_PLATFORMS=cpu $PY_TPU tools/cmn_lint.py --protocol \
        --out '$OUT/PROTOCOL_LINT_$ROUND.json' > /dev/null"

# ---- single-chip steps (run today, re-run on the slice for parity) ----

run 1 "$OUT/TPU_EVIDENCE_$ROUND.json" \
    "tpu_smoke: the full on-chip evidence suite" -- \
    $PY_TPU tools/tpu_smoke.py --out "$OUT/TPU_EVIDENCE_$ROUND.json"

run 1 "$OUT/CONVERGENCE_$ROUND.json" \
    "convergence ledger ON THE CHIP (bf16 numerics are the point)" -- \
    $PY_TPU tools/convergence_ledger.py --out "$OUT/CONVERGENCE_$ROUND.json"

run 1 "$OUT/VIT_BENCH_$ROUND.json" \
    "ViT-B/16 bench (a dense-matmul model beside the benchmark's cells: python3 -m chipbench.run)" -- \
    bash -c "$PY_TPU benchmarks/bench_vit.py > '$OUT/VIT_BENCH_$ROUND.json'"

# ---- serving: continuous-batching inference engine --------------------
# Hardware-free (forced CPU mesh) so the serving stack is exercised on
# every host: the run FAILS unless continuous admission beats the static
# batch at the same open-loop arrival rate, and the artifact feeds the
# perf gate's serving throughput floor (docs/serving.md).  On a slice,
# re-run WITHOUT the env override and with --tp to shard over ICI.
run 0 "$OUT/SERVING_$ROUND.json" \
    "continuous-batching serving bench on the 8-way CPU mesh: continuous vs static at the same arrival trace; perf_gate reads continuous.tokens_per_sec" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_serving.py --out '$OUT/SERVING_$ROUND.json' \
        --metrics '$OUT/SERVING_METRICS_$ROUND.jsonl' > /dev/null"

# ---- fleet serving: prefix cache + spec decode + router ---------------
# Hardware-free (forced CPU mesh): the full fleet artifact — prefix-
# cache A/B, draft+verify speculative decoding, and the 2-replica
# session-affine router open loop — then the STRICT serving floors
# (prefix.speedup >= 1.3, spec.accept_tokens_per_step > 1.0, session
# affinity unbroken; tools/perf_budgets.json, no regression slack).
# Render the hit-rate/acceptance lanes with
# `obs_report --serving $OUT/SERVING_FLEET_METRICS_$ROUND.jsonl`.
run 0 "$OUT/SERVING_FLEET_$ROUND.json" \
    "fleet serving gate: prefix-cache A/B + spec decode + 2-replica session-affine router, then perf_gate --serving strict floors" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_serving.py --spec-k 2 --replicas 2 \
            --out '$OUT/SERVING_FLEET_$ROUND.json' \
            --metrics '$OUT/SERVING_FLEET_METRICS_$ROUND.jsonl' > /dev/null \
        && $PY_TPU tools/perf_gate.py --serving '$OUT/SERVING_FLEET_$ROUND.json' \
            --out '$OUT/SERVING_FLEET_GATE_$ROUND.json'"

# ---- normalization boundary: fused-kernel probe + remat autotune ------
# Hardware-free (forced CPU mesh, smoke shapes) so the fused BN(+ReLU)
# Pallas path and the remat-policy autotuner run on every host; the probe
# artifact's `traffic` section is the deterministic modeled-HBM-bytes
# table the resnet_bn_traffic_bytes budget reads (direction: lower), so
# this leg must land before the PERF_GATE leg.  On a slice, re-run the
# probe WITHOUT the env override at --batch 256 --image 224 with the full
# variant set for the measured fusednorm delta (docs/performance.md
# "normalization boundary").
run 0 "$OUT/RESNET_PROBE_$ROUND.json" \
    "resnet probe incl. fusednorm variant on the 8-way CPU mesh (smoke timings; the traffic section feeds the resnet_bn_traffic_bytes budget)" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_resnet_probe.py --batch 8 --image 64 \
        --steps 2 --variants full,fusednorm \
        --out '$OUT/RESNET_PROBE_$ROUND.json' 2> /dev/null"

run 0 "$OUT/REMAT_TUNE_$ROUND.json" \
    "remat-policy autotune: sweep none/block/norm over the resnet configs, pick per-config winners from measured step time (on a slice, re-run without the env override)" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/run_configs.py --tune-remat \
        --out '$OUT/REMAT_TUNE_$ROUND.json' > /dev/null"

run 1 "$OUT/PERF_GATE_$ROUND.json" \
    "perf gate: fresh bench artifacts vs checked-in budgets (tools/perf_budgets.json; >3% regression on any tracked throughput FAILS this leg)" -- \
    $PY_TPU tools/perf_gate.py --budgets tools/perf_budgets.json \
        --root "$OUT" --out "$OUT/PERF_GATE_$ROUND.json"

# ---- collective planner: sweep -> autotune -> gate --------------------
# Hardware-free (forced CPU mesh) so the planner pipeline is exercised
# on every host; on a slice, re-run WITHOUT the env override to tune on
# real ICI/DCN (docs/collective_planner.md).
run 0 "$OUT/PLANNER_GATE_$ROUND.json" \
    "collective-planner autotune gate: sweep candidate plans, build the plan table, require the tuned pick to beat the best fixed flavor somewhere" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_allreduce.py --sweep '$OUT/ALLREDUCE_SWEEP_$ROUND.json' \
            --intra-size 4 --iters 10 --warmup 2 > /dev/null \
        && $PY_TPU tools/perf_gate.py --planner '$OUT/ALLREDUCE_SWEEP_$ROUND.json' \
            --table '$OUT/PLAN_TABLE_$ROUND.json' --out '$OUT/PLANNER_GATE_$ROUND.json'"

# ---- per-hop compressed plans: sweep -> autotune -> gate --------------
# Same pipeline as the PLANNER leg but with the compressed-inter-hop
# candidates (int8/fp8 DCN codes, bf16 ICI) in the sweep and a modeled
# DCN serialization term added to each row's time (--dcn-gbps; raw
# timings kept in us_measured).  0.03 GB/s is the CPU-host validation
# stress setting — the quantizer's CPU compute cost swamps any realistic
# modeled DCN, so only an aggressively slow link lets a compressed plan
# win a cell here; on a slice, re-run WITHOUT the env override and
# WITHOUT --dcn-gbps to tune on measured ICI/DCN (docs/compression.md
# "Per-hop compression").  The sweep artifact also carries the per-plan
# DCN-scope wire-byte table the dcn_wire_bytes budget reads.
run 0 "$OUT/PLANNER_GATE_COMPRESSED_$ROUND.json" \
    "compressed-hop planner gate: sweep incl. int8/fp8-DCN plans under modeled slow DCN, require a compressed plan to win at least one cell" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_allreduce.py \
            --sweep '$OUT/ALLREDUCE_SWEEP_COMPRESSED_$ROUND.json' \
            --intra-size 4 --dcn-gbps 0.03 --iters 10 --warmup 2 > /dev/null \
        && $PY_TPU tools/perf_gate.py \
            --planner '$OUT/ALLREDUCE_SWEEP_COMPRESSED_$ROUND.json' \
            --table '$OUT/PLAN_TABLE_COMPRESSED_$ROUND.json' \
            --out '$OUT/PLANNER_GATE_COMPRESSED_$ROUND.json'"

# ---- heterogeneous link striping: sweep -> autotune -> gate -----------
# Same pipeline again with the concurrent stage-group candidates
# (striped_plan: plain-ICI stripe || int8-DCN stripe at swept ratios)
# and BOTH link classes modeled (--link-gbps ici=X,dcn=Y adds
# plan_modeled_time_s — max over per-group chain times and per-link
# busy times — to each row; raw timings kept in us_measured).  The
# stress rates make the modeled wire term dominate CPU-measured time so
# a tuned split ratio can win cells here; --require-striped 2 makes the
# gate FAIL unless striped plans beat the best single-path plan in >= 2
# cells, and the artifact's striped.best_speedup feeds the
# striped_allreduce_speedup budget.  On a slice, re-run WITHOUT the env
# override and WITHOUT --link-gbps to tune ratios on measured ICI/DCN
# (docs/collective_planner.md "Concurrent stage groups").
run 0 "$OUT/PLANNER_GATE_STRIPED_$ROUND.json" \
    "striped planner gate: sweep incl. concurrent ICI||DCN stage-group plans under modeled heterogeneous links, require striped wins in >= 2 cells" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_allreduce.py \
            --sweep '$OUT/ALLREDUCE_SWEEP_STRIPED_$ROUND.json' \
            --intra-size 4 --link-gbps ici=0.2,dcn=0.01 \
            --stripe-ratios 0.5,0.6,0.7,0.8,0.9 --iters 10 --warmup 2 > /dev/null \
        && $PY_TPU tools/perf_gate.py \
            --planner '$OUT/ALLREDUCE_SWEEP_STRIPED_$ROUND.json' \
            --table '$OUT/PLAN_TABLE_STRIPED_$ROUND.json' \
            --require-striped 2 \
            --out '$OUT/PLANNER_GATE_STRIPED_$ROUND.json'"

# ---- MoE: matched-loss leg + all-to-all dispatch planner gate ---------
# FLOP-matched comparison first (top_k=1 expert MLP vs the dense MLP of
# identical width: same per-token FLOPs, E x parameters): the MoE run
# must reach a final loss at or below the dense baseline on the
# mode-mixture LM task.  The artifact feeds perf_gate --moe-bench below.
run 0 "$OUT/MOE_BENCH_$ROUND.json" \
    "FLOP-matched MoE vs dense LM on the mode-mixture task: MoE final loss must be <= dense (defaults bake the validated capacity-bound config)" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_moe.py --out '$OUT/MOE_BENCH_$ROUND.json' \
            > /dev/null"

# All-to-all dispatch planner gate: sweep the all-to-all plan zoo (flat,
# hierarchical intra->re-major->inter, bf16/fp8 narrow-DCN wires,
# striped) under modeled heterogeneous links, then require (a) non-flat
# plans to beat alltoall_flat in >= 2 cells, (b) >= 1.8x bf16-DCN byte
# shrink at the largest payload (feeds the moe_alltoall_dcn_bytes
# budget), and (c) the MOE_BENCH matched-loss check.  On a slice,
# re-run WITHOUT the env override and WITHOUT --link-gbps to tune the
# dispatch on measured ICI/DCN (docs/moe.md "Tuned dispatch").
run 0 "$OUT/PLANNER_GATE_ALLTOALL_$ROUND.json" \
    "MoE all-to-all planner gate: sweep the dispatch plan zoo under modeled heterogeneous links, require hierarchical wins in >= 2 cells + >= 1.8x bf16-DCN shrink + matched loss" -- \
    bash -c "env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        $PY_TPU benchmarks/bench_moe.py \
            --sweep '$OUT/ALLTOALL_SWEEP_$ROUND.json' \
            --intra-size 4 --link-gbps ici=0.2,dcn=0.01 \
            --iters 10 --warmup 2 > /dev/null \
        && $PY_TPU tools/perf_gate.py \
            --moe '$OUT/ALLTOALL_SWEEP_$ROUND.json' \
            --moe-bench '$OUT/MOE_BENCH_$ROUND.json' \
            --table '$OUT/PLAN_TABLE_ALLTOALL_$ROUND.json' \
            --out '$OUT/PLANNER_GATE_ALLTOALL_$ROUND.json'"

# ---- online autotuning: replay degraded-link spans -> retune gate -----
# Attribution-closed loop, offline leg: feed the committed degraded-DCN
# span dump (healthy ~16 GB/s ICI stage timings, ~0.5 GB/s DCN stage
# timings, plus the attribution_regression events that arm the tuner)
# through the OnlineTuner's observation store.  The tuner recovers the
# per-link GB/s from the plan_stage spans, re-prices the candidate zoo
# through plan_modeled_time_s at the observed rates, and must decide to
# hot-swap with best_speedup >= 1.05 over the previously active plan.
# Deterministic and device-free (no mesh, no 2-process spawn); the
# artifact's retune.best_speedup feeds the retune_speedup budget.
run 0 "$OUT/ONLINE_TUNE_$ROUND.json" \
    "online-tune gate: replay committed degraded-DCN span dump through the OnlineTuner, require a profitable (>=1.05x) plan-table retune decision" -- \
    bash -c "$PY_TPU benchmarks/bench_allreduce.py \
            --replay-spans tests/data/degraded_dcn_spans.json \
            --replay-topology inter:2,intra:4 \
            --replay-out '$OUT/ONLINE_TUNE_$ROUND.json' \
        && $PY_TPU tools/perf_gate.py \
            --online-tune '$OUT/ONLINE_TUNE_$ROUND.json'"

# ---- global scheduler: joint-vs-independent workload tuning gate ------
# Contention-aware joint plan tuning (docs/collective_planner.md "Joint
# scheduling across communicators"): build the two-slot step workload
# the contention observatory measures overlapping (bucketed-FSDP
# gradient allreduce + MoE dispatch/combine all-to-all) on the 8-device
# mesh shape, tune the slots independently (today's per-communicator
# argmin) and jointly (planner.schedule.jointly_tune — coordinate
# descent under the fair-share link simulator), and require the joint
# schedule to beat independent by >=1.05x with at least one slot's plan
# changed — the ceded-link decision (e.g. the striped allreduce gives
# up its DCN stripe while the MoE exchange owns that wire).
# Deterministic and device-free; comparison.speedup feeds the
# joint_schedule_speedup budget.
run 0 "$OUT/JOINT_SWEEP_$ROUND.json" \
    "joint-schedule gate: jointly tune the allreduce+MoE step workload under shared links, require >=1.05x over independent tuning with >=1 ceded-link plan change" -- \
    bash -c "$PY_TPU benchmarks/bench_joint.py \
            --topology inter:2,intra:4 --link-gbps ici=0.2,dcn=0.02 \
            --allreduce-kib 4096 --moe-kib 8192 \
            --out '$OUT/JOINT_SWEEP_$ROUND.json' \
        && $PY_TPU tools/perf_gate.py \
            --joint '$OUT/JOINT_SWEEP_$ROUND.json' \
            --out '$OUT/JOINT_GATE_$ROUND.json'"

# ---- run ledger: backfill -> regression diff -> ledger gate -----------
# Cross-run observatory (docs/observability.md "Run ledger & regression
# diffing"): register every committed artifact as a run_manifest/v1
# record (zero unknown-schema entries is the bar), replay the committed
# degraded-DCN dump against its healthy twin — the run_diff/v1 must
# localize the regression to the dcn_comm bucket — then gate today's
# artifacts against per-(device_kind, schema) ledger baselines, so a
# TPU day is held to TPU history and never to a CPU-host rerun.
run 0 "$OUT/LEDGER_$ROUND.json" \
    "run-ledger leg: backfill-ingest committed artifacts (no unknown schemas), replay healthy-vs-degraded diff (must name dcn_comm), then perf_gate --ledger per-(device_kind, schema) baselines" -- \
    bash -c "$PY_TPU tools/ledger.py ingest --root '$REPO' \
            --out '$OUT/LEDGER_$ROUND.json' > /dev/null \
        && $PY_TPU tools/ledger.py diff \
            tests/data/healthy_dcn_spans.json \
            tests/data/degraded_dcn_spans.json \
            --out '$OUT/REGRESSION_DIFF_$ROUND.json' > /dev/null \
        && $PY_TPU tools/perf_gate.py --ledger '$OUT/LEDGER_$ROUND.json' \
            --out '$OUT/LEDGER_GATE_$ROUND.json'"

# ---- elasticity: async checkpoint A/B + supervised chaos restart ------
# Hardware-free (2-controller CPU-mesh world): the async backend's
# on-step stall vs the sync npz save it replaces, then the ISSUE-19
# chaos drill — SIGKILL one controller mid-run, the supervisor harvests
# the survivor's flight dump into a restart_manifest/v1 and relaunches
# from the newest consistent generation with at most ONE step of work
# redone and loss parity against the uninterrupted run.  perf_gate
# --elastic holds async_ckpt.stall_ms and chaos.lost_steps to the
# async_ckpt_stall_ms / elastic_resume_lost_steps budgets
# (docs/elasticity.md).
run 0 "$OUT/ELASTIC_$ROUND.json" \
    "elastic leg: async-checkpoint stall A/B + SIGKILL chaos restart under the elastic supervisor (<=1 step lost, manifest embeds flight dump + attribution), gated by perf_gate --elastic" -- \
    bash -c "env JAX_PLATFORMS=cpu \
        $PY_TPU tools/elastic_smoke.py --out '$OUT/ELASTIC_$ROUND.json' \
            > /dev/null \
        && $PY_TPU tools/perf_gate.py --elastic '$OUT/ELASTIC_$ROUND.json' \
            --out '$OUT/ELASTIC_GATE_$ROUND.json'"

# ---- THE two hardware-blocked numbers (north-star metric #2) ----------

run 8 "$OUT/ALLREDUCE_SCALING_$ROUND.json" \
    "8->N allreduce scaling table (the headline hardware-day number): busbw per flavor per device count; >=0.9 scaling efficiency is the BASELINE bar" -- \
    bash -c "$PY_TPU benchmarks/bench_allreduce.py --scaling --json \
        --mb 64 --communicators xla,hierarchical,two_dimensional \
        > '$OUT/ALLREDUCE_SCALING_$ROUND.json'"

run 2 "$OUT/DB_OVERLAP_$ROUND.json" \
    "double-buffer combiner/barrier split check on REAL chips (docs/performance.md 'pending hardware validation': two collectives in the TPU schedule, grads AR overlapping fwd)" -- \
    $PY_TPU tools/check_db_overlap.py --out "$OUT/DB_OVERLAP_$ROUND.json"

run 2 "$OUT/FSDP_OVERLAP_$ROUND.json" \
    "bucketed-FSDP overlap sweep on REAL chips (docs/performance.md 'FSDP overlap knobs': the CPU mesh pins K gathers/K scatters/barriers structurally but cannot time overlap — step_ms vs num_buckets x prefetch ON ICI is the measurement; look for the knee where per-bucket latency stops hiding behind compute)" -- \
    bash -c "$PY_TPU benchmarks/bench_fsdp_overlap.py --json \
        --buckets 1,2,4,8 --prefetch 0,1,2 --wire-dtype bfloat16 \
        > '$OUT/FSDP_OVERLAP_$ROUND.json'"

run 2 "$OUT/COMPRESSION_$ROUND.json" \
    "gradient-compression sweep on REAL chips (docs/compression.md: the CPU mesh pins the wire census — K gathers/K scatters, int8 reduce-scatter bytes >=3.5x under f32, no extra collectives — but folds wire casts, so step_ms per compressor x bucket ON ICI is the bandwidth measurement; compare against the FSDP_OVERLAP leg's uncompressed times)" -- \
    bash -c "$PY_TPU benchmarks/bench_compression.py --json \
        --compressors none,none:bfloat16,int8,fp8 --buckets 1,4 \
        > '$OUT/COMPRESSION_$ROUND.json'"

# ---- full-shape configs on the slice ----------------------------------

run 4 "$OUT/RUN_CONFIGS_$ROUND.json" \
    "five BASELINE configs at full shape (repeat-median discipline)" -- \
    $PY_TPU benchmarks/run_configs.py --out "$OUT/RUN_CONFIGS_$ROUND.json"

run 8 "$OUT/RING_FLASH_$ROUND.json" \
    "ring attention x flash across real chips (sequence parallelism on ICI)" -- \
    bash -c "$PY_TPU benchmarks/bench_ring_attention.py --json > '$OUT/RING_FLASH_$ROUND.json'"

run 2 "$OUT/MULTICONTROLLER_$ROUND.txt" \
    "multi-controller worlds on real hardware (2/4/8-proc DP parity + 4-owner pipeline)" -- \
    bash -c "cd $REPO && python -m pytest tests/test_multicontroller.py -q | tee '$OUT/MULTICONTROLLER_$ROUND.txt'"

echo
echo "== runbook complete; artifacts (if any) under $OUT =="
