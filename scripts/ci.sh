#!/usr/bin/env bash
# CI entry points.
#   scripts/ci.sh smoke   — fast suite (-m "not slow"), incl. the kernel
#                           dispatch differential tests
#                           (tests/test_dispatch_differential.py +
#                           tests/test_paged_decode.py +
#                           tests/test_flash_backward.py, capped shapes)
#                           Timing audit (2026-07-30, container single-CPU,
#                           --durations=15): slowest test 27s < the 30s
#                           slow-marker threshold, no moves needed; target
#                           smoke wall-time <= ~8 min.
#   scripts/ci.sh full    — everything, incl. multi-device subprocess tests
#   scripts/ci.sh lint    — compileall + policy grep gates (no direct
#                           hypothesis imports outside the shim, no direct
#                           jax.make_mesh(..., axis_types=...) outside
#                           launch/mesh.py, no direct kernel-family imports
#                           from models/ or launch/ — everything routes
#                           through kernels.dispatch / kernels.registry —
#                           and mesh construction only via launch/mesh.py)
#   scripts/ci.sh tune    — design-space sweep; writes results/tuned_plans.json
#   scripts/ci.sh serve   — paged-serving smoke: interpret-mode ragged
#                           prefill + decode through dispatch for a few
#                           steps (static AND continuous schedules), plus
#                           BENCH_serve.json throughput/latency rows and
#                           BENCH_prefill.json kernel-vs-reference rows,
#                           plus a forced-2-device sharded smoke (--mesh 2
#                           CLI + --serve-sharded bench) gated by
#                           check_bench's baseline-free compare_tp, plus a
#                           speculative smoke (--speculate ngram CLI +
#                           --serve-speculative bench) gated by compare_spec
#   scripts/ci.sh bench   — benchmark-regression gate: re-run both serve
#                           benchmark modes and fail if decode throughput
#                           dropped or p99 per-token latency rose more than
#                           the tolerances vs the committed
#                           results/BENCH_serve.json (scripts/check_bench.py;
#                           REPRO_BENCH_TOL / REPRO_BENCH_LAT_TOL override)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

lint() {
  python -m compileall -q src tests benchmarks scripts examples
  # ROADMAP compat policy, enforced as grep gates:
  # 1. tests import the seeded shim, never hypothesis directly
  bad=$(grep -rnE '^[[:space:]]*(import hypothesis|from hypothesis)' \
        src tests --include='*.py' | grep -v '_hypothesis_compat.py' || true)
  if [ -n "$bad" ]; then
    echo "lint: direct hypothesis import (use tests/_hypothesis_compat):"
    echo "$bad"; exit 1
  fi
  # 2. mesh construction goes through repro.launch.mesh.make_mesh
  bad=$(grep -rn 'axis_types' src --include='*.py' \
        | grep -v 'launch/mesh.py' || true)
  if [ -n "$bad" ]; then
    echo "lint: jax.make_mesh axis_types outside launch/mesh.py" \
         "(use repro.launch.mesh.make_mesh):"
    echo "$bad"; exit 1
  fi
  # 3. models/ and launch/ never import a kernel family directly — every
  #    hot contraction routes through kernels.dispatch (thin facades) /
  #    kernels.registry (the one generic path), so tuned plans, route
  #    counters, and policy knobs can't be silently bypassed
  bad=$(grep -rnE \
        'kernels(\.| +import +)(matmul|attention|stencil|histogram|nbody|wkv)' \
        src/repro/models src/repro/launch --include='*.py' || true)
  if [ -n "$bad" ]; then
    echo "lint: direct kernel-family import from models/ or launch/" \
         "(route through repro.kernels.dispatch):"
    echo "$bad"; exit 1
  fi
  # 4. int8 KV pools are born in ONE place (transformer.layer_cache_init_
  #    paged, following cfg.kv_dtype) so scale leaves can never be missing
  #    or mis-sized — model/launch code must not construct int8 buffers
  #    directly (repro.core.quant owns the quantize/dequantize math)
  bad=$(grep -rnE 'jnp\.(zeros|empty|full)\([^)]*jnp\.int8' \
        src/repro/models src/repro/launch --include='*.py' \
        | grep -v 'models/transformer.py' || true)
  if [ -n "$bad" ]; then
    echo "lint: int8 KV buffer constructed outside" \
         "models/transformer.layer_cache_init_paged (route kv storage" \
         "through cfg.kv_dtype + repro.core.quant):"
    echo "$bad"; exit 1
  fi
  # 5. mesh construction goes through launch/mesh.py (Auto axis types)
  bad=$(grep -rnE 'jax\.make_mesh|sharding\.Mesh\(' src --include='*.py' \
        | grep -v 'launch/mesh.py' || true)
  if [ -n "$bad" ]; then
    echo "lint: mesh constructed outside launch/mesh.py" \
         "(use repro.launch.mesh.make_mesh / make_serving_mesh):"
    echo "$bad"; exit 1
  fi
  echo "lint: OK"
}

case "${1:-smoke}" in
  smoke) python -m pytest -q -m "not slow" ;;
  full)  python -m pytest -q ;;
  lint)  lint ;;
  tune)  python benchmarks/run.py --tune ;;
  serve)
    python -m repro.launch.serve --arch gemma-2b --smoke --cache paged \
      --dispatch kernels --slots 2 --requests 3 --prompt-len 6 \
      --max-new 4 --max-len 32 --page-size 8
    python -m repro.launch.serve --arch gemma-2b --smoke --cache paged \
      --schedule continuous --dispatch kernels --slots 2 --requests 3 \
      --prompt-len 6 --max-new 4 --max-len 32 --page-size 4 --clock tick
    # speculative smoke: ngram draft -> fixed-width verify -> rollback on
    # the same paged path; the CLI prints the verify/accept counters and
    # the bench rows carry tokens_match_baseline + acceptance_rate for
    # check_bench's baseline-free compare_spec gate
    python -m repro.launch.serve --arch gemma-2b --smoke --cache paged \
      --dispatch kernels --speculate ngram --slots 2 --requests 3 \
      --prompt-len 6 --max-new 4 --max-len 32 --page-size 8
    python benchmarks/run.py --serve --serve-dispatch kernels
    python benchmarks/run.py --serve-continuous --serve-dispatch kernels
    python benchmarks/run.py --serve-speculative --serve-dispatch kernels
    python benchmarks/run.py --prefill
    # sharded smoke: force a 2-device host mesh and run the tensor-parallel
    # paged path end-to-end — the CLI on gemma (MQA, replicated pools) and
    # the bench on codeqwen (GQA, sharded pools).  The bench rows carry the
    # correctness verdicts (tokens_match_oracle, kernels_match_reference,
    # tp_ops_in_region) that check_bench's compare_tp gates baseline-free.
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
      python -m repro.launch.serve --arch gemma-2b --smoke --cache paged \
      --dispatch kernels --mesh 2 --slots 2 --requests 3 --prompt-len 6 \
      --max-new 4 --max-len 32 --page-size 8
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
      python benchmarks/run.py --serve-sharded --serve-dispatch kernels
    python scripts/check_bench.py \
      --baseline results/BENCH_serve.json \
      --current results/BENCH_serve.json
    ;;
  bench)
    # scratch outputs live under gitignored results/scratch/ so a bench
    # run can never leave stray artifacts in the committed results/
    mkdir -p results/scratch
    rm -f results/scratch/BENCH_serve_current.json
    python benchmarks/run.py --serve --serve-dispatch kernels \
      --serve-out results/scratch/BENCH_serve_current.json
    python benchmarks/run.py --serve-continuous --serve-dispatch kernels \
      --serve-out results/scratch/BENCH_serve_current.json
    python benchmarks/run.py --serve-speculative --serve-dispatch kernels \
      --serve-out results/scratch/BENCH_serve_current.json
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
      python benchmarks/run.py --serve-sharded --serve-dispatch kernels \
      --serve-out results/scratch/BENCH_serve_current.json
    python scripts/check_bench.py \
      --baseline results/BENCH_serve.json \
      --current results/scratch/BENCH_serve_current.json
    ;;
  *) echo "usage: $0 {smoke|full|lint|tune|serve|bench}" >&2; exit 2 ;;
esac
