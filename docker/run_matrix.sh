#!/usr/bin/env bash
# Install/tier matrix — the runnable analog of the reference's
# tests/docker_extension_builds/run.sh:16-40 (build apex across ~7 images
# and assert each tier works).  The TPU build's matrix is degradation
# tiers rather than CUDA/toolchain images:
#
#   tier 1: full        — native C++ runtime + Pallas kernels
#   tier 2: no-native   — Python flatten/decode fallbacks
#   tier 3: no-pallas   — jnp kernels (APEX_TPU_DISABLE_PALLAS=1)
#   tier 4: bare        — both fallbacks at once
#
# Each tier runs the install-matrix gate (tier-equivalence tests) plus an
# import smoke.  Run from the repo root; ~5 min on an 8-core box.
set -euo pipefail
cd "$(dirname "$0")/.."

export XLA_FLAGS="--xla_force_host_platform_device_count=8"
# test_multi_tensor.py rides along for the flat-bucket matrix (ISSUE 4):
# the bucket engine is pure XLA, so every degradation tier must keep its
# numerics bit-identical.  test_telemetry.py rides along for the
# run-telemetry matrix (ISSUE 5): the event stream is pure host Python,
# so every tier must emit identical event shapes and keep the disabled
# path a bitwise no-op.  test_roofline.py + test_watchdog.py ride along
# for the attribution/health engines (ISSUE 6): cost harvesting is a
# static jaxpr walk and the watchdog a pure host fold, so every tier
# must produce identical ledgers/alerts.
# test_contrib.py + test_fused_bn_act.py ride along for the conv-path
# epilogues (ISSUE 7): test_contrib's tier-parity tests run the REAL
# xentropy kernels in interpret mode against the jnp references, so the
# no-pallas tiers must stay numerically identical.  test_cache.py rides
# for the warm-start engine (AOT warmup is pure host machinery — every
# tier must keep zero-compile-after-step-0 and bitwise parity).
# test_checkpoint.py + test_faultinject.py ride for the elastic
# fault-tolerant runtime (ISSUE 9): serialization, manifest validation,
# and kill-and-resume bit-parity are pure host + XLA machinery, so
# every degradation tier must recover identically (the faultinject
# children inherit the tier env vars through the harness).
# test_fleet.py + test_export.py + test_memory.py ride for the fleet
# observability layer (ISSUE 10): the merge/aligner and the Prometheus
# renderer are pure host JSON/text, and the memory walk a static jaxpr
# replay — every tier must produce identical attributions and
# expositions.  test_serving.py rides for the inference engine (ISSUE
# 11): the paged cache, AOT bucket table, scheduler, and hot-swap are
# host machinery over plain XLA programs, so every degradation tier
# must serve bitwise-identical greedy tokens.  test_mesh.py rides for
# the mesh frontend (ISSUE 12): the ZeRO-2/3 sharding engine is pure
# XLA collectives over the flat-bucket store, so every tier must hold
# the bitwise zero1-parity and 1/N state-sharding contracts.
# test_quant.py rides for the int8 engine (ISSUE 13): the pallas tiers
# run the REAL quantized-matmul kernel via interpret=True, the
# no-pallas tiers the jnp reference — every tier must hold the
# kernel-parity, O4-fallback-bitwise-O2, and int8-KV decode contracts.
# test_tune.py rides for the kernel autotuner (ISSUE 14): the config
# cache is pure host JSON and the tuner's interpret-mode probes run the
# REAL kernels, so every tier must hold the roundtrip/invalidation/
# corrupt-fallback contracts and the bitwise tuned-vs-default dispatch
# parity.  test_tracing.py + test_requests.py ride for the request
# tracing/SLO subsystem (ISSUE 20): span emission, the SLO fold, and
# the offline analyzer are pure host machinery over the event stream,
# so every tier must produce identical span trees, goodput verdicts,
# and bitwise-unchanged traced tokens.
FAST="python -m pytest tests/test_install_matrix.py tests/test_multi_tensor.py tests/test_telemetry.py tests/test_roofline.py tests/test_watchdog.py tests/test_contrib.py tests/test_fused_bn_act.py tests/test_cache.py tests/test_checkpoint.py tests/test_faultinject.py tests/test_fleet.py tests/test_export.py tests/test_memory.py tests/test_serving.py tests/test_tracing.py tests/test_requests.py tests/test_mesh.py tests/test_quant.py tests/test_tune.py -q -m 'not slow'"

echo "=== tier 1: full (native + pallas) ==="
python setup.py build_native
$FAST

echo "=== tier 2: no-native (python flatten/decode) ==="
# APEX_TPU_DISABLE_NATIVE short-circuits the lazy builder (which would
# otherwise just rebuild the .so with the g++ tier 1 proved present)
APEX_TPU_DISABLE_NATIVE=1 $FAST

echo "=== tier 3: no-pallas (jnp kernels) ==="
APEX_TPU_DISABLE_PALLAS=1 $FAST

echo "=== tier 4: bare (both fallbacks) ==="
APEX_TPU_DISABLE_NATIVE=1 APEX_TPU_DISABLE_PALLAS=1 $FAST

echo "=== multi-host lane: 2 REAL processes (ISSUE 12) ==="
# Spawns 2 subprocesses with distinct process ids joined through
# jax.distributed (gloo CPU collectives): mesh parity must hold
# bitwise ACROSS hosts, CheckpointManager must land one shard per
# host, and prof.fleet must merge the two real telemetry streams.
# The script manages its own per-child XLA_FLAGS.
python tools/multihost_smoke.py --nproc 2

echo "=== import smoke from outside the tree ==="
(cd /tmp && PYTHONPATH="$OLDPWD" python -c "
import apex_tpu
from apex_tpu import amp, optimizers, parallel, normalization
print('import surface ok:', apex_tpu.__name__)")

echo "ALL TIERS GREEN"
