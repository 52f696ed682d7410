#!/bin/sh
# CI entry point (↔ the reference's travis/cmake test tier, SURVEY.md §4
# tier 4): full test suite on the virtual 8-device CPU mesh, then the
# driver entry checks and a CPU-scaled bench smoke.
set -e
cd "$(dirname "$0")/.."
# CI is a CPU tier: every step below is its own process on the CPU
# backend (the heredocs also pin it before their first jax use), so no
# step holds a chip and none starts a child that needs one.  The chip
# is checked by `python chip_smoke.py`, alone, through the chip tool.
export JAX_PLATFORMS=cpu
# smoke drivers drop their JSON records here (benchmarks/driver_common.py
# emit); the perf gate at the end of this script soft-checks the timing
# ceilings in perf_budgets.json against them
export OPENDHT_TPU_SMOKE_RECORD_DIR="$(mktemp -d /tmp/odt-smoke.XXXXXX)"
trap 'rm -rf "$OPENDHT_TPU_SMOKE_RECORD_DIR"' EXIT
# packaging smoke: the wheel must build and every console entry point
# must resolve (catches pyproject drift before the Docker tier does)
python -m pip wheel --no-build-isolation --no-deps -q -w /tmp/odt-ci-wheel .
python - <<'PY'
from opendht_tpu.tools.dhtnode import main as a
from opendht_tpu.tools.dhtchat import main as b
from opendht_tpu.tools.dhtscanner import main as c
print("entry points ok")
PY
python -m pytest tests/ -q
# README/PARITY headline quotes must agree with the last accelerator
# bench capture (within the stated cross-run drift band)
python ci/check_docs.py
python - <<'PY'
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
import __graft_entry__ as g
fn, args = g.entry()
jax.block_until_ready(jax.jit(fn)(*args))
g.dryrun_multichip(8)
print("entry + dryrun ok")
PY
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
import bench
bench.main()
PY
# CPU-scaled smoke of the BASELINE config drivers — catches driver-level
# errors (e.g. a NameError in one config) that unit tests cannot see.
# config2 is skipped: it delegates to bench.measure(), which the step
# above already ran.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location(
    "baseline_configs", pathlib.Path("benchmarks/baseline_configs.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
for c in (1, 3, 4, 5, 6):
    m.main(["-c", str(c)])
PY
# wave-latency smoke (round 6): the fixed-trip round-attribution driver
# at a small wave asserts (1) the driver's MIRROR of the round-fused
# engine body is bit-identical to its round-5 unfused form through the
# compiled loop (the SHIPPING engine's reply streams are pinned by the
# goldens test in the suite above) and (2) the fused round has not
# regressed past a generous 1.5x band — p50 wave-latency regressions on
# the fused path fail here without the full bench.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location(
    "exp_round_r6", pathlib.Path("benchmarks/exp_round_r6.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "wave-latency smoke failed"
PY
# churn-merge smoke (round 7): the lane-packed merge must stay
# BIT-IDENTICAL to the unpacked merge through the SHIPPING
# churn_lookup_topk (fast2 + fast3, ragged wave) and the packed round
# must not regress past a generous 1.5x band vs the unpacked round —
# a merge-stage latency regression fails here without the full bench.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_churn_r7", pathlib.Path("benchmarks/exp_churn_r7.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-Q", "1025", "--dcap", "1024",
             "-E", "64"])
assert rc == 0, "churn-merge smoke failed"
PY
# telemetry smoke (round 8): boot a small real-UDP cluster, run
# puts/gets, scrape the proxy's GET /stats and DhtRunner.get_metrics(),
# assert the exercised counters advanced, the two exports agree, and
# the Prometheus text exposition parses line-by-line.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.telemetry_smoke import main
rc = main()
assert rc == 0, "telemetry smoke failed"
PY
# tracing smoke (round 9): boot a 5-node real-UDP cluster, run one
# traced put+get, assemble the cross-node span tree (>=3 nodes
# contributed spans, correct parentage, monotone timestamps), check
# the Chrome/Perfetto dump round-trips with the exact ph/pid/tid/ts/
# dur fields, the flight-recorder dump parses, and the ring's
# bounded-memory property (10x capacity pushed -> oldest evicted,
# RSS-stable).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.trace_assembler import main
rc = main()
assert rc == 0, "tracing smoke failed"
PY
# tracing overhead smoke (round 9): the sampled-on 8192-wave round must
# stay inside a generous 10% band vs the tracer-disabled run (the
# committed captures/trace_overhead.json documents the tight number,
# enforced against the README quote by check_docs above).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location(
    "exp_trace_r9", pathlib.Path("benchmarks/exp_trace_r9.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "tracing overhead smoke failed"
PY
# round-fused stage-profile smoke (round 11): the per-stage chain-slope
# decomposition mirroring the ROUND-6 fused round body must run end to
# end at a small shape (a stage-level compile break or an
# order-of-magnitude wave stall fails here without the full bench)
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "profile_search", pathlib.Path("benchmarks/profile_search.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "profile_search smoke failed"
PY
# kernel-ledger overhead smoke (round 11): with the cost ledger computed
# and the wave_attrs hook live on the traced record_wave path, the wave
# must stay inside a generous 5% band vs the ledger-disabled run (the
# committed captures/ledger_overhead.json documents the tight number,
# enforced against the README quote by check_docs above)
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_ledger_r11", pathlib.Path("benchmarks/exp_ledger_r11.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "ledger overhead smoke failed"
PY
# kernel-ledger export smoke (round 11): boot a node + proxy, compute a
# ledger subset, scrape GET /stats and get_metrics(), assert the
# dht_kernel_* series are present, agree, and the exposition parses
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.ledger_smoke import main
rc = main()
assert rc == 0, "ledger smoke failed"
PY
# ingest-amortization smoke (round 12): the coalesced [Q] resolve must
# still amortize the per-op dispatch (>2x at a small shape) through the
# SHIPPING find_closest_nodes_batched stack — a refactor that sneaks a
# per-target dispatch back into the wave path fails here without the
# full bench.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_ingest_r12", pathlib.Path("benchmarks/exp_ingest_r12.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "ingest amortization smoke failed"
PY
# burst-ingest smoke (round 12): boot a real-UDP cluster + proxy, fire
# concurrent gets/puts/listens from threads, assert the wave builder
# actually coalesced them (mean wave occupancy > 1 on the new
# histogram, dht_ingest_* series on the proxy /stats exposition, zero
# sheds), and that the identical workload rerun with
# ingest_batching="off" returns the same values and leaves the same
# per-node storage state — the acceptance-criteria equivalence pin.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.ingest_smoke import main
rc = main()
assert rc == 0, "ingest smoke failed"
PY
# health observatory smoke (round 14): boot a 3-node real-UDP cluster +
# proxy, assert GET /healthz flips 503->200 through bootstrap, run the
# batched replica-coverage probe (the whole sampled key set's true
# closest-8 in ONE launch) against the live stores, then choke ingest
# admission and assert the availability SLO fast-burns the verdict to
# unhealthy with health_transition/slo_violation events in the flight
# recorder and dhtmon exiting non-zero on the lookup-success invariant.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.health_smoke import main
rc = main()
assert rc == 0, "health smoke failed"
PY
# health-evaluator overhead smoke (round 14): with the evaluator
# ticking once per wave, the search round must stay inside a generous
# 5% band vs the evaluator-free run (the committed
# captures/health_overhead.json documents the tight number against the
# <1% acceptance, enforced against the README quote by check_docs).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_health_r14", pathlib.Path("benchmarks/exp_health_r14.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "health overhead smoke failed"
PY
# keyspace observatory smoke (round 15): boot a 3-node real-UDP cluster
# + proxy, drive Zipf-skewed gets/puts through the wave builder, assert
# the hot key surfaces in GET /keyspace as hot (with a hot_key_emerged
# flight event), the dht_shard_imbalance gauge exports a known value on
# GET /stats, and dhtmon --max-imbalance exits 0 on the mixed load then
# 1 under an injected single-key flood.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.keyspace_smoke import main
rc = main()
assert rc == 0, "keyspace smoke failed"
PY
# keyspace-observatory overhead smoke (round 15): with the count-min
# sketch observing every wave's full target batch (one async batched
# scatter-add per wave + candidate sampling), the search round must
# stay inside a generous 5% band vs the observatory-free run (the
# committed captures/keyspace_overhead.json documents the tight number
# against the <1% acceptance, enforced against the README quote by
# check_docs above).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_keyspace_r15", pathlib.Path("benchmarks/exp_keyspace_r15.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "keyspace overhead smoke failed"
PY
# load-aware resharding smoke (round 21): boot a 3-node real-UDP
# cluster + proxy, flood one hot key past the rebalance threshold, and
# assert the closed loop live: a burst shorter than the sustain window
# causes ZERO swaps (hysteresis skips advance, dhtmon --max-imbalance
# exits 1), the sustained flood swaps a new layout generation (virtual
# mode, reshard_swap flight event, dht_reshard_* on /stats), fold
# attribution follows the new traffic-weighted edges (live imbalance
# drops under the gate, dhtmon flips to 0), and get/put/listen are
# identical across the swap.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.reshard_smoke import main
rc = main()
assert rc == 0, "reshard smoke failed"
PY
# reshard balance smoke (round 21): the boundary-solver benchmark at a
# small shape — Zipf-hot traffic on the uniform split must read
# imbalanced, the solved layout must refold balanced, the weighted
# shard state must stay BIT-IDENTICAL to the single-device engine
# (including an in-flight wave crossing the swap), and the committed
# captures/reshard_balance.json quotes are enforced against README/
# PARITY by check_docs above.
python - <<'PY'
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_reshard_r17", pathlib.Path("benchmarks/exp_reshard_r17.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "reshard balance smoke failed"
PY
# hot-cache smoke (round 16): boot a 3-node real-UDP cluster + proxy
# (node 0 caches, nodes 1-2 cache-off), Zipf-flood the hot key until
# hot_key_emerged, and assert the observe→act loop closes live: the
# cache admits the key off the observatory tick, hot gets serve from
# cache (hit counters advance, wave occupancy attributable to the hot
# key ~0), the windowed hit ratio reaches >=0.9 with dhtmon
# --min-cache-hit exiting 0 then 1 under a cold-key miss storm, a
# fresh put invalidates with the new value visible on every surface
# (runner ops, proxy REST, listeners), and cache-on == cache-off
# results throughout.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.cache_smoke import main
rc = main()
assert rc == 0, "cache smoke failed"
PY
# hot-cache probe overhead smoke (round 16): with the probe running
# over every wave's full target batch against a full device table (all
# misses — the worst case), the search round must stay inside a
# generous 5% band vs the cache-free run (the committed
# captures/cache_overhead.json documents the tight number against the
# <1% acceptance, enforced against the README quote by check_docs
# above).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_cache_r16", pathlib.Path("benchmarks/exp_cache_r16.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "cache overhead smoke failed"
PY
# flight-data-recorder smoke (round 17): boot a 3-node real-UDP cluster
# + proxy, assert dhtmon's windowed invariants read each node's
# GET /history frames (no scrape-diff wait; pinned equal to the legacy
# paths), induce an SLO burn and assert a black-box bundle
# auto-captures with the burn visible in its frames and GET
# /debug/bundle serving fresh ones, dhtmon --since exits 1 during the
# burn window then 0 after recovery, the bundle round-trips through the
# cluster timeline assembler with the health transition present, and
# the ring + on-disk spill stay bounded under a 10x flood.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.history_smoke import main
rc = main()
assert rc == 0, "history smoke failed"
PY
# flight-data-recorder overhead smoke (round 17): with the recorder
# ticking once per wave (full-registry delta frame + spill armed), the
# search round must stay inside a generous 5% band vs the recorder-free
# run (the committed captures/history_overhead.json documents the tight
# number against the <1% acceptance, enforced against the README quote
# by check_docs above).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_history_r17", pathlib.Path("benchmarks/exp_history_r17.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "history overhead smoke failed"
PY
# adversarial chaos smoke (round 18): (1) a scripted partition+heal on
# a small real-UDP cluster — the isolated node's gets fail, /healthz
# degrades to 503, a black-box bundle auto-captures on the unhealthy
# transition and dhtmon --since flags the burn window; healing rolls
# the verdict back (healthz 200, dhtmon clean).  (2) the virtual-net
# storm: chaos-off == baseline pinned (armed-but-empty plan delivers
# identical results with zero drops), then per-link loss/dup/reorder +
# an asymmetric partition phase + join/leave storm steps with per-rule
# drop accounting and every stored key still resolvable post-heal.
# (3) a 4096-node device swarm steps the same storm arc: invariants
# degrade mid-partition and are restored after healing.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.chaos_smoke import main
rc = main()
assert rc == 0, "chaos smoke failed"
PY
# swarm-stepper smoke (round 18): the storm arc rerun at S=4096 through
# benchmarks/exp_chaos_r18.py --smoke, asserting bit-for-bit
# determinism under the fixed seed (two runs replay identically) and
# feeding the perf gate's swarm_tick_ms timing record; the full
# S=50000 acceptance run is committed as captures/swarm_storm.json.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_chaos_r18", pathlib.Path("benchmarks/exp_chaos_r18.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "--ticks", "22"])
assert rc == 0, "swarm stepper smoke failed"
PY
# per-op latency waterfall smoke (round 19): boot a 3-node real-UDP
# cluster + proxy, run mixed put/get traffic, assert the always-on
# dht_stage_seconds{stage=} histograms advance on the scrape (queue
# wait, device launch, scatter-back, real-UDP rpc_wait), GET /profile
# serves the waterfall JSON + ?fmt=folded flamegraph stacks (400 on a
# bad fmt), a hot-bucket exemplar trace id reassembles into a span
# tree through the trace assembler, dhtmon --max-stage exits 0 at a
# gate above the healthy baseline then 1 under an injected
# scatter-path stall, and the OPEN-bound tracker drops a well-formed
# settling record (status="unsettled" on CPU).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.waterfall_smoke import main
rc = main()
assert rc == 0, "waterfall smoke failed"
PY
# stage-profiler overhead smoke (round 19): with the always-on profiler
# observing every wave's device stage (compile/execute split + exemplar
# stamping), the search round must stay inside a generous 5% band vs
# the profiler-disabled run (the committed
# captures/waterfall_overhead.json documents the tight number against
# the <1% acceptance, enforced against the README quote by check_docs
# above), and the wave outputs stay bit-identical profiler on vs off.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_waterfall_r19", pathlib.Path("benchmarks/exp_waterfall_r19.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "waterfall overhead smoke failed"
PY
# wave-pipeline smoke (round 20): boot a 3-node real-UDP cluster +
# proxy, run the concurrent mixed burst at ingest_pipeline_depth=2 and
# assert the double-buffer actually stacks (the
# dht_ingest_pipeline_inflight_peak gauge reaches >=2 via the
# deterministic stack probe, both pipeline series ride the proxy
# /stats exposition), the always-on stage histograms keep advancing
# with the device stage now measured at consume, and the identical
# workload rerun at depth=1 (the exact pre-pipeline serial path)
# returns the same values / listener deliveries / per-node storage.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.pipeline_smoke import main
rc = main()
assert rc == 0, "pipeline smoke failed"
PY
# wave-pipeline overlap smoke (round 20): sustained ingest through the
# SHIPPING WaveBuilder at a small shape — depth-2 results must stay
# bit-identical to depth-1, the in-flight machinery must hold two
# waves (slow-ready shim), and the paired-delta band guards against
# the pipeline REGRESSING sustained ingest (the committed
# captures/pipeline_overlap.json documents the full-shape figure,
# enforced against the README quote by check_docs above).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_pipeline_r20", pathlib.Path("benchmarks/exp_pipeline_r20.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "wave pipeline smoke failed"
PY
# pipeline-utilization smoke (round 22): boot a 3-node real-UDP
# cluster + proxy at depth 2, drive a Zipf-skewed get flood, and
# assert the utilization observatory measured it — the
# dht_pipeline_occupancy gauge leaves unknown for a value in (0, 1]
# consistent with the stage histograms (device-stage samples <= waves,
# both > 0, busy <= window), GET /pipeline serves the snapshot and
# ?fmt=trace the three-lane Perfetto doc, both pipeline-occupancy
# series ride the proxy /stats exposition, a forced admission choke is
# attributed as a queue_empty bubble, and dhtmon --min-occupancy exits
# 0 below the measured gauge then 1 at an impossible floor.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.pipeline_util_smoke import main
rc = main()
assert rc == 0, "pipeline utilization smoke failed"
PY
# observatory overhead smoke (round 22): with the full per-wave
# lifecycle (fill/dispatch/bubble-classify/device_done/scatter_done +
# frame checkpoint) tracking every wave, the search round must stay
# inside a generous 5% band vs the observatory-disabled run (the
# committed captures/pipeutil_overhead.json documents the tight number
# against the <1% acceptance, enforced against the README quote by
# check_docs above), the wave outputs stay bit-identical on vs off,
# and the timed trips must leave a CLOSED ledger
# (Σ(busy)+Σ(bubbles)==window).
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_pipeutil_r21", pathlib.Path("benchmarks/exp_pipeutil_r21.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "observatory overhead smoke failed"
PY
# per-peer observatory smoke (round 23): boot 3-node real-UDP clusters
# and inject chaos-plane faults on ONE link — the same delay+jitter
# rule (RTTs straddling the fixed 1.0s timer) runs once with the
# fixed timetable and once with the adaptive per-peer RTO, and the
# adaptive run must record measurably fewer spurious retransmits while
# the untouched link's srtt/RTO stay baseline; then a one-way loss
# rule on node0->node2 must land on exactly that directed edge of the
# cluster wire map (testing/wiremap_assembler.py over every node's
# GET /peers), tick dht_net_attempt_timeouts_total at the EXPIRED
# transitions, and flip dhtmon --max-peer-fail from 0 to 1 across the
# injected fail ratio.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.peer_smoke import main
rc = main()
assert rc == 0, "per-peer observatory smoke failed"
PY
# per-peer ledger overhead smoke (round 23): with 256 synthetic
# request lifecycles per wave over 32 peers (every completion a clean
# Karn sample driving the RFC 6298 estimator + per-peer histogram +
# gauge writes), the search round must stay inside a generous 5% band
# vs the ledger-disabled run (the committed
# captures/peers_overhead.json documents the tight number against the
# <1% acceptance, enforced against the README quote by check_docs
# above), and the wave outputs stay bit-identical on vs off.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_peers_r23", pathlib.Path("benchmarks/exp_peers_r23.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "per-peer ledger overhead smoke failed"
PY
# wave-scale listen/push smoke (round 24): boot a 3-node real-UDP
# cluster + proxy with >= 512 live listeners across runner ops and
# proxy SUBSCRIBE/LISTEN registrations, flood a Zipf put mix, and pin
# the batched listener match result-equivalent to the synchronous
# listen_batching="off" arm on EVERY delivery surface (runner
# callbacks with all of a key's listeners agreeing, the proxy LISTEN
# stream, SUBSCRIBE push dispatches); dht_listener_* occupancy/
# latency series must advance on GET /stats and dhtmon
# --max-listener-lag must read 0 healthy and flip to 1 under an
# injected drain stall.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
from opendht_tpu.testing.listener_smoke import main
rc = main()
assert rc == 0, "listener smoke failed"
PY
# listener amortization + on-cost smoke (round 24): the batched
# per-listener delivery slope must sit below the host per-put dispatch
# slope, and with the table ACTIVE at full capacity plus a worst-case
# all-miss flush per trip the 8192-wave search round must stay inside
# a generous 5% band vs the table-free run (the committed
# captures/listener_match.json + captures/listener_overhead.json
# document the tight numbers against the slope-ratio and <1%
# acceptances, enforced against the README quotes by check_docs
# above), wave outputs bit-identical in both modes.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_listener_r24", pathlib.Path("benchmarks/exp_listener_r24.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke", "-N", "16384", "-W", "1024", "--reps", "7"])
assert rc == 0, "listener amortization smoke failed"
PY

# maintenance smoke (round 10): boot a 3-node real-UDP cluster, pin the
# fused maintenance sweep bit-identical to the host stale set on the
# LIVE routing table, force a bucket refresh + a due republish, and
# assert the dht_maintenance_* counters advanced with the refresh
# find_nodes actually on the wire.
python - <<'PY'
import jax
jax.config.update("jax_platforms", "cpu")   # CI runs on the CPU backend
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location(
    "exp_maint_r10", pathlib.Path("benchmarks/exp_maint_r10.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "maintenance smoke failed"
PY
# table-sharded iterative mode on a REAL 8-device virtual mesh: the
# 8-device flag must land before the first jax import, hence the
# heredoc rather than the module CLI.
python - <<'PY'
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib
spec = importlib.util.spec_from_file_location(
    "baseline_configs", pathlib.Path("benchmarks/baseline_configs.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
assert len(jax.devices()) == 8
m.main(["-c", "3", "--tp", "-N", "65536", "-Q", "1024"])
PY
# row-sharded table smoke (round 13, ROADMAP item 1): one t=4 sharded
# wave on the 8-device virtual mesh.  Asserts the compiled HLO's
# in-loop collective-site count AND bytes/query/hop EQUAL the
# committed TP_SCALING.json values (drift fails BOTH directions — an
# extra in-loop collective and an unrecorded fusion alike), the
# per-shard resident table stays inside the N/t*5*4 B*(1+eps) bound,
# and the wave is bit-identical to the single-device engine.
python - <<'PY'
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import importlib.util, pathlib, sys
sys.path.insert(0, str(pathlib.Path("benchmarks")))
spec = importlib.util.spec_from_file_location(
    "exp_shard_r13", pathlib.Path("benchmarks/exp_shard_r13.py"))
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
rc = m.main(["--smoke"])
assert rc == 0, "row-sharded table smoke failed"
PY
# kernel cost-model perf gate (round 11, ROADMAP item 3): every shipped
# kernel's lowered XLA cost model (flops / bytes accessed / arg+output
# bytes at its canonical shape) must sit inside the committed
# perf_budgets.json tolerances — DETERMINISTIC on the CPU runner, so a
# refactor that doubles a kernel's HBM traffic fails CI here with a
# budget-vs-observed diff.  Wall-clock stays advisory: the smoke records
# collected above are checked against the timing_soft ceilings as
# warnings only (shared runners flake; cost gates, timing informs).
python ci/perf_gate.py --records "$OPENDHT_TPU_SMOKE_RECORD_DIR"
