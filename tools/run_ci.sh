#!/usr/bin/env bash
# CI gate (reference capability: the tools/ check scripts + CTest
# orchestration).  Runs on the virtual CPU mesh so no TPU is needed.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
# `python tools/foo.py` puts tools/ (not the repo root) on sys.path[0]
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

echo "== byte-compile check =="
python -m compileall -q paddle_tpu

echo "== API compatibility gate =="
python tools/check_api_compatible.py

echo "== unit tests (full, incl. slow) =="
PADDLE_TPU_RUN_SLOW=1 python -m pytest tests/ -q

echo "== fault-tolerance drills (torn-write + preemption resume) =="
python -m pytest tests/test_fault_tolerance.py -q

echo "== fault-injection spec validation =="
python - <<'EOF'
from paddle_tpu.utils import fault_injection as fi

# well-formed specs parse to typed params
spec = fi.parse("ckpt_write:after_bytes=128,mode=raise;step:crash_at=3")
assert spec["ckpt_write"]["after_bytes"] == 128
assert spec["step"]["crash_at"] == 3

# gray-failure points (ISSUE 17): in-call rpc stall + scheduler stall
spec = fi.parse("rpc_slow:to=rep-0,delay_s=0.25,count=3;"
                "engine_slow:to=rep-1,delay_s=0.5,count=8")
assert spec["rpc_slow"]["to"] == "rep-0"
assert spec["rpc_slow"]["delay_s"] == 0.25
assert spec["engine_slow"]["count"] == 8

# hot-spare ladder points (ISSUE 20): torn peer transfer + dead buddy,
# plus the step point's rank filter and once-file relaunch guard
spec = fi.parse("peer_snap_drop:at_step=3,rank=1,after_chunks=2;"
                "buddy_crash:rank=0,count=1;"
                "step:crash_at=3,rank=1,once_file=/tmp/x.once")
assert spec["peer_snap_drop"]["after_chunks"] == 2
assert spec["buddy_crash"]["count"] == 1
assert spec["step"]["once_file"] == "/tmp/x.once"

# malformed specs must be rejected loudly, never silently inject nothing
for bad in ("bogus:after_bytes=1", "ckpt_write", "ckpt_write:after_bytes",
            "ckpt_write:after_bytes=xyz", "step:nope=1",
            "rpc_slow", "rpc_slow:delay_s=abc", "engine_slow:nope=1",
            "peer_snap_drop", "peer_snap_drop:nope=1", "buddy_crash",
            "buddy_crash:rank=abc"):
    try:
        fi.parse(bad)
    except fi.FaultSpecError:
        pass
    else:
        raise SystemExit(f"spec {bad!r} was not rejected")
print("fault-injection spec validation OK")
EOF

echo "== serving smoke (engine start -> concurrent requests -> clean shutdown) =="
python - <<'EOF'
import threading
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import Engine, ServingConfig

before = {t.ident for t in threading.enumerate()}
paddle.seed(0)
model = GPTForCausalLM(gpt_config(
    "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
    vocab_size=128, max_seq_len=64))
rng = np.random.default_rng(0)
eng = Engine(model, ServingConfig(num_slots=2)).start()
futs = [eng.submit(rng.integers(0, 128, (int(rng.integers(3, 9)),))
                   .astype("int32"), max_new_tokens=6)
        for _ in range(6)]
outs = [f.result(timeout=300) for f in futs]
assert all(o.output_ids.size == 6 for o in outs), outs
snap = eng.stats()
assert snap["requests_completed"] == 6, snap
assert snap["slot_occupancy"] > 0, snap
eng.shutdown()
leaked = {t.ident for t in threading.enumerate()} - before
assert not leaked, f"leaked threads: {leaked}"
import paddle_tpu.observability as obs
with open("/tmp/pt_serving_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
print(f"serving smoke OK: 6 requests, occupancy "
      f"{snap['slot_occupancy']:.2f}, ttft {snap['ttft_ms_avg']:.0f}ms, "
      f"{snap['tick_compiled_hits']} compiled ticks, no leaked threads")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_serving_ci.prom \
    --serving-tick

echo "== multi-tenant adapter telemetry exposition =="
timeout -k 10 300 python - <<'EOF'
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import Engine, ServingConfig

def mk():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=128, max_seq_len=64))
    m.eval()
    return m

tmp = mk()
nn.attach_lora(tmp, rank=4)
rng = np.random.default_rng(7)
specs = {}
for i in range(2):
    for l in nn.lora_layers(tmp).values():
        l.lora_A.set_value(rng.standard_normal(
            l.lora_A.shape).astype(np.float32) * 0.3)
        l.lora_B.set_value(rng.standard_normal(
            l.lora_B.shape).astype(np.float32) * 0.3)
    specs[f"t{i}"] = nn.adapter_spec(tmp)
eng = Engine(mk(), ServingConfig(
    num_slots=2, max_queue=4, max_adapters=1, adapter_rank_pool=4,
    adapters=specs)).start()
prompt = rng.integers(0, 128, (6,)).astype("int32")
futs = [eng.submit(prompt, max_new_tokens=4, adapter_id=f"t{i}")
        for i in range(2)]
outs = [f.result(timeout=300) for f in futs]
snap = eng.stats()
assert snap["adapters_loaded"] >= 2, snap
assert snap["adapter_evictions"] >= 1, snap
assert snap["requests_routed_adapter"] == 2, snap
eng.shutdown()
import paddle_tpu.observability as obs
with open("/tmp/pt_lora_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
print(f"adapter smoke OK: {snap['adapters_loaded']} hot-loads, "
      f"{snap['adapter_evictions']} eviction(s) through a 1-slot pool")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_lora_ci.prom --lora

echo "== data pipeline goodput telemetry exposition =="
timeout -k 10 300 python - <<'EOF'
import numpy as np
from paddle_tpu import data as D
from paddle_tpu import observability as obs

class DS:
    def __len__(self):
        return 64
    def __getitem__(self, i):
        return np.float32(i)

pipe = D.pipeline(DS()).shard(0, 1).shuffle(seed=1).batch(8) \
    .device_prefetch(2)
n = sum(1 for _ in pipe)
assert n == 8, n
snap = pipe.goodput.snapshot()
assert snap["batches"] == 8, snap
with open("/tmp/pt_data_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
print(f"data goodput smoke OK: {snap['batches']} batches, "
      f"input_bound {snap['input_bound']:.2f}")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_data_ci.prom --data

echo "== sentinel rollback drill (loss spike -> anchor rollback -> replay-with-skip) =="
# bounded: the fast in-process drills prove detection + rollback +
# quarantined replay match a clean run, then the worker produces a
# sentinel dump that must pass the schema gate.
timeout -k 10 240 python -m pytest tests/test_sentinel.py -q -p no:randomly \
    -k "rollback_drill or quarantine_drill or off_trajectory"
rm -rf /tmp/pt_sentinel_drill && mkdir -p /tmp/pt_sentinel_drill
FLAGS_sentinel_dump_path=/tmp/pt_sentinel_drill/sentinel.json \
FLAGS_fault_inject="loss_spike:at_step=7,scale=1e6" \
    python tests/_sentinel_worker.py rollback /tmp/pt_sentinel_drill
python tools/check_telemetry.py \
    --sentinel-dump /tmp/pt_sentinel_drill/sentinel.json
python - <<'EOF'
import json
rep = json.load(open("/tmp/pt_sentinel_drill/report.json"))["report"]
assert rep["rollbacks"] == 1, rep
assert 7 in rep["quarantined"], rep
print(f"sentinel drill OK: {rep['rollbacks']} rollback, "
      f"quarantined {rep['quarantined']}, anchor at it "
      f"{rep['anchor_it']}")
EOF

echo "== hot-spare telemetry exposition (stream + park + peer restore -> prometheus gate) =="
timeout -k 10 120 python - <<'EOF'
import tempfile
import numpy as np
from paddle_tpu import observability as obs
from paddle_tpu.distributed.store import FileKVStore
from paddle_tpu.framework import hot_spare

store = FileKVStore(tempfile.mkdtemp(prefix="hs_ci_"))
hot_spare.declare_metrics()
# an async manager pre-declares ckpt.save_blocked_ms at zero samples
from paddle_tpu.framework.checkpoint_manager import CheckpointManager
CheckpointManager(tempfile.mkdtemp(prefix="hs_ci_ck_"), async_save=True)
hot_spare.advertise_buddy_map(store, "hs_ci", 2)
a0 = hot_spare.HotSpareAgent("hs_ci", 0, 2, store=store, every=1)
a1 = hot_spare.HotSpareAgent("hs_ci", 1, 2, store=store)
state = {"w": np.arange(4096, dtype=np.float32), "step": 5}
a0.snapshot_now(5, state, {"step": 5})
a0.close(park=False)        # the "dead" rank never parks
a1.park()                   # the survivor parks its held replica
a1.close(park=False)
hot_spare._STORES.pop("hs_ci", None)     # a relaunch starts cold
got = hot_spare.peer_restore("hs_ci", 0, store=store)
assert got is not None and int(got[0]["step"]) == 5, got
assert got[2] == "peer", got[2]
from paddle_tpu.observability import registry
assert registry.counter("ckpt.peer.snapshots").value >= 1
assert registry.counter("ckpt.peer.bytes_sent").value > 0
assert registry.counter("ckpt.peer.restores").value >= 1
with open("/tmp/pt_hot_spare_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
print("hot-spare smoke OK: snapshot streamed, parked by the buddy, "
      f"restored from {got[2]!r}, "
      f"{int(registry.counter('ckpt.peer.bytes_sent').value)} "
      "bytes replicated")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_hot_spare_ci.prom \
    --hot-spare

echo "== hot-spare recovery drill (2 procs, rank 1 hard-killed -> peer restore, losses match uninterrupted) =="
# bounded: one controller relaunch on the virtual CPU mesh, ~15s wall.
# The drill asserts restored_from=peer for the dead rank and a resumed
# loss trajectory within 5e-4 of the uninterrupted reference; the
# buddy_crash disk-fallback variant runs in the full RUN_SLOW suite.
PADDLE_TPU_RUN_SLOW=1 timeout -k 10 300 python -m pytest \
    tests/test_hot_spare.py -q -k "drill_peer_restore" -p no:randomly

echo "== telemetry smoke (hapi fit + exporter -> prometheus/json gates) =="
FLAGS_metrics_export_path=/tmp/pt_metrics_ci.jsonl \
FLAGS_metrics_export_interval_s=0.2 \
python - <<'EOF'
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import observability as obs

class Data:
    def __len__(self):
        return 32
    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        return (rng.normal(size=(8,)).astype(np.float32),
                np.array([i % 2], dtype=np.int64))

net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
model = paddle.Model(net)
model.prepare(optimizer=paddle.optimizer.SGD(
    learning_rate=0.1, parameters=net.parameters()),
    loss=nn.CrossEntropyLoss())
model.fit(Data(), batch_size=8, epochs=2, verbose=0)
snap = model.step_metrics.snapshot()
assert snap["steps"] == 8, snap
assert snap["step_time_ms"]["p50"] and snap["step_time_ms"]["p99"], snap
assert snap["examples_per_sec"] > 0, snap
assert snap["mfu"] is None, snap          # a CPU has no peak: no MFU
obs.stop_exporter()                      # flush the final snapshot line
with open("/tmp/pt_metrics_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
print(f"telemetry smoke OK: p50 {snap['step_time_ms']['p50']:.2f}ms, "
      f"p99 {snap['step_time_ms']['p99']:.2f}ms, "
      f"{snap['examples_per_sec']:.0f} examples/s")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_metrics_ci.prom \
    --snapshots /tmp/pt_metrics_ci.jsonl \
    --require-series train_step_time_ms train_examples_per_sec

echo "== flight-recorder drill (unhandled exception -> readable dump) =="
rm -f /tmp/pt_flightrec_ci.json
FLAGS_flight_recorder_path=/tmp/pt_flightrec_ci.json \
    python tests/_flightrec_worker.py crash 2>/dev/null || true
python - <<'EOF'
import json
data = json.load(open("/tmp/pt_flightrec_ci.json"))
assert data["reason"] == "exception", data["reason"]
assert data["error"]["type"] == "RuntimeError"
assert any(e["kind"] == "step" for e in data["events"])
print(f"flight recorder OK: {len(data['events'])} events, "
      f"reason={data['reason']}")
EOF

echo "== hang drill (collective_delay -> blamed timeout + stall dump) =="
rm -rf /tmp/pt_hang_drill
mkdir -p /tmp/pt_hang_drill/out /tmp/pt_hang_drill/logs
drill_start=$(date +%s)
set +e
FLAGS_collective_timeout_s=3 \
FLAGS_stall_dump_path=/tmp/pt_hang_drill/stall.json \
FLAGS_flight_recorder_path=/tmp/pt_hang_drill/flightrec.json \
FLAGS_fault_inject="collective_delay:op=all_reduce,at_seq=6,delay_s=300,rank=1" \
PADDLE_GUARDIAN_TERM_GRACE_S=5 \
timeout -k 10 120 python -m paddle_tpu.distributed.launch \
    --nproc_per_node 2 --max_restart 0 \
    --log_dir /tmp/pt_hang_drill/logs \
    tests/_guardian_worker.py /tmp/pt_hang_drill/out
drill_rc=$?
set -e
drill_elapsed=$(( $(date +%s) - drill_start ))
# the job must FAIL (not hang to the harness timeout, not succeed)
if [ "$drill_rc" -eq 0 ] || [ "$drill_rc" -ge 124 ]; then
    echo "hang drill FAILED: rc=$drill_rc (expected fast guardian abort)"
    exit 1
fi
grep -q "CollectiveTimeoutError" /tmp/pt_hang_drill/logs/worker.*.log
grep -q "all_reduce" /tmp/pt_hang_drill/logs/worker.*.log
# stall dump: schema-valid, blamed op/rank, detection < 2x the timeout
python tools/check_telemetry.py \
    --stall-dump /tmp/pt_hang_drill/stall.rank0.json
python - <<'EOF'
import json
d = json.load(open("/tmp/pt_hang_drill/stall.rank0.json"))
s = d["stall"]
assert s["op"] == "all_reduce" and s["missing_ranks"] == [1], s
assert s["waited_s"] < 2 * s["timeout_s"], \
    f"detection took {s['waited_s']}s vs timeout {s['timeout_s']}s"
print(f"hang drill OK: blamed {s['op']!r} seq {s['seq']} missing "
      f"ranks {s['missing_ranks']}, detected in {s['waited_s']}s")
EOF
echo "hang drill total wall time: ${drill_elapsed}s (rc=$drill_rc)"

echo "== elastic resize drill (train on 4 procs -> SIGTERM -> resume on 2) =="
# trains 4 steps on 4 procs, preempts, resumes on 2 — trajectory must
# match the uninterrupted run modulo batch order, and the resumed
# incarnation must genuinely reshard (layout fast path off, moment
# shards reassembled).  Bounded: the drill itself takes ~20s on CPU.
# PADDLE_TPU_RUN_SLOW: the resize drills are tier-1 `slow`-marked (they
# cost ~14s each); this dedicated lane still runs the 4->2 one
PADDLE_TPU_RUN_SLOW=1 timeout -k 10 300 python -m pytest \
    tests/test_reshard.py -q -k "resize_4_to_2" -p no:randomly

echo "== serving graceful-drain drill (SIGTERM -> finish in-flight, fail queue) =="
rm -rf /tmp/pt_drain_drill && mkdir -p /tmp/pt_drain_drill
FLAGS_flight_recorder_path=/tmp/pt_drain_drill/flightrec.json \
    python tests/_serving_drain_worker.py /tmp/pt_drain_drill
python - <<'EOF'
import json
d = json.load(open("/tmp/pt_drain_drill/drain.json"))
assert d["completed"] == 2 and d["tokens"] == [30, 30], d
assert d["queued_failed"] == 3 and d["rejected_after_drain"] == 1, d
print(f"serving drain OK: {d['completed']} in-flight completed, "
      f"{d['queued_failed']} queued failed, admissions closed")
EOF

echo "== gray-failure chaos campaign (seeded episodes + guardian ejection drill) =="
# bounded: thread-mode 3-replica fleet, fixed seed, 20 episodes drawn
# round-robin from {rpc_slow, rpc_drop, engine_slow, kill} plus the
# engine_slow ejection/readmission drill (a 10x-slow replica must be
# health-ejected, p99 must recover to <=1.5x the healthy baseline, and
# the victim must be canary-readmitted once the fault clears).  The
# runner exits nonzero on any lost/duplicate/mismatched request or
# leaked KV page; the gates re-check the summary schema and the
# guardian counter exposition.  Same --seed reproduces the identical
# fault schedule.
rm -rf /tmp/chaos_campaign_ci_traces
timeout -k 10 420 python tools/chaos_campaign.py --seed 0 --episodes 20 \
    --requests 4 --ejection-drill \
    --trace-dir /tmp/chaos_campaign_ci_traces \
    --out /tmp/chaos_campaign_ci.json \
    --episode-log /tmp/chaos_campaign_ci.jsonl \
    --prom-out /tmp/chaos_campaign_ci.prom
python tools/check_telemetry.py --campaign-summary /tmp/chaos_campaign_ci.json
python tools/check_telemetry.py --prometheus /tmp/chaos_campaign_ci.prom \
    --router --gray-failure

echo "== distributed tracing gate (chaos traces -> critical-path p99 attribution) =="
# the traced campaign above left per-process spools + the collector's
# merged.json; the analyzer must reconstruct >=95% complete critical
# paths, find exactly one winning span per kept trace, exactly one
# tail-sampling decision per request, and the span-sum must agree with
# the measured latency within 10% (ISSUE 19 acceptance).
python tools/trace_analyze.py \
    --trace /tmp/chaos_campaign_ci_traces/merged.json \
    --out /tmp/chaos_campaign_ci_trace_report.json --strict
python tools/check_telemetry.py \
    --trace /tmp/chaos_campaign_ci_traces/merged.json \
    --trace-report /tmp/chaos_campaign_ci_trace_report.json

echo "== tracing zero-overhead-off check (outputs byte-identical either way) =="
python - <<'EOF'
import os
import numpy as np

def run(trace_dir):
    from paddle_tpu.utils.flags import set_flags
    set_flags({"FLAGS_trace_dir": trace_dir,
               "FLAGS_trace_latency_threshold_ms": 0.0})
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_config
    from paddle_tpu.serving import Engine, ServingConfig
    paddle.seed(0)
    model = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=128, max_seq_len=64))
    rng = np.random.default_rng(0)
    with Engine(model, ServingConfig(num_slots=2)) as eng:
        futs = [eng.submit(
            rng.integers(0, 128, (int(rng.integers(3, 9)),))
            .astype("int32"), max_new_tokens=5) for _ in range(4)]
        return [f.result(timeout=300).output_ids.tobytes()
                for f in futs]

os.makedirs("/tmp/pt_trace_ci_overhead", exist_ok=True)
off = run("")
on = run("/tmp/pt_trace_ci_overhead")
assert off == on, "tracing changed the served bytes"
from paddle_tpu.observability import tracing
tracing.spool_now("/tmp/pt_trace_ci_overhead")
merged = tracing.merge_spools("/tmp/pt_trace_ci_overhead")
assert len(merged["traces"]) == 4, len(merged["traces"])
print("tracing overhead check OK: 4 requests byte-identical with "
      "tracing on/off, 4 traces collected when armed")
EOF

echo "== serving fleet router + migration telemetry (thread-mode disagg fleet -> prometheus gate) =="
python - <<'EOF'
import threading
import time
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import (ReplicaConfig, ReplicaServer,
                                RouterConfig, ServingConfig,
                                ServingRouter)

before = {t.ident for t in threading.enumerate()}
paddle.seed(0)
model = GPTForCausalLM(gpt_config(
    "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
    vocab_size=128, max_seq_len=64))
rng = np.random.default_rng(0)
master = TCPStore(is_master=True)
rcfg = ReplicaConfig(heartbeat_interval_s=0.2, heartbeat_ttl_s=1.5)
rep_p = ReplicaServer("rep-p", model, TCPStore("127.0.0.1", master.port),
                      ServingConfig(num_slots=2, max_queue=8,
                                    role="prefill"), rcfg)
rep_d = ReplicaServer("rep-d", model, TCPStore("127.0.0.1", master.port),
                      ServingConfig(num_slots=2, max_queue=8,
                                    role="decode"), rcfg)
router = ServingRouter(TCPStore("127.0.0.1", master.port),
                       RouterConfig(heartbeat_ttl_s=1.5,
                                    poll_interval_s=0.1,
                                    disaggregation=True)).start()
deadline = time.monotonic() + 60
while len(router.ring.members) < 2:
    assert time.monotonic() < deadline, router.replicas()
    time.sleep(0.05)
futs = [router.submit(rng.integers(0, 128, (5,)).astype("int32"),
                      max_new_tokens=4, session_id=i) for i in range(3)]
outs = [f.result(timeout=300) for f in futs]
assert all(o.output_ids.size == 4 for o in outs), outs
assert all(o.decoded_by == "rep-d" for o in outs), \
    [o.decoded_by for o in outs]
snap = router.stats()
assert snap["router_requests_routed"] == 3, snap
assert snap["router_replicas_alive"] == 2, snap
assert snap["migrations"] == 3, snap
assert snap["migration_pages_sent"] >= 3, snap
assert snap["migration_resumed_requests"] == 3, snap
with open("/tmp/pt_fleet_ci.prom", "w") as f:
    f.write(obs.render_prometheus())
router.close()
rep_p.close()
rep_d.close()
master.close()
time.sleep(1.0)                    # rpc handler threads exit on close
leaked = [t.name for t in threading.enumerate()
          if t.ident not in before and t.is_alive()]
assert not leaked, f"leaked threads: {leaked}"
print("fleet telemetry smoke OK: 3 routed, 3 migrated to rep-d, "
      "prometheus dumped, no leaked threads")
EOF
python tools/check_telemetry.py --prometheus /tmp/pt_fleet_ci.prom \
    --router --migration

echo "== driver hooks compile =="
python - <<'EOF'
import jax
from __graft_entry__ import entry, dryrun_multichip
fn, args = entry()
jax.jit(fn)(*args)
dryrun_multichip(2)
print("driver hooks OK")
EOF

echo "CI gates all green"
