"""Generate docs/INVENTORY.md — the auto-generated component inventory
(analog of the reference's contrib/codegen-tools op-def generation:
there it generates op classes + docs from definitions; here the living
registries ARE the definitions, and this script renders them).

    python tools/gen_inventory.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

# CPU-only by nature: doc generation imports every module and builds
# tiny nets to read their structure; it must never take an attached
# chip away from the one process that may hold it
jax.config.update("jax_platforms", "cpu")


# hand-maintained operations doc, re-emitted on every regeneration so
# the auto-generated op reference never clobbers it (ISSUE 2 satellite:
# the telemetry workflow lives in docs/OPS.md)
TELEMETRY_OPS_SECTION = """
## Telemetry operations (obs/)

Operating a run with the telemetry spine (ARCHITECTURE.md §9):

**Capture a timeline.** `DL4J_TPU_TRACE=1 python train.py` writes
`dl4j_tpu_trace_<pid>.jsonl` (or set the flag to an explicit path).
Drop the file into `chrome://tracing` or https://ui.perfetto.dev to
see per-thread `fit/etl` / `fit/step` / `fit/h2d` / `fit/dispatch` /
`fit/sync` spans. Summarize from the shell with

    python tools/xprof_summary.py dl4j_tpu_trace_<pid>.jsonl

(the same tool's XProf mode covers the device side: point it at a
`jax.profiler.trace` capture dir).

**Scrape metrics.** Start the endpoint with
`DL4J_TPU_METRICS_PORT=9464` (or `obs.metrics.start_server()` in
code), then point Prometheus — or `curl` — at
`http://127.0.0.1:9464/metrics`; `/healthz` returns 503 naming any
worker whose heartbeat is older than `DL4J_TPU_STALE_WORKER_SECS`.
Step-latency histograms, ETL waits, serving queue depth, retrace
sentry and compile-cache counters all appear as `dl4j_tpu_*`
families.

**Is the wrapper's pipeline full?** `ParallelWrapper.fit` keeps one
batch and one step in flight. Over the count of
`dl4j_tpu_worker_step_latency_seconds`,
`dl4j_tpu_worker_staged_ahead_total` is the share of steps whose batch
was on the chips before the step before them ended, and
`dl4j_tpu_worker_steps_ahead_total` the share launched before their
predecessor's loss was read: both read (n-1)/n for calls of n batches,
and the second reads 0 under an elastic context and falls with the
numerics monitor's cadence (a diagnostic step runs alone) and with a
checkpoint's or an evaluation's (a listener whose `reads_state` says
it saves or evaluates at an iteration has that step read before the
next is launched, so the zip holds its own iteration's weights). Since
a step's blocking read is its predecessor's,
`dl4j_tpu_worker_collective_sync_seconds_total` is the time the host
waited under a running step, not time the chips stood still; it holds
every step's wait, a call's last step's too, which is read after the
loop.

**Watch a long run.** `tools/tpu_watch.py` samples the same
surfaces from OUTSIDE the run — it touches no JAX backend and starts
no process, so the run keeps its chip to itself:

    python tools/tpu_watch.py --interval 600 \\
        --metrics-url http://127.0.0.1:9464/metrics \\
        --healthz-url http://127.0.0.1:9464/healthz \\
        --trace-jsonl dl4j_tpu_trace_<pid>.jsonl

printing one structured JSON line per sample (step counts/latency
sums, retrace/compile counters, stale workers, top span totals) —
redirect stdout to keep them.

**Post-mortems.** HBM-OOM crash dumps (`utils/crashreport.py`) carry
`perf.compile_report()` and `obs.report()` — metric values, worker
health, and the last spans of the dying run — next to the device
memory map.
"""

# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 3 satellite: the failure & recovery runbook lives in
# docs/OPS.md next to the telemetry workflow)
RESILIENCE_OPS_SECTION = """
## Failure & recovery runbook (resilience/)

Operating a run through failure (ARCHITECTURE.md §10):

**Preemption.** SIGTERM (what a preemptible slice receives) is honored
at the next iteration boundary when training under
`FaultTolerantTrainer`: the run checkpoints, persists `progress.json`
(with the mid-epoch `batch_in_epoch` position), and returns cleanly —
exit code 0. Re-running the same script resumes via
`resume_or_init(factory, ckpt_dir)`: newest *valid* checkpoint +
progress counters, replaying the exact uninterrupted trajectory
(`dl4j_tpu_preemptions_total` counts the clean stops).

**Corrupt checkpoints.** Every restore path verifies before it
restores (zip CRC sweep + required entries + the sidecar
`*.manifest.json` CRC32/size). A corrupt or partial checkpoint is
moved to `<ckpt_dir>/corrupt/` — inspect it there, it never blocks
the restart loop — and restore falls back to the newest valid one
(`dl4j_tpu_checkpoints_quarantined_total`). Writes are atomic
(tmp+fsync+`os.replace`), so only an external writer or disk fault
can produce one. The orbax sharded path behaves the same:
`ShardedCheckpointer.restore_latest_valid()` quarantines unrestorable
step dirs.

**Sharded optimizer checkpoints (ZeRO).** Training with
`ParallelWrapper(..., sharded_update=True)` carries the optimizer
state as 1/N shards per replica; checkpoint it with
`ShardedCheckpointer.save_wrapper(step, wrapper)` and restore with
`restore_wrapper(wrapper)` onto the SAME mesh topology — each device
writes/reads only its shard and the replicated layout is never
materialized. For zip/`ModelSerializer` export, fold first with
`wrapper.gather_opt_state()` (replicated-layout copy: export only,
never in the training loop).

**Retries.** `FaultTolerantTrainer` classifies errors
(`resilience.policy.classify`): transient (OSError/ConnectionError/
TimeoutError/bare RuntimeError) → restore newest valid checkpoint and
retry under exponential backoff with seeded jitter; deterministic
(shape/dtype/NaN messages) → ONE restore, then re-raise. Watch
`dl4j_tpu_resilience_restarts_total` — a climbing counter with flat
loss means the job is paying restore tax, not training.

**Serving under overload.** `ParallelInference` sheds instead of
blocking: a full queue raises `QueueFullError` at enqueue; a request
whose deadline (the `output(timeout=)` budget, or
`output_async(deadline_s=)`) expires in the queue is dropped
undispatched; `shutdown()` errors queued requests out immediately.
All three surface as
`dl4j_tpu_inference_requests_shed_total{reason=queue_full|deadline|shutdown}`
— alert on its rate vs `dl4j_tpu_inference_requests_total`.

**Fault drills.** Inject failures into a real run with
`DL4J_TPU_FAULT_PLAN` — named plans (`ckpt-io-flake`, `worker-crash`,
`etl-flake`, `serving-crash`, `preempt`) or rule syntax
`site:error=OSError:p=0.5:seed=3:max=2;...` over sites `ckpt_write`,
`ckpt_commit`, `step`, `iterator`, `worker_step`, `serving`. Unset,
the sites cost one branch (counter-asserted). Fires appear in
`dl4j_tpu_faults_injected_total{site=}`. The standing drill harness:

    python tools/chaos.py --plan ckpt-io-flake     # train scenario
    python tools/chaos.py --plan serving-crash     # serving scenario
    python tools/chaos.py --plan "ckpt_write:error=OSError:nth=1" --example lenet_mnist
    python tools/chaos.py --list

asserts convergence-to-baseline under each plan (bit-exact resume for
clean restore paths) and exits nonzero on any regression — run it
after touching checkpoint, trainer, or serving code.
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 4 satellite: the divergence-diagnosis runbook lives in
# docs/OPS.md next to the telemetry workflow)
NUMERICS_OPS_SECTION = """
## Diagnosing divergence (obs/numerics.py)

Operating a run through numeric trouble (ARCHITECTURE.md §11):

**Turn the observatory on.** `net.monitor_numerics(every=N)` makes
every N-th step a *diagnostic step*: the same XLA program returns
per-layer gradient/update/param norms, activation stats from the real
training forward, and non-finite counts as aux outputs — only scalars
cross to host, only at cadence. A `StatsListener` attaches a
record-aligned monitor automatically, so the training dashboard's
grad-norm / update:param-ratio / replica-divergence panels fill in
with zero extra configuration.

**Read the panels.** Healthy runs show update:param ratios drifting
around 1e-3 (the reference StatsListener's rule of thumb) and
per-layer grad norms moving together. A layer whose ratio runs orders
of magnitude hotter than its peers is mis-scaled (LR override,
init); a grad norm collapsing to 0 is a dead layer (check the
`dl4j_tpu_numerics_grad_norm` family); absmax activations marching
toward 3e38 forecast an overflow before it happens.

**NaN attribution.** When gradients or activations go non-finite, the
sentinel raises `NonFiniteError{layer, kind, iteration}` — forward
origin for activations (first layer in forward order), backward
origin for gradients. Under `FaultTolerantTrainer` this classifies
deterministic: ONE restore from the newest valid checkpoint, then
re-raise if it recurs — the log reads "layer gpt.h3.attn gradients
went non-finite at iteration 412 ... restoring iter_400". A
non-finite *score* at a sparse cadence escalates the next step to a
diagnostic one, so attribution is at most one step late.

**Replica divergence.** On the `ParallelWrapper` SYNC path, the
diagnostic step is an explicit `shard_map`: per-replica gradient
norms are `pmax − pmin` reduced before the mean erases them, and the
spread surfaces as `dl4j_tpu_numerics_replica_divergence{layer=}`. A
growing spread with healthy per-replica losses is the signature of a
sick chip (or a desynced data shard) — restart that worker before
the allreduce averages the damage into every replica.

**Watch remotely.** `tools/tpu_watch.py --metrics-url ...` renders a
`numerics` view per sample: top-k update:param outliers, a
total-grad-norm sparkline, worst replica divergence, and a
NONFINITE_ALARM line from the `dl4j_tpu_numerics_nonfinite_total`
counters. With `DL4J_TPU_TRACE` on, per-layer norms also stream as
Perfetto counter tracks (`numerics/grad_norm`) next to the step
spans.

**Drill it.** `DL4J_TPU_FAULT_PLAN="step:error=NonFiniteError:nth=6"`
injects the structured sentinel at the step site — the standing way
to verify the attribute-classify-restore path end-to-end without
poisoning real params.
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 7 satellite: the elastic-fleet runbook lives in docs/OPS.md
# next to the failure & recovery workflow)
ELASTIC_OPS_SECTION = """
## Elastic fleets & host preemption (resilience/elastic.py)

Operating training on preemptible/spot capacity (ARCHITECTURE.md §13):

**Bring-up.** Every host joins the fleet through a
`MembershipCoordinator` over a shared directory: an atomically-written
*lease* file per host, renewed like a heartbeat and mirrored into
`obs/health.py` (a dying peer is named on `/healthz` before the fleet
even reacts). `ElasticTrainer.bring_up()` waits for the expected
hosts, runs the propose→ack→commit agreement round, and forms the
mesh at the agreed world size — the committed *mesh epoch*
(generation number, `dl4j_tpu_mesh_epoch`) stamps every subsequent
step.

**Lease timing.** `DL4J_TPU_HOST_LEASE_SECS` (default 15) is the
eviction window: a host that misses it is moved aside
(`members/evicted/`, `dl4j_tpu_hosts_evicted_total`) at the next
agreement. The collective watchdog defaults to twice the lease — a
peer's death turns an indefinite collective hang into a
`CollectiveTimeoutError` within that window (a gloo/ICI connection
reset surfaces even faster). Size the lease to tolerate your worst
GC/compile pause: the background auto-renew thread keeps a busy host
alive, and a *wedged* host is fenced by the epoch stamp
(`StaleMeshEpoch`), not by lease expiry.

**Host loss.** The survivors' failed step raises (no indefinite
hang), and re-formation happens by *exec*: the wedged collective
runtime cannot be torn down in-process, so each survivor replaces its
process image, re-joins, agrees on the reduced membership (epoch+1,
a new epoch-salted coordinator port — stragglers from the old
generation are rejected, `dl4j_tpu_resilience_restarts_total` counts
the reforms), and **reshard-restores** the newest valid checkpoint:
`ShardedCheckpointer.restore_wrapper(reshard=True)` reads the
`world_<step>.json` manifest, gathers the N-sharded optimizer state,
and re-scatters it through `FlatShardLayout` onto the surviving M
devices — bit-exact on the real content. A corrupt newest step
quarantines and the next-newest valid one still reshards
(`restore_latest_valid(wrapper=...)`).

**Preemption.** SIGTERM on one host of a fleet = graceful departure:
the host drops its lease (`leave()`), peers re-form without waiting
out the window. SIGTERM on a *single-host* world checkpoints first
(the PR 3 behavior). Under `FaultTolerantTrainer` with a ZeRO
`sharded_update=True` wrapper, the preemption checkpoint publishes
through `save_wrapper` (1/N shards + world manifest) — never the
replicated zip path — and resume picks the newer of the sharded and
zip chains.

**Drills.** The standing fleet drill (also
`tests/test_elastic.py`):

    python tools/chaos.py --elastic                    # SIGKILL one of 3 hosts
    python tools/chaos.py --elastic --plan host-preempt  # graceful SIGTERM departure

asserts: bounded-timeout raise within the lease window, re-formation
at the reduced world size, reshard-restore of the newest valid step,
and a post-recovery trajectory bit-identical to the same-scale
uninterrupted baseline. Site-level drills: `host_death` and
`coordinator` fire under `DL4J_TPU_FAULT_PLAN` (named plans
`host-preempt`, `coord-flake`) like every other failure mode.
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 12 satellite: the fleet observability & straggler-hunting
# runbook lives in docs/OPS.md next to the elastic-fleet workflow)
FLEET_OPS_SECTION = """
## Fleet observability & straggler hunting (obs/fleet.py)

Operating a multi-host fleet with the fleet plane (ARCHITECTURE.md
§14):

**What publishes.** Every elastic host (`ElasticTrainer`) atomically
writes a versioned snapshot — its `/metrics` exposition, heartbeat
ages, a numerics tail, mesh epoch, step, and per-step barrier
entry/exit stamps — into `<elastic_dir>/telemetry/<host>.json` at the
`DL4J_TPU_FLEET_PUBLISH_SECS` cadence (default 1 Hz). Non-elastic
training pays one branch and publishes nothing
(`dl4j_tpu_fleet_snapshots_published_total` stays 0).

**Read the fleet view.** Aggregate from anywhere that sees the shared
dir:

    python tools/tpu_watch.py --interval 30 --fleet-dir <elastic_dir>

emits one `fleet` line per sample: the per-host step/epoch/age table,
a collective-skew sparkline with the straggler named, and
NONFINITE/EVICTED alarms. In code, `obs.fleet.aggregate(dir)` merges
every snapshot into one Prometheus exposition where each sample
carries `host=` and `mesh_epoch=` labels; the standing `/metrics`
server also serves it on `/fleet` after
`obs.metrics.set_fleet_dir(dir)` (done automatically by
`ElasticTrainer.bring_up`).

**Hunt stragglers.** `dl4j_tpu_collective_skew_seconds{host=}` is how
late each host entered the anchor collective relative to the first-in
peer; `dl4j_tpu_collective_straggler{host=}` is 1 for the last-in
host. A host that is 40ms late EVERY step is a sick chip or a starved
input pipeline — compare its `fit_etl` share before blaming the ICI.
Attribution anchors on lease evidence, never snapshot staleness: with
every lease live it uses the newest step COMMON to all hosts'
published windows (a snapshot lagging by the publish cadence is
normal, not a verdict); a lease-dead host (expired or no lease at
all) is the straggler, so a corpse is named even while every survivor
is wedged at the same barrier. `/healthz` tells the same story from
one table: `stale_hosts` (lease ages, each under its OWN lease
window) next to `stale_workers`.

**Post-mortems.** On `NonFiniteError`, `StaleMeshEpoch`,
`CollectiveTimeoutError`, SIGTERM preemption, or eviction, the flight
recorder dumps a versioned bundle into `<elastic_dir>/postmortem/`:
the last `DL4J_TPU_FLEET_RING` step records (barrier stamps, loss,
mesh-epoch events), the obs span/metric tail, and the fleet skew view
at the moment of death (`dl4j_tpu_flight_recorder_dumps_total{cause=}`).
When a host is evicted, the surviving leader snapshots the corpse's
FINAL telemetry into `<host>.evicted.<ts>.json` — the dead host's
last step survives the death. Start there: the eviction bundle's
`fleet.skew.straggler` is the ADJUDICATED naming (computed after the
lease verdict); survivor crash dumps race instant transport errors
and are best-effort testimony.

**Drill it.** `python tools/chaos.py --elastic` SIGKILLs one host of
a live fleet and asserts the whole chain: survivor bundles exist with
skew views, the eviction bundle names the corpse as the final-step
straggler and carries its last step, and the post-reform fleet
exposition carries the bumped `mesh_epoch=` labels.
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 13 satellite: the serving-under-load runbook lives in
# docs/OPS.md next to the failure & recovery workflow)
SERVING_OPS_SECTION = """
## Serving under load (serving/)

Operating the continuous-batching gateway (ARCHITECTURE.md §15):

**Bring-up.** Build the gateway over a trained LM and warm it BEFORE
taking traffic:

    gw = ServingGateway(model, net, max_slots=16, block=16,
                        max_context=2048, queue_limit=256)
    gw.warmup()          # decode step + every prefill bucket, AOT

After `warmup()` the retrace sentry must stay flat no matter how
traffic arrives — shapes are fixed at `(max_slots, block)` and
prompts snap to the same power-of-two buckets `generate()` uses
(`zoo.gpt.prompt_bucket`, one shared table). A climbing
`dl4j_tpu_retrace_unplanned_shapes{function="serving.decode_step"}`
means someone changed the step signature without re-warming.

**Size the pool.** The paged KV cache is the admission currency: each
request reserves `ceil(max(prompt_bucket, prompt+max_new-1)/block)`
pages for its WHOLE life, so an admitted sequence never stalls
mid-flight. Watch `dl4j_tpu_serving_kv_pages_free` against
`dl4j_tpu_serving_queue_depth`: pages pinned at 0 with a standing
queue means the pool (`n_pages`) is the bottleneck, not the slots.
Pool bytes = `n_pages x n_layers x Hkv x 2D x block` (x1 int8, x4
f32) — int8 pages (`cache_quant="int8"`) halve the read traffic AND
double the sequences a pool holds.

**Watch the SLOs.** `dl4j_tpu_serving_ttft_seconds` (submit -> first
token: queue wait + prefill) is the admission-health histogram —
a fattening p99 with free pages means slot pressure; with
`dl4j_tpu_serving_kv_pages_free` at 0 it means pool pressure.
`dl4j_tpu_serving_step_seconds` is the wall time a decode step adds,
observed when its tokens are read: while the loop keeps a step in
flight that is the gap between two tokens of every in-flight
sequence; for the first step after a drain it is launch, device time
and read-back. `dl4j_tpu_serving_steps_ahead_total` over its count is
the share of steps launched before their predecessor's tokens were
read: it falls with admissions (each drains the step in flight), and
reads 0 under `spec_k > 1` and `prefix_sharing`, which read every
step before the next launch. Shed posture mirrors
ParallelInference:
`dl4j_tpu_serving_requests_shed_total{reason=queue_full|deadline|shutdown|fault}`
— alert on its rate vs `dl4j_tpu_serving_requests_total`.
`tools/tpu_watch.py --metrics-url ...` renders a `serving` view per
sample (occupancy, TTFT p50/p99, token-throughput sparkline, SHED
alarms).

**Load-test.** The standing trace driver:

    python tools/serving_trace.py --mode open --rate 200 --requests 256
    python tools/serving_trace.py --mode closed --clients 32 --baseline

(open loop = arrivals you don't control, overload shows up as shed
rate + TTFT tail; closed loop = sustainable throughput at fixed
concurrency; `--baseline` adds the request-at-a-time `generate()`
comparison). The tool runs on whatever platform `JAX_PLATFORMS`
names (unset: the attached TPU) as ONE process; `--smoke` is its
small wiring configuration, and its report names the platform it
ran on.

**Fault posture.** An exception inside a decode iteration (including
the `serving` fault site under `DL4J_TPU_FAULT_PLAN`) sheds every
in-flight sequence with a structured `SequenceAborted` carrying the
tokens already streamed, releases their pages, and keeps serving —
never a wedged slot or leaked page. Drill it:

    python tools/chaos.py --plan serving-crash

asserts both front ends (batched queue + gateway) shed-and-survive,
with page conservation checked.

**Request-scoped traces.** Under `DL4J_TPU_TRACE` every request
leaves an async track in the Chrome JSONL keyed by its request id:
`serving.request` (submit → retire/abort, tenant + outcome + token
count in the args) with nested `serving.request/queue_wait`,
`/prefill`, and `/decode_steps` phases — drop the file into Perfetto
to see exactly where one tenant's p99 went. With or without the
flag each request leaves one record in the always-on ring
(ARCHITECTURE.md §9); with it off no event is built or written.

**Reading a decode-step record.** The worker keeps one decode step in
flight, so a `serving.decode_step` record (one an iteration that
launches a device step) stamps what the THREAD did, not one step's
life: `/dispatch` is the launch of this record's step, `/sync` the
blocking read of the step launched the iteration before (it returns
while this one runs, so in steady state it is about one device step
long and the device never waits for it), `/deliver` that earlier
step's tokens pushed. `active`, `kv_pages` and `state_bytes` are those
of the step launched; `ahead` is 1 when it was launched before its
predecessor's tokens were read, 0 for the step that enters an empty
pipeline (the first, and the first after an admission, a pause or a
park: each drains the step in flight and leaves a `serving.drain`
record). A device program therefore ends after the `sync` of the
record that launched it: lay the next record's `sync` over it.

**KV-page occupancy.** `dl4j_tpu_serving_kv_page_occupancy` (fraction
of usable pages reserved — 1.0 means admission control is the
bottleneck, add pages or shed earlier) and
`dl4j_tpu_serving_kv_pages_reserved` per tenant (whole-life
reservations — one tenant pinning the pool starves the rest; the
`tpu_watch` serving view surfaces both next to `kv_pages_free`).
`dl4j_tpu_serving_kv_pages_walked` is the pages the last decode
step's attention read (the sum over active slots of
`ceil(length / block)`, also `kv_pages` on every
`serving.decode_step` record): against `max_slots × max_context /
block` page-table entries it is the share of the pool a step touches,
and what the paged kernel's time should follow (ARCHITECTURE.md §17).
A retention model (`mixer="power_retention"`) walks no KV page:
`dl4j_tpu_serving_state_pool_bytes` is its recurrent-state pool as
stored (one fixed-size page a sequence; 0 for a KV pool) and
`dl4j_tpu_serving_state_bytes_moved` counts the state bytes its decode
steps read and wrote (`state_bytes` on every `serving.decode_step`
record): over the steps' device time it is the bandwidth the state
kernel reaches (ARCHITECTURE.md §15).
A hybrid decoder (`mixer="hybrid"`: Mamba-2 layers beside attention
layers) does both at once: its steps count `state_bytes` (the float32
states and the convolution tails of its Mamba layers, a live slot's
read once and written once) AND `kv_pages` (what its attention layers
walk), `dl4j_tpu_serving_state_pool_bytes` reads its state pool as
stored (states and tails, trash page included) and
`dl4j_tpu_serving_kv_pages_free` its KV pages alone: a sequence's state
page is its decode slot's, so only a KV page can leak.
A latent-attention model (`mixer="latent"`) walks latent pages:
`dl4j_tpu_serving_latent_rows_read_total` counts the cached positions
its decode steps' attention read (`latent_rows` on every
`serving.decode_step` record: one row of `kv_rank + rope` values a
position and layer; `latent_chunks` beside it counts the (slot, chunk)
items a layer's page walk of that step has, by the decode kernel's own
chunk: all but a call's first are copied while their predecessor is
multiplied, and `latent_chunks` x the chunk's rows over `latent_rows`
bounds the rows the kernel multiplies for one it needs; a record
count only, no `/metrics` name). Where its feed-forward routes,
`dl4j_tpu_serving_expert_pairs_total` counts the token-expert pairs
the experts HELD HERE computed, decode steps and prefills alike; a
step's record carries `expert_pairs`, `experts_hit` (held experts with
at least one pair, summed over the expert layers: what the step had to
read of the experts' weights) and `expert_pairs_max` (the fullest held
expert's pairs, summed over the layers: max over mean is the load
imbalance). These three are of the step whose tokens that call READ;
`latent_rows` and `latent_chunks` are of the step it launched.
`dl4j_tpu_moe_expert_layers_traced_total{path="kernel"|"loop"}` says
which form `ops.moe.experts` chose for the expert layers it TRACED
(the pipelined tile kernel where an expert's three matrices lie in VMEM
twice over, lane-aligned, on the TPU; else the tile loop): decided once
a program from the operands' shapes, so a program loaded by its key
counts nothing, and the traced program's `compile/jaxpr_trace` record
carries `expert_layers_kernel` / `expert_layers_loop` with `expert_f`,
`expert_w`, `expert_held`.
"""

# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 16 satellite: the spec-decode + prefix-sharing runbook lives
# in docs/OPS.md next to the serving runbook it extends)
SPEC_DECODE_OPS_SECTION = """
## Speculative decode + prefix sharing (serving/)

Two opt-in gateway features (ARCHITECTURE.md §18) that attack the
serving cost from both ends — admission (copy-on-write prefix
sharing: requests repeating a known prefix adopt its pages and
prefill only the novel suffix) and steady-state decode (self-
speculative multi-token steps: k-1 host-drafted tokens verified in
one fixed-shape forward, the agreeing prefix accepted):

    gw = ServingGateway(model, net, max_slots=16, block=16,
                        spec_k=4, prefix_sharing=True)
    gw.warmup()    # + per-k spec step, CoW copy, suffix buckets

**The k grid.** `spec_k` must come from `scheduler.SPEC_KS` (the
constructor rejects off-grid widths): warmup AOT-compiles one spec
executable per configured k plus the downward closure of suffix
prefill buckets, so ANY admission order — fresh prompt, whole-prompt
repeat, partial-prefix extension — stays retrace-free. Lint rule 10
(`tools/lint_instrumentation.py`) holds the builder set, the
`WARMUP_FEEDS` table, and `SPEC_KS` in lockstep, and fails CI when a
`dl4j_tpu_serving_spec_*` family loses its dashboard/runbook surface.

**Watch the accept rate.** `dl4j_tpu_serving_spec_accept_rate`
(per-step histogram of accepted/(k-1)) is the feature's health
number: tokens/step = `1 + accept_rate * (k-1)`, so a rate pinned
near 0 means the verify rows are pure overhead — lower k or turn
spec off for that workload. The cumulative pair
`dl4j_tpu_serving_spec_accepted_total` /
`dl4j_tpu_serving_spec_drafted_total` gives the same ratio across a
whole deployment window (`tpu_watch`'s serving view renders it as
`spec_accept_rate`). Greedy only: the gateway refuses
`sample=True` + spec, because the accept rule compares argmax.

**Watch the sharing win.** `dl4j_tpu_serving_prefix_hits_total` over
`dl4j_tpu_serving_requests_total` is the admission hit rate;
`dl4j_tpu_serving_prefix_prefill_tokens_saved_total` is the prefill
work sharing deleted (the TTFT win is proportional);
`dl4j_tpu_serving_prefix_shared_pages` gauges how much of the pool is
multi-referenced right now, and
`dl4j_tpu_serving_prefix_cow_copies_total` counts tail-page clones —
a high CoW rate with a low hit rate means prompts share page-aligned
prefixes rarely (raise the system-prompt length, or align it to
`block`).

**Acceptance measurement.** The shared-system-prompt A/B (baseline
gateway vs spec+sharing on the same weight-read-bound CPU smoke LM):

    python tools/serving_trace.py --shared-prefix

reports TTFT and tokens/sec speedups beside prefix-hit rate, prefill
tokens saved, and the accept rate; the dossier's `spec_decode` row
records the same report via the forced-CPU subprocess protocol.
Custom traces: `--prefix-sharing --spec-k 4` on any
`tools/serving_trace.py` run.

**Fault posture.** Refcounted pages keep the shed contract exact: an
aborted sequence drops only its OWN refs — shared pages survive for
their siblings, and the pager's `check_invariants()` machine-checks
refcount conservation (no free-while-referenced, no leak) after
every transition. Drill it:

    python tools/chaos.py --plan serving-crash

runs the gateway with CoW sharing + spec decode live, faults a step
mid-trace, and asserts page conservation plus a dense-identical
post-fault shared wave.
"""

# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 18 satellite: the serving-fleet autoscaling runbook lives in
# docs/OPS.md between the serving runbook and the elastic-fleet
# machinery it composes)
SERVING_FLEET_OPS_SECTION = """
## Serving fleet autoscaling (serving/fleet.py)

One gateway is one process; the fleet layer (ARCHITECTURE.md §20)
turns N of them into one elastic service on three already-shipped
planes: PR 6 membership leases, PR 7 fleet telemetry, and the
content-addressed compile store. Nothing here adds a side channel —
the router steers by exactly what replicas publish.

**Bring-up.** Each replica runs startup prefetch BEFORE its first
lease: `ServingReplica.start()` AOT-compiles every `STARTUP_PREFETCH`
bucket (lint rule 12 holds that tuple equal to the scheduler's
`WARMUP_FEEDS` keys, and holds the warmup call ahead of the lease
calls), consults the compile store's manifest for its program
fingerprint, then opens the HTTP front end and renews. `/healthz`
answers 503 `warming` until the gateway is warm — a cold replica is
never routable. Point every replica and the router at the same
shared directory; set `DL4J_TPU_COMPILE_STORE` to the fleet store so
a respawned process deserializes its siblings' compiles (the
`--serving-fleet` drill asserts cold p50 TTFT ≤ 1.2× warm via
`aot_hits` and persistent-cache counters).

**Routing.** `ServingRouter.submit` places each request on the
least-loaded live+ready replica (published queue depth + active
slots + the router's own in-flight count); transport failures
re-route; an impossible placement is shed as a structured
`SequenceAborted` bounded by `DL4J_TPU_FLEET_SHED_BUDGET` — never a
hung client. Watch the plane:

    python tools/tpu_watch.py --fleet-dir /shared/fleet

adds replica columns (ready/live, queue depth, KV occupancy, warm
buckets, sheds, lease age) and a NOT_READY alarm; the router's own
exposition carries `dl4j_tpu_router_requests_total` (per replica),
`dl4j_tpu_router_replicas_ready`, `dl4j_tpu_router_reroutes_total`,
and `dl4j_tpu_router_sheds_total` (by reason — `no_replica` means
capacity, `over_budget` means the contract breached, page the
operator). Fleet capacity moves show as
`dl4j_tpu_serving_fleet_spawns_total` /
`dl4j_tpu_serving_fleet_evictions_total`, per-replica warmth as
`dl4j_tpu_serving_fleet_warm_buckets` and
`dl4j_tpu_serving_fleet_replica_ready`.

**One process for each chip.** A chip belongs to one process at a
time: a parent that has initialised a JAX backend holds it, and a
child that needs it then fails or hangs. `FleetSupervisor.spawn_fn`
leaves the spawn to the deployment, so the rule is the deployment's
to keep: on ONE host, replicas are threads of one process, one per
device (`jax.devices()[i]`), never one process per replica on a
shared chip; separate replica processes belong on separate hosts (or
on disjoint chips handed out by `TPU_VISIBLE_CHIPS` before either
starts JAX). The supervisor's own process must stay off JAX if the
replicas it spawns need the chip. The chaos drills
(`tools/chaos.py`) start several processes and therefore run on the
CPU backend by nature.

**Scaling + failure.** `FleetSupervisor.poll()` evicts expired
leases and respawns toward `target` (a spawn stays pending until its
lease appears — no double-spawn). A killed replica disappears from
routing within one lease window; its postmortem bundle lands under
`<fleet>/postmortem/`. Drill the whole contract:

    python tools/chaos.py --serving-fleet

kills one of three replicas mid-trace and asserts detection ≤ one
lease window, zero hung clients, losses ≤ the shed budget (all
structured), store-warmed respawn TTFT, and the epoch flip with the
new replica ready.
"""

# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 14 satellite: the Pallas-gap-naming runbook lives in
# docs/OPS.md next to the other runbooks)
DEVTIME_OPS_SECTION = """
## Naming the Pallas gaps (obs/devtime.py)

ARCHITECTURE §4's policy is "Pallas only where XLA has a gap"; the
device-time observatory (ARCHITECTURE.md §16) is the instrument that
names the gaps. Host wall-clock spans cannot attribute
asynchronously-dispatched device time to layers — this pipeline asks
the device itself.

**On demand.** The perf dossier emits the ranked report on every run:

    python tools/perf_dossier.py --smoke --out dossier.json
    # -> the "hot_path_gaps" section

Each entry carries `gap.scope` (the `named_scope`-derived layer /
phase name, or `op:<class>` for unattributed ops), `gap.device_ms` /
`gap.share` (measured device time and its share of the window),
`gap.ops` / `gap.fusions` / `gap.backward_ms`, `gap.flops` /
`gap.bytes` (HLO-derived estimates), `gap.utilization` and
`gap.bound` (achieved-vs-roofline fraction of the binding resource,
peaks by `device_kind` from `environment.DEVICE_PEAKS`; an unknown
device is an error, `DL4J_TPU_PEAK_*` are explicit overrides), and
`gap.pallas_candidate` — true when the scope is ≥5% of the window,
under 35% of roofline, and not already a custom call. Rank by
`gap.share`, filter by `gap.pallas_candidate`: that list IS the
kernel-library backlog, with the evidence attached.

**On cadence.** `DL4J_TPU_DEVTIME=1` installs the fit-loop monitor:
every `DL4J_TPU_DEVTIME_EVERY`-th iteration opens a
`jax.profiler.trace` window for `DL4J_TPU_DEVTIME_STEPS` steps,
attributes it, and publishes `dl4j_tpu_devtime_scope_seconds` /
`dl4j_tpu_devtime_scope_share` / `dl4j_tpu_devtime_scope_utilization`
(per scope, last capture), `dl4j_tpu_devtime_pallas_candidates`, and
the capture-cost meters `dl4j_tpu_devtime_captures_total` /
`dl4j_tpu_devtime_capture_seconds_total` — budget the cadence with
the latter: a capture costs a profiler session plus an xplane parse,
so keep `EVERY` in the hundreds. `tpu_watch --metrics-url` renders
the ranking as the `devtime` view. Unset, the fit loops pay one
branch and run zero profiler sessions (counter-fenced).

**Raw captures.** `tools/xprof_summary.py DIR` summarizes the newest
capture session under DIR, merging every host's `*.xplane.pb`; pass
an explicit `.xplane.pb` file to read one host. Attribution quality:
scopes come from the executed programs' HLO metadata — AOT-warm the
step (`net.warmup(...)`) before capturing, or un-warmed programs fall
back to `op:<class>` buckets.
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 15 satellite: the gap-closing runbook lives in docs/OPS.md
# next to the gap-naming runbook it completes)
FUSED_OPS_SECTION = """
## Closing a named gap (ops/ fused-primitive library)

The §4 policy is "Pallas only where XLA has a gap"; "Naming the
Pallas gaps" (above) produces the candidate list. This runbook is the
other half — turning a named gap into a closed one (ARCHITECTURE §17).

**1. Confirm the gap.** Re-run the dossier and check the scope still
ranks: `python tools/perf_dossier.py --smoke --out d.json`, read
`hot_path_gaps` — you want `gap.share` ≥ 5%, `gap.utilization` < 35%,
`gap.closed_by` null. Scopes already closed are listed under
`closed_gaps` with the kernel that closed them; `open_gaps` is the
remaining backlog.

**2. Write the kernel in `ops/`.** Fwd + bwd Pallas kernels with a
`jax.custom_vjp`, a trace-time dispatch gate (TPU or
`DL4J_TPU_KERNEL_FORCE`), and a fallback that is the EXACT expression
the call site ran before — gate-off programs must stay
byte-identical. `ops/fused_norms.py` is the template: single-pass
forward, recompute-style backward, cross-row parameter grads
accumulated over the sequential grid.

**3. Register it.** Add a `KERNEL_REGISTRY` entry
(`ops/kernel_registry.py`): fallback, parity test reference, the
kernel's own `devtime.scope` name, and `closes` patterns matching the
gap-report scopes it serves. Add the kernel to `SCOPE_SITES`
(`tools/lint_instrumentation.py`). Lint rule 9 fails tier-1 until all
of it lines up — and rejects any `pl.pallas_call` outside `ops/`.

**4. Prove the close.** Parity tests (fwd AND bwd, interpret mode,
run under `DL4J_TPU_KERNEL_FORCE=1`), the gate-off byte-identity
fence, and a before/after dossier row. The next `gap_report()` marks
the scope `gap.closed_by` = your kernel, drops its
`dl4j_tpu_devtime_scope_pallas_candidate` gauge to 0, and the
kernel's parity test (its `KERNEL_REGISTRY` `parity` anchor) carries
the per-kernel parity status from then on.

**Ride-alongs to check.** If the kernel serves the training path,
verify the numerics observatory still attributes (the diagnostic taps
ride the same forward) and the strict-sentry fit fence still passes
(the kernel must not add traced shapes). If it serves decode/serving,
re-run the serving identity fences (paged decode is token-identical
to dense decode by contract).
"""


# hand-maintained operations doc, re-emitted on every regeneration
# (ISSUE 17 satellite: the wire-bound-hunting runbook lives in
# docs/OPS.md next to the gap-naming runbook it extends)
COMM_OPS_SECTION = """
## Hunting wire-bound steps (obs/commtime.py)

"Naming the Pallas gaps" (above) attributes device time to scopes;
this runbook attributes the INTERCONNECT — per-collective wire bytes
and collective device time, joined to the same `dl4j.*` scopes
(ARCHITECTURE.md §19). A scope whose collective time exceeds half its
device time is wire-bound: the link, not a kernel, is the ceiling, so
it is never a Pallas candidate — fix it with overlap, sharding, or
gradient compression instead.

**Static (any box, no capture).** The wire ledger reads compiled HLO:

    python -m tools.collective_volume --markdown

prints per-config collective counts, ring-model wire bytes/step, the
projected ICI time at the v5e `ici_gbs` peak of
`environment.DEVICE_PEAKS` (45 GB/s per link direction), and the measured-vs-dense column for
the encoded-gradient exchange. In code,
`commtime.wire_ledger(executables)` gives the same account per scope
(`by_scope["zero.reduce_scatter"]`, ...) — anonymous collectives land
in `op:<kind>` buckets, and lint rule 11 keeps the in-repo emitters
scoped so those stay empty.

**On cadence.** `DL4J_TPU_COMMTIME=1` installs the fit-loop monitor
(`DL4J_TPU_COMMTIME_EVERY` / `DL4J_TPU_COMMTIME_STEPS`, same shape as
the devtime monitor): each window publishes
`dl4j_tpu_comm_scope_wire_bytes_per_step`,
`dl4j_tpu_comm_scope_collective_seconds`,
`dl4j_tpu_comm_scope_step_share`,
`dl4j_tpu_comm_scope_link_utilization` (achieved GB/s over the
device's `ici_gbs` peak), `dl4j_tpu_comm_op_count` per kind,
`dl4j_tpu_comm_wire_bound_scopes`, and the capture meters
`dl4j_tpu_comm_captures_total` /
`dl4j_tpu_comm_capture_seconds_total`. `tpu_watch --comm` renders the
ranking; the fleet snapshot carries it host-labeled for free. Unset,
the fit loops pay one branch and run zero profiler sessions
(counter-fenced).

**Reading the numbers.** On TPU the collective seconds are ICI time
and `link_utilization` is achieved-vs-peak; on CPU/gloo captures they
time host-side copies — the views are marked `estimate_only` and only
the ledger bytes are exact. `gap.bound == "wire"` in the dossier's
`hot_path_gaps` (and `comm_observatory.wire_bound_scopes`) is the
per-scope alarm; `tools/xprof_summary.py DIR --comm` is the offline
twin over a kept capture. Gates: the ZeRO step's ledger must show
reduce-scatter tensor bytes ≈ grad_bytes/N under
`zero.reduce_scatter` and all-gather tensor bytes ≈ param bytes under
`zero.all_gather` (the bench `comm` section asserts both ≈ 1.0).
"""


def main():
    import warnings
    warnings.filterwarnings("ignore")
    import deeplearning4j_tpu.nn.layers  # noqa: F401 (registers layers)
    from deeplearning4j_tpu.autodiff.ops_registry import OPS
    from deeplearning4j_tpu.nn.layers.base import _LAYER_REGISTRY
    from deeplearning4j_tpu.ops import activations, losses
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.nn.constraints import _CONSTRAINTS, _NOISES
    from deeplearning4j_tpu import zoo

    lines = ["# Component inventory (auto-generated)",
             "",
             "Run `python tools/gen_inventory.py` to refresh.",
             ""]

    def section(title, names, per_line=6):
        lines.append(f"## {title} ({len(names)})")
        lines.append("")
        names = sorted(names)
        for i in range(0, len(names), per_line):
            lines.append(", ".join(f"`{n}`"
                                   for n in names[i:i + per_line]) + ",")
        if lines[-1].endswith(","):
            lines[-1] = lines[-1][:-1]
        lines.append("")

    # honesty split: an "alias" is a second name bound to the same
    # implementation object (the reference registry aliases the same
    # way, e.g. multiply/mul) — report base vs alias counts separately
    # so the headline number can't be read as inflated
    seen_impl = {}
    aliases = []
    for name in OPS:
        impl = OPS[name]
        if id(impl) in seen_impl:
            aliases.append(name)
        else:
            seen_impl[id(impl)] = name
    base_ops = [n for n in OPS if n not in set(aliases)]
    lines.append(f"## SameDiff ops ({len(OPS)} registered = "
                 f"{len(base_ops)} base + {len(aliases)} aliases)")
    lines.append("")
    section("Base ops", base_ops)
    section("Aliases (same implementation object as a base op)",
            sorted(aliases))
    section("Layers", list(_LAYER_REGISTRY))
    section("Activations", list(activations._REGISTRY))
    section("Losses", list(losses._REGISTRY))
    def all_subclasses(cls):
        out = []
        for c in cls.__subclasses__():
            out.append(c.__name__)
            out.extend(all_subclasses(c))
        return out

    section("Updaters", all_subclasses(upd.Updater))
    scheds = [c.__name__ for c in upd.Schedule.__subclasses__()]
    section("LR schedules", scheds)
    section("Constraints", list(_CONSTRAINTS))
    section("Weight noise", list(_NOISES))
    import inspect
    zoo_models = [
        n for n in dir(zoo)
        if inspect.isclass(getattr(zoo, n))
        and issubclass(getattr(zoo, n), zoo.ZooModel)
        and getattr(zoo, n) is not zoo.ZooModel]
    zoo_models += [n for n in dir(zoo)
                   if not inspect.isclass(getattr(zoo, n))
                   and callable(getattr(zoo, n)) and n[:1].isupper()
                   and n not in ("DL4JResources",)]
    section("Zoo models", sorted(set(zoo_models)))

    from deeplearning4j_tpu.nn.vertices import _VERTEX_REGISTRY
    section("Graph vertices", list(_VERTEX_REGISTRY))
    from deeplearning4j_tpu.nn.preprocessors import _PREPROC_REGISTRY
    section("Input preprocessors", list(_PREPROC_REGISTRY))
    from deeplearning4j_tpu import clustering as _cl
    section("Clustering / manifold / ANN",
            [n for n in _cl.__all__])
    from deeplearning4j_tpu import nlp as _nlp
    section("NLP", [n for n in _nlp.__all__])
    from deeplearning4j_tpu.train import solver as _sv
    section("Solvers", [c.__name__ for c in
                        _sv.BaseOptimizer.__subclasses__()])
    from deeplearning4j_tpu import eval_ as _ev
    section("Evaluation", [n for n in _ev.__all__])

    out = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "INVENTORY.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {os.path.normpath(out)}:")
    for ln in lines:
        if ln.startswith("## "):
            print(" ", ln[3:])

    # ---- per-op API reference (docs/OPS.md) ---------------------------
    # analog of the reference codegen's generated op documentation
    # (contrib/codegen-tools): signature + alias target + OpValidation
    # status per op, straight from the living registry and the
    # coverage-gated validation suite
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tests"))
    from test_op_validation import CASES  # noqa: E402
    alias_of = {n: seen_impl[id(OPS[n])] for n in aliases}
    n_grad = sum(1 for cs in CASES.values()
                 if any(c[2] for c in cs))
    n_gold = sum(1 for cs in CASES.values()
                 if any(c[3] is not None for c in cs))
    op_lines = [
        "# SameDiff op reference (auto-generated)", "",
        "Every op is a pure jax-traceable function in "
        "`autodiff.ops_registry.OPS`, callable eagerly, through "
        "`sd.math.<name>(...)` in a SameDiff graph, or via "
        "`Nd4j.exec`. Signatures below: positional args are arrays, "
        "keyword args are static attributes (reference: iArgs/tArgs/"
        "bArgs of the declarable op).", "",
        "**OpValidation status** (reference "
        "`org.nd4j.autodiff.opvalidation`, coverage-gated by "
        "`tests/test_op_validation.py::test_every_op_has_validation_"
        "case`): every op below has at least one executed forward "
        f"case; {n_grad} are finite-difference gradient-checked "
        f"(`grad`), {n_gold} are compared against numpy goldens "
        "(`golden`). An op with neither marker is forward-validated "
        "only (shape + finiteness).", ""]
    for name in sorted(OPS):
        fn = OPS[name]
        try:
            sig = str(inspect.signature(fn))
        except (ValueError, TypeError):
            sig = "(...)"
        doc = (inspect.getdoc(fn) or "").split("\n")[0].strip()
        entry = f"- **`{name}`**`{sig}`"
        tags = []
        if name in alias_of:
            tags.append(f"alias of `{alias_of[name]}`")
        cs = CASES.get(name, [])
        if any(c[2] for c in cs):
            tags.append("grad")
        if any(c[3] is not None for c in cs):
            tags.append("golden")
        if tags:
            entry += f" [{', '.join(tags)}]"
        if doc and not doc.startswith("lambda"):
            entry += f" — {doc}"
        op_lines.append(entry)
    op_lines += ["", TELEMETRY_OPS_SECTION.strip(),
                 "", RESILIENCE_OPS_SECTION.strip(),
                 "", NUMERICS_OPS_SECTION.strip(),
                 "", ELASTIC_OPS_SECTION.strip(),
                 "", FLEET_OPS_SECTION.strip(),
                 "", SERVING_OPS_SECTION.strip(),
                 "", SPEC_DECODE_OPS_SECTION.strip(),
                 "", SERVING_FLEET_OPS_SECTION.strip(),
                 "", DEVTIME_OPS_SECTION.strip(),
                 "", FUSED_OPS_SECTION.strip(),
                 "", COMM_OPS_SECTION.strip()]
    ops_out = os.path.join(os.path.dirname(out), "OPS.md")
    with open(ops_out, "w") as f:
        f.write("\n".join(op_lines) + "\n")
    print(f"wrote {os.path.normpath(ops_out)} ({len(OPS)} ops, "
          f"{n_grad} gradchecked, {n_gold} golden-checked)")


if __name__ == "__main__":
    main()
