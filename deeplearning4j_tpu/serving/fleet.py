"""Elastic serving fleet — leased replicas, health-steered routing,
zero-cold-start autoscaling (ARCHITECTURE.md §20).

PR 8's gateway serves one process; this module turns N of them into
one fault-tolerant service by connecting three shipped planes:

- **Membership** — each replica takes a PR 6 file-plane lease
  (``resilience/elastic.MembershipCoordinator``); a dead replica's
  lease expires within one lease window and any peer (or the
  supervisor) evicts it.
- **Telemetry** — each replica publishes a ``serving`` section
  (readiness, queue depth, KV-page occupancy, warm buckets, port)
  through its PR 7 ``obs/fleet.FleetTelemetry`` snapshot; the
  :class:`ServingRouter` steers by exactly that published evidence,
  so the routing plane needs no side channel.
- **Compilation** — cold start dies by *startup prefetch*: a replica
  AOT-compiles every :data:`STARTUP_PREFETCH` bucket (the scheduler's
  ``WARMUP_FEEDS`` table) **before** taking its first lease, against
  the content-addressed ``perf/compile_store.py`` (fenced by jaxlib/
  topology, so a fresh process deserializes its siblings' compiles
  instead of rebuilding them).

Contracts the chaos drill (``tools/chaos.py --serving-fleet``) holds:

- the router admits only to live (lease evidence) AND ready
  (warmup-complete) replicas — never to a replica that would
  cold-trace on the request path;
- a dead replica's in-flight requests are re-routed first; a request
  that cannot be placed is *structurally shed* —
  ``SequenceAborted``, bounded by the shed budget
  (``DL4J_TPU_FLEET_SHED_BUDGET``) — never a hung client (every
  transport has a socket timeout, every wait a deadline);
- the supervisor respawns capacity on eviction, and the respawned
  replica's warm path rides the compile store (asserted via
  ``aot_hits`` + store/cache counters).

Host-side orchestration only: no jitted entry points live here (the
gateway owns those behind lint rule 7's sentry/warmup fence).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from deeplearning4j_tpu.obs import fleet as obs_fleet
from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.serving.gateway import SequenceAborted

#: the startup-prefetch table: every replica-facing builder the
#: scheduler declares MUST be reachable from here — lint rule 12 holds
#: this tuple equal to ``serving/scheduler.py``'s ``WARMUP_FEEDS``
#: keys, and holds ``ServingReplica.start``'s warmup call *before* its
#: first lease acquisition, so a replica can never advertise a lease
#: while a bucket is still cold
STARTUP_PREFETCH = (
    "_build_step_fn",
    "_build_admit_fn",
    "_build_chunk_admit_fn",
    "_build_spec_step_fn",
    "_build_suffix_admit_fn",
    "_build_cow_fn",
)


def _shed_budget_default() -> int:
    from deeplearning4j_tpu import environment
    return int(environment.get_flag("DL4J_TPU_FLEET_SHED_BUDGET"))


class RouterError(RuntimeError):
    """Transport-level failure talking to one replica (connection
    refused/reset, HTTP 5xx, socket timeout) — re-routable."""


# -- per-replica HTTP front end ----------------------------------------------

class ReplicaServer:
    """Stdlib HTTP front end for one gateway (the ``metrics.py``
    server pattern): ``POST /generate`` (JSON in, JSON out — 200
    complete, 409 structured abort, 429 queue-full shed, 503 not
    ready/shut down), ``GET /healthz`` (the readiness gate: 503 until
    warmup AOT-compiled every declared bucket), ``GET /stats``
    (gateway + AOT + compile-store counters, the drill's evidence)."""

    def __init__(self, gateway, port: int = 0, *,
                 store=None, request_timeout_s: float = 120.0):
        self.gateway = gateway
        self.port = int(port)
        self.store = store
        self.request_timeout_s = float(request_timeout_s)
        self._httpd = None
        self._thread = None
        self.sheds = 0              # 409/429 responses served

    # the drill's per-replica evidence: AOT hits prove prefetch warmed
    # the entry points, cache/store counters prove the compiles came
    # off the fleet store rather than a cold build
    def stats(self) -> Dict[str, Any]:
        from deeplearning4j_tpu.perf import compile_cache, sentry
        out = dict(self.gateway.stats())
        out["ready"] = self.gateway.ready()
        out["aot_hits"] = sum(
            int(s.get("aot_hits", 0)) for s in sentry.stats().values())
        out["cache"] = compile_cache.counters()
        out["store"] = (self.store.counters()
                        if self.store is not None else None)
        out["sheds"] = self.sheds
        warm = self.gateway.warm_report()
        out["warm_buckets"] = list(warm["buckets"]) if warm else []
        return out

    def start(self) -> "ReplicaServer":
        import http.server

        srv = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def _reply(self, code: int, obj: Dict[str, Any]):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/healthz":
                    ready = srv.gateway.ready()
                    self._reply(200 if ready else 503,
                                {"ready": ready,
                                 "status": "ok" if ready
                                 else "warming"})
                elif path == "/stats":
                    self._reply(200, srv.stats())
                else:
                    self._reply(404, {"error": "unknown path",
                                      "paths": ["/generate",
                                                "/healthz", "/stats"]})

            def do_POST(self):
                if self.path.split("?", 1)[0] != "/generate":
                    self._reply(404, {"error": "unknown path"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    self._reply(400, {"error": "bad json"})
                    return
                self._reply(*srv._generate(req))

            def log_message(self, *a):
                pass

        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="dl4j-replica-http")
        self._thread.start()
        return self

    def _generate(self, req: Dict[str, Any]):
        from deeplearning4j_tpu.parallel.inference import (
            DeadlineExpiredError, QueueFullError,
            ServingShutdownError)
        if not self.gateway.ready():
            # readiness ≠ liveness: a cold gateway refuses rather
            # than cold-tracing on the request path
            return 503, {"error": "not ready"}
        try:
            stream = self.gateway.submit(
                req.get("prompt") or [],
                max_new=req.get("max_new"),
                tenant=str(req.get("tenant", "default")),
                temperature=req.get("temperature"),
                deadline_s=req.get("deadline_s"))
            tokens = stream.result(timeout=self.request_timeout_s)
            return 200, {"tokens": [int(t) for t in tokens],
                         "n_prompt": int(stream.prompt.size),
                         "ttft_s": stream.ttft_s,
                         "rid": stream.rid}
        except SequenceAborted as e:
            # the structured-abort contract crosses the wire intact:
            # tokens-so-far + cause, never a dropped connection
            self.sheds += 1
            return 409, {"error": "aborted", "message": str(e),
                         "tokens": [int(t) for t in e.tokens],
                         "cause": repr(e.cause)}
        except QueueFullError as e:
            self.sheds += 1
            return 429, {"error": "queue_full", "message": str(e)}
        except DeadlineExpiredError as e:
            self.sheds += 1
            return 429, {"error": "deadline", "message": str(e)}
        except ServingShutdownError as e:
            return 503, {"error": "shutdown", "message": str(e)}
        except TimeoutError as e:
            self.sheds += 1
            return 409, {"error": "aborted", "message": str(e),
                         "tokens": [], "cause": repr(e)}
        except ValueError as e:
            return 400, {"error": "bad request", "message": str(e)}

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


# -- one replica's lifecycle --------------------------------------------------

class ServingReplica:
    """One gateway's fleet residency: startup prefetch → readiness →
    lease → publish loop. The ordering is the contract (lint rule 12
    checks it statically): warmup completes BEFORE the first lease
    renewal, so the instant a router can see this replica's lease it
    is already safe to route to."""

    def __init__(self, gateway, coordinator, telemetry, *,
                 store=None, server_port: int = 0,
                 agree_timeout_s: float = 5.0,
                 clock: Callable[[], float] = time.time):
        self.gateway = gateway
        self.coord = coordinator
        self.telemetry = telemetry
        self.store = store
        self.server: Optional[ReplicaServer] = None
        self.server_port = int(server_port)
        self.agree_timeout_s = float(agree_timeout_s)
        self.clock = clock
        self.host = coordinator.host
        self._probe_name = f"serving:{self.host}"

    def _fingerprint(self, prompt_lens) -> str:
        from deeplearning4j_tpu.perf import compile_store
        sched = self.gateway._sched
        return compile_store.program_fingerprint(
            buckets=sorted(int(b) for b in (prompt_lens or [])),
            block=int(getattr(sched, "block", 0)),
            max_slots=int(getattr(sched, "max_slots", 0)),
            n_pages=int(getattr(sched.pager, "n_pages", 0)),
            spec_k=int(getattr(sched, "spec_k", 1)),
            prefetch=list(STARTUP_PREFETCH))

    def start(self, prompt_lens=None) -> Dict[str, Any]:
        """Bring the replica up: prefetch-warm every bucket (compile
        store consulted first, manifest republished after), register
        the readiness probe, start the HTTP front end, and only THEN
        take the membership lease."""
        _faults.inject("replica_spawn")
        fingerprint = self._fingerprint(prompt_lens)
        manifest = None
        if self.store is not None:
            raw = self.store.get(fingerprint)
            if raw is not None:
                try:
                    manifest = json.loads(raw)
                except ValueError:
                    manifest = None
        # startup prefetch: every WARMUP_FEEDS bucket AOT-compiles
        # here — behind it, JAX's persistent cache (routed through the
        # store's fenced xla/ plane) turns sibling compiles into
        # deserialization, which is what kills the cold start
        report = self.gateway.warmup(prompt_lens)
        report = dict(report)
        report["fingerprint"] = fingerprint
        report["manifest_hit"] = manifest is not None
        if self.store is not None:
            self.store.put(fingerprint, json.dumps({
                "buckets": [int(b) for b in report.get("buckets", [])],
                "spec_k": report.get("spec_k"),
                "compiled": report.get("compiled"),
                "seconds": report.get("seconds"),
            }).encode())
        _metrics.FLEET_WARM_BUCKETS.set(
            len(report.get("buckets", [])))
        _metrics.register_readiness(self._probe_name,
                                    self.gateway.ready)
        self.server = ReplicaServer(self.gateway,
                                    port=self.server_port,
                                    store=self.store).start()
        # warm and serving — NOW advertise the lease
        self.coord.renew()
        self.coord.start_auto_renew()
        self.publish(force=True)
        return report

    def publish(self, force: bool = False) -> None:
        """Refresh the serving section of this host's telemetry
        snapshot — the router's only eligibility evidence."""
        stats = self.gateway.stats()
        pager = self.gateway._sched.pager
        usable = max(1, int(getattr(pager, "n_pages", 1)) - 1)
        occupancy = min(1.0, max(
            0.0, 1.0 - float(stats["free_pages"]) / usable))
        warm = self.gateway.warm_report()
        self.telemetry.update_serving(
            ready=self.gateway.ready() and self.server is not None,
            addr=(f"127.0.0.1:{self.server.port}"
                  if self.server is not None else None),
            queue_depth=int(stats["queued"]),
            active=int(stats["active"]),
            kv_pages_free=int(stats["free_pages"]),
            kv_page_occupancy=round(occupancy, 4),
            warm_buckets=(list(warm["buckets"]) if warm else []),
            sheds=(self.server.sheds if self.server is not None
                   else 0),
            tokens_out=int(stats["tokens_out"]))
        self.telemetry.publish(force=force)

    def tick(self) -> Dict[str, Any]:
        """One supervision heartbeat (call from the serve loop):
        evict expired peers, converge the membership epoch when the
        live set changed (the epoch flip the post-drill ``/fleet``
        exposition shows), republish serving telemetry."""
        now = self.clock()
        evicted = self.coord.evict_expired(now)
        for _ in evicted:
            _metrics.FLEET_EVICTIONS.inc()
        live = self.coord.live_members(now)
        rec = self.coord.epoch_record()
        if rec is None or sorted(rec.get("members", [])) != live:
            try:
                rec = self.coord.agree_membership(
                    timeout_s=self.agree_timeout_s)
                if int(rec["epoch"]) != self.telemetry.mesh_epoch:
                    self.telemetry.event(
                        "mesh_epoch_commit", epoch=int(rec["epoch"]),
                        members=list(rec["members"]))
            except TimeoutError:
                pass        # peers not all ticking yet — next tick
        self.publish()
        return {"evicted": evicted, "live": live,
                "epoch": self.telemetry.mesh_epoch}

    def stop(self, drain: bool = True) -> None:
        """Graceful departure: advertise not-ready, drop the lease
        (survivors evict immediately instead of waiting out the
        window), then drain the gateway and stop the front end."""
        _metrics.register_readiness(self._probe_name, None)
        try:
            self.telemetry.update_serving(ready=False)
            self.telemetry.publish(force=True)
        except Exception:
            pass
        self.coord.leave()
        if self.server is not None:
            self.server.stop()
            self.server = None
        self.gateway.shutdown(drain=drain)


# -- the front-end router -----------------------------------------------------

class HttpTransport:
    """Default wire: JSON over stdlib urllib with a hard socket
    timeout — a dead replica costs a bounded wait, never a hung
    client."""

    def __init__(self, timeout_s: float = 30.0):
        self.timeout_s = float(timeout_s)

    def generate(self, addr: str, payload: Dict[str, Any]
                 ) -> Dict[str, Any]:
        import urllib.error
        import urllib.request
        req = urllib.request.Request(
            f"http://{addr}/generate",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        try:
            with urllib.request.urlopen(
                    req, timeout=self.timeout_s) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                body = json.loads(e.read())
            except ValueError:
                body = {}
            if e.code == 409:
                # the replica's structured abort — salvaged tokens
                # and cause intact; the router decides shed/re-route
                raise SequenceAborted(
                    body.get("message", "aborted by replica"),
                    tokens=body.get("tokens"),
                    cause=body.get("cause"))
            raise RouterError(
                f"replica {addr} answered {e.code}: "
                f"{body.get('error', '')}")
        except (OSError, ValueError) as e:
            raise RouterError(f"replica {addr} unreachable: {e!r}")


class ServingRouter:
    """Health-steered front end over the fleet's telemetry plane.

    ``submit`` forwards to the least-loaded live+ready replica —
    load is the replica's *published* queue depth + active slots plus
    this router's own in-flight count against it (published telemetry
    refreshes once per tick, so without the local term every tie
    would break to the lexically first host and the rest of the fleet
    would idle); a transport failure re-routes (the replica set is
    re-read, so a replica whose lease lapsed disappears within one
    lease window); when no placement is possible before the deadline
    the request is structurally shed as :class:`SequenceAborted` —
    bounded by the shed budget, and never a hang (client-side
    timeouts end-to-end).
    """

    def __init__(self, directory, *,
                 shed_budget: Optional[int] = None,
                 transport=None,
                 request_timeout_s: float = 30.0,
                 retry_pause_s: float = 0.05,
                 clock: Callable[[], float] = time.time):
        self.dir = directory
        self.shed_budget = (shed_budget if shed_budget is not None
                            else _shed_budget_default())
        self.transport = (transport if transport is not None
                          else HttpTransport(request_timeout_s))
        self.retry_pause_s = float(retry_pause_s)
        self.clock = clock
        self.sheds = 0
        self.reroutes = 0
        self._lock = threading.Lock()
        self._inflight: Dict[str, int] = {}

    def replicas(self) -> Dict[str, Dict[str, Any]]:
        """Live+ready replicas from the telemetry plane (one
        aggregator read — the router holds no connection state)."""
        view = obs_fleet.aggregate(self.dir, now=self.clock())
        table = view.serving_table()
        ready = {h: row for h, row in table.items()
                 if row["ready"] and row["live"] and row.get("addr")}
        _metrics.ROUTER_READY.set(len(ready))
        return ready

    def _shed(self, reason: str, message: str,
              cause=None) -> SequenceAborted:
        with self._lock:
            self.sheds += 1
        _metrics.ROUTER_SHEDS.labels(reason=reason).inc()
        return SequenceAborted(message, cause=cause)

    def submit(self, prompt, *, max_new: Optional[int] = None,
               tenant: str = "default",
               temperature: Optional[float] = None,
               deadline_s: float = 30.0) -> Dict[str, Any]:
        """Place one request; returns the replica's JSON result with
        ``replica`` added. Raises :class:`SequenceAborted` (and only
        that) on structural loss."""
        _faults.inject("router")
        payload = {"prompt": [int(t) for t in prompt],
                   "max_new": max_new, "tenant": tenant,
                   "temperature": temperature}
        deadline = self.clock() + float(deadline_s)
        tried: set = set()
        last_err: Optional[Exception] = None
        while True:
            reps = self.replicas()
            with self._lock:
                inflight = dict(self._inflight)
            cands = sorted(
                (int(row.get("queue_depth") or 0)
                 + int(row.get("active") or 0)
                 + inflight.get(h, 0), h)
                for h, row in reps.items() if h not in tried)
            if not cands:
                if self.clock() >= deadline:
                    break
                # every known replica failed this attempt — the set
                # may be re-forming (eviction + respawn mid-flight):
                # re-read it after a pause rather than aborting early
                tried.clear()
                time.sleep(self.retry_pause_s)
                continue
            host = cands[0][1]
            _metrics.ROUTER_REQS.labels(replica=host).inc()
            with self._lock:
                self._inflight[host] = \
                    self._inflight.get(host, 0) + 1
            try:
                out = self.transport.generate(reps[host]["addr"],
                                              payload)
                out["replica"] = host
                return out
            except SequenceAborted as e:
                # the replica itself shed mid-stream (fault path):
                # structural loss, surfaced — not silently retried
                # past the budget's accounting
                raise self._shed("replica_abort", str(e),
                                 cause=e) from e
            except RouterError as e:
                tried.add(host)
                last_err = e
                with self._lock:
                    self.reroutes += 1
                _metrics.ROUTER_REROUTES.inc()
            finally:
                with self._lock:
                    n = self._inflight.get(host, 1) - 1
                    if n > 0:
                        self._inflight[host] = n
                    else:
                        self._inflight.pop(host, None)
        if self.sheds >= self.shed_budget:
            # over budget: this abort still surfaces (never a hang),
            # but reason="over_budget" marks the contract breach the
            # drill asserts never happens within one eviction
            raise self._shed(
                "over_budget",
                f"no routable replica before deadline and shed "
                f"budget {self.shed_budget} exhausted", cause=last_err)
        raise self._shed(
            "no_replica",
            "no live+ready replica accepted the request before the "
            "deadline", cause=last_err)


# -- the supervisor -----------------------------------------------------------

class FleetSupervisor:
    """Capacity keeper: evicts expired leases and respawns replicas
    until the live count reaches ``target``. ``spawn_fn() -> host_id``
    is the deployment's own bring-up (subprocess, k8s pod, ...) — the
    supervisor only decides *when*; a spawn is pending (not double-
    spawned) until its lease appears."""

    def __init__(self, coordinator, spawn_fn: Callable[[], str], *,
                 target: int,
                 clock: Callable[[], float] = time.time):
        self.coord = coordinator
        self.spawn_fn = spawn_fn
        self.target = int(target)
        self.clock = clock
        self._pending: set = set()

    def poll(self) -> Dict[str, Any]:
        now = self.clock()
        evicted = self.coord.evict_expired(now)
        for _ in evicted:
            _metrics.FLEET_EVICTIONS.inc()
        live = self.coord.live_members(now)
        self._pending -= set(live)
        self._pending -= set(evicted)
        spawned: List[str] = []
        while len(live) + len(self._pending) + len(spawned) \
                < self.target:
            _faults.inject("replica_spawn")
            host = self.spawn_fn()
            _metrics.FLEET_SPAWNS.inc()
            spawned.append(str(host))
        self._pending.update(spawned)
        return {"evicted": evicted, "live": live, "spawned": spawned,
                "pending": sorted(self._pending)}


__all__ = ["STARTUP_PREFETCH", "ReplicaServer", "ServingReplica",
           "ServingRouter", "FleetSupervisor", "HttpTransport",
           "RouterError"]
