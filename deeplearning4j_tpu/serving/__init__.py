"""Continuous-batching serving gateway (ARCHITECTURE.md §15).

The serving subsystem the north star's "heavy traffic from millions of
users" needs: in-flight batching for ``CausalTransformerLM.generate``
over a paged/block KV cache, behind a front end that keeps
``ParallelInference``'s shed/deadline/drain posture.

- :mod:`~deeplearning4j_tpu.serving.kv_pager` — fixed pool of
  block-token KV pages, per-sequence page table, free-list allocation,
  int8 page storage (the ``nn.decoder_infer.quant_kv`` codes);
- :mod:`~deeplearning4j_tpu.serving.scheduler` — ONE fixed-shape
  jitted decode step over every slot + per-bucket prefill-into-pages;
  zero retraces after ``warmup()``;
- :mod:`~deeplearning4j_tpu.serving.gateway` — ``submit()`` returning
  a streaming :class:`TokenStream`, admission control keyed on free
  pages, per-tenant round-robin fairness, graceful ``shutdown()``;
- :mod:`~deeplearning4j_tpu.serving.loadgen` — the open/closed-loop
  synthetic trace driver (``tools/serving_trace.py`` CLI; bench/
  dossier rows);
- :mod:`~deeplearning4j_tpu.serving.fleet` — the elastic fleet layer
  (ARCHITECTURE.md §20): leased replicas publishing serving telemetry,
  a health-steered :class:`ServingRouter`, and a capacity supervisor
  with compile-store-backed zero-cold-start respawn.
"""
from deeplearning4j_tpu.serving.fleet import (FleetSupervisor,
                                              ReplicaServer,
                                              RouterError,
                                              ServingReplica,
                                              ServingRouter,
                                              STARTUP_PREFETCH)
from deeplearning4j_tpu.serving.gateway import (SequenceAborted,
                                                ServingGateway,
                                                TokenStream)
from deeplearning4j_tpu.serving.kv_pager import KVPager, PageTableError
from deeplearning4j_tpu.serving.scheduler import DecodeScheduler

__all__ = ["ServingGateway", "TokenStream", "SequenceAborted",
           "KVPager", "PageTableError", "DecodeScheduler",
           "ServingReplica", "ServingRouter", "ReplicaServer",
           "FleetSupervisor", "RouterError", "STARTUP_PREFETCH"]
