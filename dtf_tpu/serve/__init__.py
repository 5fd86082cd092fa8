"""Online inference: continuous-batching decode over the GPT flagship.

The training side of the framework has carried every PR so far; this
package is the serving side the ROADMAP north star ("serves heavy traffic
from millions of users") actually asks for. Five layers:

- :mod:`dtf_tpu.serve.engine` — ``DecodeEngine``: KV cache + per-slot
  positions/rng/sampling-params as persistent sharded device state, with
  exactly TWO AOT-compiled fixed-shape programs (``prefill_into_slot``,
  ``decode_all``) — or exactly FOUR with speculative decoding armed
  (``prefill``, ``decode/verify``, ``draft_prefill``, ``draft_all``: a
  small draft model proposes k tokens per slot per tick, the verifier
  scores all k+1 positions in one masked pass, token streams identical
  to plain decode) — plus an optional prefix page pool with its own
  ``page_save``/``page_load`` pair. Zero steady-state recompiles by
  construction.
- :mod:`dtf_tpu.serve.pages` — the block-granular prefix KV cache:
  fixed-size pages with refcounts and LRU eviction, keyed by token-hash
  with exact-match verification, so shared prompt stems prefill once.
- :mod:`dtf_tpu.serve.scheduler` — request queue, FIFO admission with
  prefill/page-load/decode interleave, slot allocation, EOS/max-len
  eviction, and TTFT / per-token-latency / queue-depth / occupancy /
  SLO metrics.
- :mod:`dtf_tpu.serve.router` — ``Router``: N engine replicas (one shared
  param tree, independent KV state) behind least-occupancy admission with
  queue-depth tiebreak, ``router_wait`` spans and per-replica SLO
  rollups. With ``prefill_replicas=N`` the fleet DISAGGREGATES: dedicated
  prefill replicas absorb long-prompt work and hand the KV off through a
  shared page store (``PageStore`` — the pool as transport) to decode
  replicas, and admission routes by request phase instead of occupancy
  alone.
- :mod:`dtf_tpu.serve.client` — in-process submit/poll API plus a seeded
  Poisson load generator for benching.
- :mod:`dtf_tpu.serve.health` — the resilience tier (ISSUE 12): a
  per-replica health state machine (healthy → degraded → quarantined →
  probation) on the PR 11 stall-watchdog idiom, plus serve-side fault
  injection (``DTF_FAULT_INJECT=wedge_replica@... | slow_decode |
  poison_request``). Pairs with per-request deadlines, bounded-queue
  load shedding and quarantine requeue in scheduler/router.

Above the router sits the zero-downtime WEIGHT HOT-SWAP (ISSUE 14):
``Router.start_swap`` rolls newly published param versions
(:mod:`dtf_tpu.publish` — atomic versioned manifests) across the fleet
one drained replica at a time with a health-gated canary and automatic
fleet-wide rollback; completed records are stamped with the param
version that decoded them and prefix pages are version-epoch'd so
cached KV never crosses a swap.

docs/SERVING.md walks the architecture and the fixed-shape rules;
docs/RESILIENCE.md "Serving" + §9 walk the failure semantics.
"""

from dtf_tpu.serve.client import (Heartbeat, PoissonLoadGen, ServeClient,
                                  replay)
from dtf_tpu.serve.engine import (DecodeEngine, EngineStateLost,
                                  decode_step_view)
from dtf_tpu.serve.health import (HealthConfig, HealthTracker,
                                  install_serve_fault)
from dtf_tpu.serve.pages import PageStore, PrefixIndex
from dtf_tpu.serve.router import Router, SwapConfig
from dtf_tpu.serve.scheduler import (FAILED_STATUSES, Request,
                                     RequestFailed, Scheduler)

__all__ = ["DecodeEngine", "EngineStateLost", "FAILED_STATUSES", "Heartbeat",
           "HealthConfig", "HealthTracker", "PageStore", "PoissonLoadGen",
           "PrefixIndex", "Request", "RequestFailed", "Router", "Scheduler",
           "ServeClient", "SwapConfig", "decode_step_view",
           "install_serve_fault", "replay"]
