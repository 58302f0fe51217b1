"""paddle_tpu.serving — continuous-batching LLM serving on TPU with a
unified-step (chunked prefill) scheduler.

The production tail of the inference stack (the reference grew
paddle/fluid/inference the same way): a paged KV cache
(:mod:`kv_cache`), a continuous-batching scheduler (:mod:`engine`) over
the fused ragged paged-attention kernel
(kernels/paged_attention.py), and the serving facade over the
framework-wide metrics registry (:mod:`metrics` →
paddle_tpu.observability).  ``inference.Config.enable_generation()`` +
``create_predictor`` expose it through the predictor API; the cells
``serve-gpt3-1.3b-closed16`` and ``serve-minicpm-sala-8l-closed16-16k``
(``python3 benchmark/run.py --workload ...``) measure tokens/s and the gap
between tokens under a closed loop of 16 clients.

Unified-step scheduling (this replaced the prefill/decode phase split):
there is ONE jitted program, ``serving::unified_step``, and every
in-flight request advances through it each step as a ragged row
carrying (query_len, context_len).  A prompt is split into
``chunk_len``-token chunks that run as ordinary rows next to decode
rows, writing their K/V into the paged pool incrementally, so a long
prompt can never stall the decoding batch (head-of-line blocking) —
the worst decode stall is one chunk step.  ``chunk_len`` is the knob:
larger chunks finish a given prompt's prefill in fewer steps, smaller
chunks bound the per-step latency everyone else pays.  The first token
is sampled by the step in which the LAST chunk completes — that is the
TTFT event (``serving_ttft_seconds``), and each chunk increments
``serving_prefill_chunks_total``.

One step is kept in flight: ``Engine.step()`` dispatches a program while
the one before it runs and only then reads and commits that one, so the
host's scheduling hides behind the device.  A token is visible one
``step()`` call after the call that dispatched its program, a finish is
seen one call late (a stop token costs its row one dropped step more; a
length finish none), and ``has_work()`` is true until the last step is
committed (:mod:`engine`, "One step in flight").

Admission semantics: any prompt with prompt + max_new_tokens ≤
cfg.max_seq_len (and a page count the pool could ever hold) is
admissible — there is no prompt-length ceiling below that.  Pages are
allocated chunk-by-chunk: admission reserves
only the first chunk, later chunks extend the page table step by step,
and memory pressure preempts the youngest row — mid-prefill rows
included, whose already-written chunk pages are freed (likewise on
deadline eviction).

Overload behavior is part of the contract (README "Resilience"):
infeasible requests are REJECTED hard at submit; with watermarks
armed, feasible-but-unlucky ones get the soft RETRY_AFTER; requests
with a TTL are EVICTED (pages freed, partial output kept) the moment
a step starts past their deadline; the ``serving_engine_healthy``
gauge tells ops which regime the engine is in.

Drain-estimate contract: every RETRY_AFTER request carries
``Request.retry_after_s`` — a finite, strictly positive number of
seconds derived from the live backlog (queued + running decode tokens
still owed) divided by the engine's EWMA decode rate
(``Engine.estimated_drain_s()``).  Before the EWMA has its first real
sample the estimate never reports below the configurable
``drain_floor_s`` cold-start floor (default ``Engine.DRAIN_FLOOR_S``),
so a freshly (re)started replica is never advertised as instantly
drainable.  The same figure is published as the
``serving_estimated_drain_seconds`` gauge and on the telemetry server's
``/healthz`` (README "Flight recorder"), so front-ends and fleet
schedulers back off by measured drain time, not a guessed constant.
Every request is additionally traced
queued→chunk[i]→decode[i]→terminal through ``Engine.tracer``
(chrome-trace / JSON exportable).

Prefix-cache contract (:mod:`kv_cache` radix tree + refcounts — README
"Serving fleet"): with ``Engine(prefix_cache=True)`` (the default),
admission walks a radix tree keyed on page-aligned token-ID prefixes;
the longest cached prefix is mapped into the new request's page table
**read-only** (per-page refcount bump) and chunked prefill starts at
the first uncached token — a fully-cached prompt copy-on-writes only
its final page and re-runs exactly one token for logits.  A prompt's
FULL pages enter the tree when its prefill completes.  Semantics the
cache guarantees:

- **token-identical** — cached K/V is a pure function of the token
  prefix, so a cache-hit request's greedy output equals a cold prefill
  of the same prompt (parity-tested, mid-chunk hits and failover
  included).
- **mid-decode pages are never shared** — only full *prompt* pages are
  cached.  The partial final prompt page (and every decode page) keeps
  receiving writes from its owning sequence, so it never enters the
  tree; sharing it would let one request's decode corrupt another's
  context.
- **eviction vs shedding** — ``free()`` decrements, never force-frees:
  a page returns to the pool only at refcount zero.  Cached pages no
  sequence references are *evictable*: ``occupancy()`` counts them as
  free and allocation LRU-evicts them on demand, so a warm cache never
  trips the RETRY_AFTER watermarks — shedding fires on real memory
  pressure only, and deadline eviction of a request mid-prefill
  decrements its shared pages rather than corrupting its siblings.
- ``defrag()`` relocates a shared page once and rewrites every
  referencing page table plus its radix node.

Fleet-router contract (:mod:`router` — README "Serving fleet"): a
:class:`FleetRouter` over N replica engines is the fleet-level
robustness unit.  Semantics it guarantees:

- **drain-based, cache-aware balancing** — each admission goes to the
  admittable replica with the best ``estimated_drain_s −
  expected_prefix_hit_tokens × cache_hit_token_s`` score (queue depth
  + running count break ties): backlog self-levels across the fleet,
  and a request whose system prompt is already warm somewhere routes
  there unless that replica's backlog outweighs the prefill saved.
  Expected hits come from bounded radix summaries (hash-only, no token
  ids) each replica publishes — in-process pulls by default,
  :mod:`prefix_gossip` over TCPStore for cross-host fleets.  Gossip is
  advisory: the target re-walks its own tree at admission, so stale
  summaries cost FLOPs, never correctness.
- **bounded backpressure** — a replica's RETRY_AFTER closes its
  admission window for ``max(retry_after_s, jittered exponential
  delay)`` capped at ``backoff_cap_s`` (``resilience.retry``'s
  full-jitter generator); the window resets on the next successful
  dispatch.  The router never hammers a shedding replica and never
  abandons it either.
- **circuit breaker** — ``breaker_threshold`` failures (OSError from
  step/admit/probe, an admission stall over ``stall_timeout_s`` wall
  time, or ``probe_miss_threshold`` missed health probes) open the
  replica's breaker: out of rotation until restarted.
- **idempotent re-enqueue (zero loss)** — on failover or drain
  deadline, every in-flight request moves back to the router queue
  head *exactly once per event*, re-dispatched as an ordinary
  admission of ``prompt + harvested tokens``; KV state is rebuilt,
  never trusted, only completed-step tokens count as emitted, so
  greedy output is token-identical to an un-failed run and nothing is
  emitted twice.
- **rolling restarts** — ``drain(rid)`` stops admissions, lets decode
  finish within ``drain_deadline_s`` (stragglers re-dispatched), then
  rebuilds the engine from its factory and re-enters rotation.
- **fleet health fold** — ``/healthz`` (with the router attached to
  the telemetry server) is 503 only when NO replica can admit: all
  breakers open or draining.  One shedding replica is soft
  backpressure, not an outage, and the cascade breaker being open
  with admittable replicas left is likewise soft (the payload carries
  ``cascade_breaker_open``, ``quarantined`` and ``suspects``).

Blast-radius containment contract (:mod:`engine` + :mod:`router` —
README "Serving fleet"): failures are attributed to the narrowest
thing that caused them — a row, a request, a replica — and contained
there.  Semantics it guarantees:

- **per-row isolation (engine)** — a Python exception raised while
  planning or committing one specific row (packing its chunk, mapping
  its pages, sampling/committing its token) is pinned on that request:
  terminal ``RequestState.FAILED``, pages freed, trace closed with the
  error — the other rows in the batch and the engine itself sail on.
  Only failures not attributable to a row (the jitted step itself, the
  top-of-step fault site, OSError RPC edges) escalate to the router's
  replica-failure path.
- **suspicion by content (router)** — every request aboard a replica
  at the moment of an *uncontrolled* failure earns one suspicion
  point, keyed by prompt hash, per DISTINCT failure event: failover
  re-dispatches and re-submitted retries accumulate instead of
  resetting.  Finishing a run exonerates the prompt.
- **canary trial** — a request with ``canary_threshold`` (default 2)
  points is only ever dispatched ALONE, on an idle replica reserved
  for it (``canary_for``); no innocent is ever co-batched with a
  request on trial.  Killing the canary convicts it: terminal
  ``FleetRequestState.QUARANTINED`` with evidence attached (suspicion,
  failure-event ids, canary replica, error) — never re-dispatched.  A
  canary death is *controlled*: the replica restarts from its factory
  and is counted in ``router_canary_deaths_total``, not the failure
  window — which is what bounds a K-threshold poison storm at ≤ K+1
  uncontrolled replica kills.
- **cascade breaker (fleet)** — ``cascade_threshold`` uncontrolled
  failures inside ``cascade_window_s`` open the fleet breaker
  (``router_cascade_breaker_open`` = 1, a ``router::cascade`` span
  brackets the storm): every suspect with ≥ 1 point must pass a canary
  trial before normal dispatch resumes for it, and the attached
  autoscaler vetoes scale-up while the breaker is open (a poison storm
  is failure churn, not load — spawning would feed it fresh victims;
  zero-healthy recovery still scales).  The breaker closes when the
  window empties and no suspects remain queued or on trial.
- **innocents are never taxed** — a co-batched innocent rides the
  ordinary exactly-once failover: re-dispatch replays ``prompt +
  harvested tokens`` and sampling (on the device, keyed by seed and
  position: ``serving/sampling.py``) is batch-
  composition-independent, so its output stays token-identical to a
  poison-free run no matter how many neighbours get quarantined.

Autoscaler contract (:mod:`autoscaler` — README "Elastic fleet"): an
:class:`Autoscaler` attached to a router sizes the fleet from live
signals.  Semantics it guarantees:

- **signals** — each tick polls, on an injectable clock: every healthy
  replica's ``estimated_drain_s`` and queue depth, the router's
  pending depth, the shed/RETRY_AFTER delta since the last poll, and
  the goodput ratio (finished ÷ dispatched, telemetry).  They fold
  into one *pressure* figure: mean drain seconds per **ready** replica
  plus a pending-depth term.
- **warming is not capacity** — a replica whose decode EWMA has no
  real sample (``health()['decode_rate_tok_s'] is None``) still
  advertises ``drain_floor_s`` and is excluded from the ready count.
  ``Engine.warmup()`` preserves this: it compiles the unified step via
  one tiny request, then resets the EWMA, so a freshly scaled-up
  replica enters rotation warm-compiled but still on the cold-start
  floor until its first real decode step.
- **hysteresis + per-direction cooldowns** — up only when pressure is
  *strictly* above ``up_pressure_s`` (or pending strictly above
  ``up_pending_depth``, or any shed since the last poll); down only
  when pressure is *strictly* below ``down_pressure_s`` with zero
  pending/queued/shed and nothing draining.  Load exactly on a band
  boundary produces zero events, and each direction freezes for its
  own cooldown after acting — no flapping.
- **scale-up = supervised spawn** — revive the cheapest DEAD
  restartable replica, else append through the engine factory
  (``router.add_replica``); either way ``warmup()`` runs before
  rotation entry, and spawn attempts retry with jittered backoff out
  of a bounded budget (the supervisor discipline; the
  ``autoscaler.scale_up`` fault site injects the OSError this path
  must survive, ``autoscaler.poll`` the control-loop stall).
- **scale-down = cache-warmth-aware drain** — victim is the *coldest*
  healthy replica by gossiped radix summary (sum of cached prefix
  token depths = the prefill FLOPs its cache is worth; ties: fewest
  in-flight, then youngest), drained gracefully with
  ``router.drain(rid, restart=False)`` — stragglers re-dispatch
  exactly once, zero loss holds through every scale event.
- **observability** — ``autoscaler_scale_events_total{direction,
  reason}`` / ``autoscaler_target_replicas`` / ``autoscaler::scale``
  spans, and an ``autoscaler`` block folded into ``/fleet``.
- **SLO coupling** (both optional) — with a
  :class:`~paddle_tpu.observability.timeseries.TimeSeriesStore`
  attached (``timeseries=``), the shed and goodput signals become
  ``signal_window_s``-windowed, counter-reset-safe store deltas
  instead of tick-to-tick counter differences; with an
  :class:`~paddle_tpu.observability.slo.SLOEngine` attached
  (``slo=``), a firing fast-burn **page** escalates scale-up past the
  hysteresis band (reason ``slo_fast_burn`` — budget emptying at page
  speed IS demand, even before pressure catches up; cooldown,
  ``max_replicas`` and the cascade veto still bound it), and
  scale-down additionally requires a *healthy* budget: no alert
  active and every objective retaining at least
  ``slo_down_min_budget`` of its error budget.

Distributed-tracing contract (paddle_tpu.observability.tracing +
:mod:`router` — README "Distributed tracing"): every request carries
ONE globally unique ``trace_id`` from router admission to terminal
state, across processes and across failures.  Semantics it guarantees:

- **globally unique ids** — trace/span ids are prefixed with a
  per-process nonce (pid + random), so segments recorded by the
  router, by each replica engine, and by a restarted process never
  collide and can be merged by ``trace_id`` alone.
- **cross-process propagation** — the router serialises a
  ``TraceContext`` (trace_id + parent span_id) into every dispatch;
  ``Engine.add_request(..., trace_context=...)`` continues the trace
  as a child segment.  A failover re-dispatch reuses the ORIGINAL
  request's context, so a hard-killed request reads as one trace with
  both ``router::dispatch`` hops and the ``router::failover`` span on
  it — never two half-traces.
- **tail-based retention** — completed traces are kept by what
  happened on them (error, fault-injection event, flagged span,
  rejection/retry/eviction/failover, deadline, slow-tail), with a
  seeded coin-flip for the boring rest; the ring evicts boring-first,
  so a flood of healthy traffic cannot push out the one trace that
  shed or failed over.  Fired fault injections
  (:mod:`paddle_tpu.resilience.faults`) record (site, kind,
  occurrence, seed) on the ambient span, making a retained trace
  self-describing.
- **fleet collection** — each replica publishes its retained ring
  over the TCPStore plane (``TraceRingPublisher`` /
  ``collect_fleet_traces``); ``router.collect_traces()`` and the
  telemetry server's ``/traces?fleet=1`` merge segments by trace_id
  into one fleet-wide view, chrome-trace exportable.  Histogram
  exemplars (``serving_ttft_seconds`` et al.) link each latency
  bucket to a retained exemplar trace in the OpenMetrics exposition.

Soak exit criteria (:mod:`soak`; ``tests/test_soak.py`` runs the
compressed variant): replaying a seeded diurnal/bursty trace
(:mod:`traffic`) through the autoscaled fleet while the chaos timeline
fires hard kills, admission stalls, poll stalls, spawn I/O errors,
KV-page bitflips, and poison storms must end with ``lost_requests ==
0`` (quarantined/row-failed requests are *contained and accounted*,
not lost), bounded TTFT p99, at least one scale-up AND one scale-down
recorded in ``/fleet``, every poison request terminal ``QUARANTINED``
and visible on ``/fleet`` and the retained trace ring, and every chaos
event visible as a ``soak::*`` record in ``/flight``.
"""
from .engine import Engine, Request, RequestState, SamplingParams  # noqa: F401
from .kv_cache import PagedKVCache, prefix_hashes  # noqa: F401
from .model import GPTServed, HybridServed, as_served  # noqa: F401
from .prefix_gossip import (  # noqa: F401
    PrefixSummaryPublisher,
    collect_prefix_summaries,
)
from .metrics import (  # noqa: F401
    AutoscalerMetrics,
    Counter,
    Gauge,
    Histogram,
    RouterMetrics,
    ServingMetrics,
)
from .router import (  # noqa: F401
    FleetRequest,
    FleetRequestState,
    FleetRouter,
    Replica,
    ReplicaState,
)
from .autoscaler import Autoscaler  # noqa: F401
from .traffic import Arrival, TrafficGenerator  # noqa: F401
from .replica import ReplicaServer  # noqa: F401
from .soak import ChaosEvent, run_soak  # noqa: F401
