"""Continuous-batching generation engine — unified-step scheduler.

The serving-side counterpart of the training HybridEngine: requests
enter a FIFO admission queue and ONE statically-shaped jitted program
(``serving::unified_step``, compiles exactly once) advances every
in-flight request each step — whether the request is mid-prefill or
decoding.  There is no prefill phase: a prompt is split into
bounded-size *chunks* (``chunk_len`` tokens) that run as ordinary rows
of the ragged batch next to decode rows, so one long prompt can never
stall the decoding requests sharing the batch (the head-of-line
blocking the old prefill/decode phase split suffered from — "Ragged
Paged Attention", arXiv:2604.15464).

Per ``step()``:
  1. evict — drop every request (running or queued) past its deadline.
  2. admit — pop the queue head while a batch slot AND pages for its
     *first chunk* exist (pages are allocated chunk-by-chunk, not for
     the whole prompt upfront).
  3. unified step — plan the ragged batch under ``token_budget`` packed
     query tokens: every decode row gets its one token, then
     mid-prefill rows split the remaining budget fairly (a newly
     admitted short prompt is not starved behind a long one).  Chunk
     K/V is written into the paged pool incrementally; the row whose
     chunk completes its prompt samples the first token (TTFT), decode
     rows sample their next token.  The program is dispatched and left
     running.
  4. settle the step before — wait for the program the *previous* call
     dispatched, read its ids and commit them (tokens, spans, finishes).
  5. gauges — page-pool occupancy into the metrics registry.

One step in flight.  ``step()`` call k plans, packs and dispatches
program k while program k-1 runs on the device, and only then waits for
k-1: the host's phases hide behind the chip.  The scheduler therefore
plans from what it has *scheduled* (``Request.prompt_pos`` and
``Request._pending`` advance at dispatch), not from what is committed, and
a decode row whose newest token is still on the device is packed with a
marker the program resolves from the previous step's ids
(``models/ragged.py``).  A finish the host can foresee (``max_new_tokens``,
``max_seq_len``) costs nothing: the row is not scheduled past its last
token.  One it cannot (a stop token, seen at commit when the row already
rides in the next program) is over-run by exactly one step, whose result
for that row is dropped; the position it wrote lies in pages freed with
the request, and the device runs programs in order, so a later tenant's
writes land after it.  A token is visible one ``step()`` call after the
call that dispatched its program, and ``has_work()`` stays true until the
last one is committed.  What has to see committed state settles the step
in flight first (``_drain``): a preemption for memory, ``evacuate()``, an
armed ``serving.step`` fault site.  With nothing in flight (the first
step, the step after a drain) the same code runs in the plain order; depth
is one and there is no switch.

Admission control: requests that can NEVER fit (prompt + max_new_tokens
over the model's max_seq_len, or more pages than the whole pool) are
rejected at submit with Request.state == REJECTED — the engine's
graceful-overload contract.  Any prompt up to that bound is admissible:
it is chunked, whatever its length.
Requests that merely can't fit *now* stay queued.  If a sequence
outgrows the pool mid-flight (admission is optimistic), the youngest
running sequence — mid-prefill or decoding — is preempted back to the
queue head and recomputed later — memory pressure degrades throughput,
never correctness.

Overload robustness (the production-traffic contract):

- **load shedding** — with watermarks configured, crossing the HIGH
  page-occupancy or queue-depth mark flips the engine to *degraded*:
  new submissions return ``RequestState.RETRY_AFTER`` (a soft "come
  back later", distinct from the hard ``REJECTED`` of an infeasible
  request) until occupancy/queue fall below the LOW marks (hysteresis,
  so the admit/shed decision doesn't flap per token).  The
  ``serving_engine_healthy`` gauge mirrors the state for ops.
- **deadlines** — a request with a TTL (``SamplingParams.ttl_s`` or
  the engine's ``default_ttl_s``) is EVICTED the moment a step starts
  past its deadline — mid-decode or still queued — freeing its pages
  for requests that can still meet theirs.  A request nobody is
  waiting for anymore is pure waste to keep decoding.
- **retry-after hint** — a shed request carries ``retry_after_s``:
  the engine's ``estimated_drain_s`` (outstanding decode tokens ÷ the
  EWMA decode rate), so a cooperating front-end backs off for exactly
  as long as the backlog needs instead of hammering a bare
  RETRY_AFTER.  The same figure is published on ``/healthz`` and the
  ``serving_estimated_drain_seconds`` gauge.

Flight recorder: every request is traced — a root span per request
(one chrome-trace track), with ``queued`` / ``chunk[i]`` /
``decode[i]`` child spans carrying batch-slot and page-pool-occupancy
attributes, through terminal states finished / evicted / shed.  The
engine shares the process-wide tracer by default; with an injected
``clock`` it gets a private Tracer on that clock so tests drive span
timestamps deterministically.

Prefix reuse (``prefix_cache=True``, the default for a model without
recurrent layers, and refused for one with them): admission walks the
page pool's radix tree for the longest cached page-aligned prefix of
the prompt, maps those pages in read-only (a refcount bump instead of
prefill FLOPs) and starts chunked prefill at the first uncached token —
mid-chunk starts are fine, the planner just sees a shorter remaining
prompt.  A prompt whose prefill completes inserts its full pages back
into the tree.  K/V is a pure function of the token prefix, so a
cache-hit request's greedy output is token-identical to a cold prefill
of the same prompt (parity-tested).  Zero-ref cached pages are counted
as free for watermark/occupancy purposes and LRU-evicted on demand, so
a warm cache never sheds traffic it could serve.

Window layers (a model whose ``window`` is set: sliding-window attention
layers beside full ones).  Their pools are a second group with a page table
and a budget of their own (``serving/kv_cache.py``, kind
``"window_pages"``).  Before a row's pages are extended for a step, the
window pages whose every position lies more than ``window`` behind the
first token the step runs for that row are given back, so a row holds at
most ``ceil((window + chunk_len) / page_size) + 1`` of them
(``kv_cache.window_pages_per_row``) however long it grows; admission and
the per-step extension need both pools to cover the row (a row that either
cannot cover waits, or preempts, as before).  A page
given back while the step before is still in flight is safe to hand to
another row: the device runs the programs in order, and the step that
writes it is the later one.  Such a model is served cold (``prefix_cache``
off): a cached prefix's window pages are gone once its sequence moved on.

Sampling is on the device (``serving/sampling.py``): the jitted step
ends by choosing each row's token (greedy / temperature / top-k / top-p)
and the host reads ``[B]`` ids, never the ``[B, V]`` logits.  A draw is
keyed by the request's seed and the sampled token's position, so outputs
are deterministic for a fixed seed regardless of batch composition,
preemption or re-dispatch.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from ..models.ragged import (
    batch_shapes,
    empty_batch,
    pending_token,
    resolve_pending,
)
from ..observability.compile_watchdog import watch
from ..observability.profiling import pop_phase, push_phase
from ..observability.tracing import Tracer, default_tracer
from ..profiler.profiler import RecordEvent
from ..resilience.faults import fault_armed, fault_point
from .kv_cache import PagedKVCache, window_pages_per_row
from .metrics import STEP_PHASES, ServingMetrics
from .model import as_served
from .sampling import GREEDY, sample_tokens, slot_entry

__all__ = ["SamplingParams", "Request", "RequestState", "Engine"]


class RequestState:
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    REJECTED = "rejected"      # hard: can never be served (infeasible)
    RETRY_AFTER = "retry_after"  # soft: shed under load, resubmit later
    EVICTED = "evicted"        # deadline/TTL passed before completion
    EVACUATED = "evacuated"    # pulled off a failed/draining replica; the
    #                            fleet router re-enqueues it elsewhere
    FAILED = "failed"          # a row-attributable exception: THIS request
    #                            broke, its pages are freed, the engine
    #                            (and every co-batched request) lives on


@dataclasses.dataclass
class SamplingParams:
    """temperature == 0 is greedy (argmax); top_k/top_p only apply when
    sampling.  The token is chosen on the device, inside the serving step
    (``serving/sampling.py``).  ``seed`` names a stream of draws keyed by
    (seed, position of the sampled token): the same seed gives the same
    tokens whoever shares the batch, through a preemption, and when the
    request is moved to another engine with its output so far as prompt;
    any Python int, of which 64 bits are used.  stop_token_ids end
    generation (the stop token is kept in the output, reason "stop");
    max_new_tokens caps it (reason "length").  ttl_s bounds submit→finish
    wall time: past it the request is evicted (reason "deadline") even
    mid-decode."""
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    stop_token_ids: tuple = ()
    ttl_s: float = None


@dataclasses.dataclass
class Request:
    id: int
    prompt: list
    sampling: SamplingParams
    state: str = RequestState.QUEUED
    tokens: list = dataclasses.field(default_factory=list)  # prompt + output
    finish_reason: str = None
    t_submit: float = 0.0
    t_admitted: float = None
    t_first_token: float = None
    t_finished: float = None
    deadline: float = None     # absolute engine-clock time, None = no TTL
    retry_after_s: float = None  # drain-estimate hint on RETRY_AFTER
    prompt_pos: int = 0        # prompt tokens scheduled into pages (by a
    #                            dispatched step, committed or in flight)
    _pending: int = 0          # tokens a dispatched step chooses for this
    #                            request that are not in ``tokens`` yet
    _chunks_done: int = 0      # prefill chunks completed (span index)
    _span: object = None       # root trace span (one per request)
    _phase: object = None      # current lifecycle child span

    @property
    def output(self):
        return self.tokens[len(self.prompt):]

    def _reset_for_recompute(self):
        """Preemption rewinds to the prompt — including mid-prefill
        chunk progress; a draw is keyed by (seed, position), so the
        recomputation replays the exact same draws and a preempted
        request's final output is identical to its uninterrupted one."""
        self.tokens = list(self.prompt)
        self.prompt_pos = 0
        self._pending = 0
        self._chunks_done = 0
        self.state = RequestState.QUEUED


@dataclasses.dataclass
class _InFlight:
    """A dispatched step program whose ids the host has not read."""
    sched: list                # (slot, req, q, new ctx) of every packed row
    ids: object                # [B] int32 on the device, on its way to the host
    logits: object             # [B, V] on the device
    t0: float                  # engine clock at dispatch
    kind: str                  # "prefill_chunk" | "decode": the sampler's tag


class _StepPhases:
    """One ``Engine.step()`` call cut into ``STEP_PHASES``.

    ``with phases.phase(name):`` is the one way the engine opens a
    phase.  It feeds the three sinks the repo already has: a
    ``RecordEvent`` named ``serving::step/<name>`` (the profiler's ring
    while a ``Profiler`` records, and a ``jax.profiler`` trace whenever
    one runs), the ``serving_step_phase_seconds{phase}`` histogram, and
    — with ``tag`` — the ``StackSampler``'s phase marker.  Time is
    ``RecordEvent``'s (``time.perf_counter_ns``), never the engine's
    injectable clock.  Phases follow one another and never nest, so the
    object is its own context manager; a phase entered twice in a call
    (a drain before the call's own wait) adds up.  ``close()`` observes
    every phase exactly once, 0 for one the call did not reach, so the
    n-th sample of every phase is the n-th call's.  ``pack`` and
    ``dispatch`` are of the program the call sends, ``device_wait``,
    ``fetch``, ``sample`` and ``commit`` of the one sent before it.
    """

    _NAMES = {p: f"serving::step/{p}" for p in STEP_PHASES}

    def __init__(self, series):
        self._series = series
        self._ns = dict.fromkeys(STEP_PHASES, 0)
        self._name = self._event = self._tag = None

    def phase(self, name, tag=None):
        self._name, self._tag = name, tag
        return self

    def __enter__(self):
        if self._tag is not None:
            push_phase(self._tag)
        self._event = RecordEvent(self._NAMES[self._name])
        self._event.begin()

    def __exit__(self, *exc):
        self._event.end()
        self._ns[self._name] += self._event.elapsed_ns
        if self._tag is not None:
            pop_phase()
        return False

    def close(self):
        for name, ns in self._ns.items():
            self._series[name].observe(ns * 1e-9)


class Engine:
    """Continuous-batching generation over a paged KV cache with a
    unified (chunked-prefill) step scheduler.

    model/params: what is served, as an object with the interface of
    ``serving/model.py`` — its ragged step and the state that step
    carries — or a config object that file knows how to wrap (a
    ``GPTConfig``, a ``HybridConfig``); params default to the model's own
    initializer (useful for benches and tests).  The engine names no
    model: scheduler, phases, metrics and allocator are the same for all.
    page_size/num_pages size the page pools;
    max_batch_size fixes the in-flight row count (static shape).
    ``chunk_len`` bounds the prompt tokens any single row contributes
    per step — the knob that trades TTFT of the chunked prompt against
    the stall it imposes on everyone else; it does not cap the
    admissible prompt length.
    ``token_budget`` is the packed query-token width of the one
    compiled step (default chunk_len + max_batch_size - 1: one full
    chunk plus a decode token for every other row).

    Robustness knobs: ``default_ttl_s`` is the per-request deadline when
    SamplingParams doesn't set one.  ``shed_occupancy_high/low`` (pool
    fraction, 0..1) and ``shed_queue_high/low`` (queue depth) arm
    watermark load shedding; lows default to 3/4 of their high.
    ``drain_floor_s`` is the cold-start floor on the drain estimate:
    until the decode-rate EWMA has its first real sample the engine
    cannot know how fast it drains, so ``estimated_drain_s()`` (and
    the ``retry_after_s`` hint built on it) never reports below this
    floor — a freshly (re)started replica advertises "give me a
    moment" instead of a useless 0 that would invite the whole fleet's
    backlog at once.  Once a decode step has measured the real rate
    the floor no longer applies.
    ``clock`` replaces time.perf_counter (tests drive a manual clock so
    deadline behavior is deterministic, not sleep-based).  ``tracer``
    overrides the flight recorder; by default the engine records into
    the process-wide tracer, or — when a custom ``clock`` is injected —
    into a private Tracer on that clock (so manual-clock tests get
    deterministic span timestamps without touching global state).
    """

    #: assumed decode throughput (tok/s) until the first decode step has
    #: measured the real EWMA rate — only ever used for the drain
    #: estimate of a request shed before any decoding happened
    ASSUMED_DECODE_RATE = 100.0

    #: default cold-start floor (seconds) on the drain estimate while
    #: the decode-rate EWMA has no sample yet
    DRAIN_FLOOR_S = 0.5

    def __init__(self, model, params=None, *, page_size=16,
                 num_pages=256, max_batch_size=4, chunk_len=None,
                 token_budget=None, default_ttl_s=None,
                 shed_occupancy_high=None, shed_occupancy_low=None,
                 shed_queue_high=None, shed_queue_low=None,
                 drain_floor_s=None, prefix_cache=None, clock=None,
                 tracer=None, mesh=None, num_window_pages=None):
        self.model = model = as_served(model)
        self.cfg = cfg = model.cfg
        #: positions a sliding-window layer reads (None: no such layers)
        self.window = getattr(model, "window", None)
        if prefix_cache and self.window:
            raise ValueError(
                "prefix_cache=True with a model that has window layers: a "
                "cached prefix's window pages were given back as its "
                "sequence moved past them, so it cannot be resumed.  Such "
                "a model is served cold (prefix_cache=False, the default "
                "for it)")
        if prefix_cache and model.recurrent:
            raise ValueError(
                "prefix_cache=True with a model that has recurrent layers: "
                "a cached prefix is pages of keys and values, and the "
                "row's recurrent state never saw the tokens behind them. "
                "Such a model is served cold (prefix_cache=False, the "
                "default for it) until a prefix can carry a state snapshot")
        if prefix_cache is None:
            prefix_cache = not model.recurrent and not self.window
        self._clock = clock or time.perf_counter
        if tracer is None:
            tracer = (default_tracer() if clock is None
                      else Tracer(clock=self._clock))
        self.tracer = tracer
        self._decode_rate_ewma = None     # tok/s, None until first decode
        self._ewma_alpha = 0.25
        self._t_settled = float("-inf")   # clock when a step's ids were last read
        self.default_ttl_s = default_ttl_s
        self.drain_floor_s = (self.DRAIN_FLOOR_S if drain_floor_s is None
                              else float(drain_floor_s))
        self.shed_occupancy_high = shed_occupancy_high
        self.shed_occupancy_low = (
            shed_occupancy_low if shed_occupancy_low is not None
            else (None if shed_occupancy_high is None
                  else 0.75 * shed_occupancy_high))
        self.shed_queue_high = shed_queue_high
        self.shed_queue_low = (
            shed_queue_low if shed_queue_low is not None
            else (None if shed_queue_high is None
                  else max(0, int(0.75 * shed_queue_high))))
        self._shedding = False
        self.params = params if params is not None else model.init_params()
        self.page_size = page_size
        self.max_batch_size = max_batch_size
        # prompts of ANY admissible length are chunked through it
        self.chunk_len = max(1, min(chunk_len or 64, cfg.max_seq_len))
        self.token_budget = max(
            token_budget or (self.chunk_len + max_batch_size - 1),
            max_batch_size)
        sizes = {"num_pages": num_pages, "page_size": page_size,
                 "max_batch_size": max_batch_size}
        if self.window:
            # the most one row holds, for every row: no row ever waits
            # for a window page unless the caller sized the pool below it
            per_row = min(window_pages_per_row(self.window, page_size,
                                               self.chunk_len),
                          -(-cfg.max_seq_len // page_size))
            sizes["num_window_pages"] = (num_window_pages
                                         or max_batch_size * per_row)
            if sizes["num_window_pages"] < per_row:
                raise ValueError(
                    f"num_window_pages {num_window_pages}: one row's "
                    f"window and chunk take {per_row} pages")
        self.cache = PagedKVCache(
            num_pages=num_pages, page_size=page_size,
            max_seq_len=cfg.max_seq_len, state=model.state_spec(**sizes))
        # the static sizes of the step's ragged batch: rows, packed query
        # tokens, width of a row's page table
        self.batch_dims = (max_batch_size, self.token_budget,
                           self.cache.max_pages_per_seq)
        self._window_tables = self.cache.num_window_pages > 0
        self._window_released_seen = 0
        # prefix/radix reuse: admission walks the radix tree so a shared
        # system prompt is a refcount bump instead of prefill FLOPs;
        # completed prompts are inserted back.  Off = always-cold
        # admission.
        self.prefix_cache = bool(prefix_cache)
        self._prefix_seen = {"hits": 0, "hit_tokens": 0, "evictions": 0}
        self.metrics = ServingMetrics()
        self.metrics.recurrent_state_bytes.set(
            self.cache.recurrent_state_bytes())
        self._queue = deque()
        self._slots = [None] * max_batch_size
        self._just_finished = []
        self._inflight = None               # the dispatched, unread step
        self._admit_seq = 0                 # admission order, for preemption
        self._next_id = 0
        # donation chains every state pool through steps; XLA:CPU can't
        # donate and warns, so only donate on accelerators
        n_state = len(self.cache.arrays)
        donate = tuple(range(1, 1 + n_state)) \
            if jax.default_backend() != "cpu" else ()
        model_step = model.make_step(max_q=self.chunk_len, mesh=mesh)
        # numbers the model's step counts on the device (int32 [n], after
        # its logits and state): they ride to the host behind the ids, in
        # the one array the host already reads
        n_stats = len(getattr(model, "step_stats", ()))

        # the rows' sampling parameters by batch slot (sampling.py): the
        # host's copy, and the device's, sent again only after admission
        # put a tenant with another entry into a slot
        self._slot_sampling = [GREEDY] * max_batch_size
        self._sampling_table = None
        #: the ``logits [B, V]`` of the step whose ids were read last, as
        #: the program left them on the device: while ``_sample_token``
        #: runs for a row, the logits that row's id was chosen from.  For
        #: a test or a debugging caller; ``step()`` never reads them
        self.step_logits = None

        def _step(params, *args):
            # (params, every state pool, the ragged batch, the sampling
            # table, the ids the step before chose): the pools flat, so
            # that they are donated one by one.  A token the host has not
            # seen is named in the batch and filled in here: the model's
            # step sees ids only
            *state, batch, sampling, prev_ids = args
            with jax.named_scope("pending"):
                if n_stats:
                    prev_ids = prev_ids[:max_batch_size]
                batch = resolve_pending(batch, prev_ids)
            logits, state, *stats = model_step(params, tuple(state), batch)
            with jax.named_scope("sample"):
                ids = sample_tokens(logits, sampling, batch.query_lens,
                                    batch.context_lens)
                if n_stats:
                    ids = jnp.concatenate([ids, stats[0].astype(ids.dtype)])
            return (ids, logits, *state)

        # GSPMD serving (prepare(mesh=...) analogue): the model shards its
        # params and names the page pools' spec (the dense family: the
        # mesh.py GPT rule table, and the pools [L, P, ps, H, hd] on their
        # HEAD axis along "mp") — each model-parallel shard owns its head
        # group's pages, so page writes are local and the only
        # cross-shard traffic is the per-layer psum GSPMD inserts at the
        # residual write plus ONE logits gather per step (the rows are
        # sampled from the gathered logits; out_shardings pins the ids and
        # the logits replicated, and the ids come back in as the next
        # step's ``prev_ids`` the same way; pages stay sharded
        # end-to-end, never gathered).
        self.mesh = mesh
        self._page_sharding = self._replicated = None
        jit_kw = {"donate_argnums": donate}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..distributed import mesh as mesh_mod

            self.params, p_sh, page_spec = model.shard(self.params, mesh)
            psh = NamedSharding(mesh, mesh_mod.resolve_spec(
                page_spec, self.cache.k_pages.shape, mesh))
            for name, a in self.cache.arrays.items():
                self.cache.arrays[name] = jax.device_put(a, psh)
            self._page_sharding = psh
            self._replicated = rep = NamedSharding(mesh, P())
            jit_kw.update(
                in_shardings=(p_sh,) + (psh,) * n_state + (rep,) * 3,
                out_shardings=(rep, rep) + (psh,) * n_state)
        # the ids the newest dispatched step chose, still on the device:
        # the next step's last operand
        ids = np.zeros((max_batch_size + n_stats,), np.int32)
        self._prev_ids = (jnp.asarray(ids) if self._replicated is None
                          else jax.device_put(ids, self._replicated))
        # watchdog-wrapped: the ONE statically-shaped program — prompt
        # chunks and decode rows share it — must compile exactly once;
        # any recompile here is a serving bug the watchdog flags with
        # the offending shape diff.  Described by its shapes alone (no
        # lowering, no compile here), so that the watchdog can say what
        # the compiled step is made of after this engine is gone
        # (``instruction_table``)
        self._step_fn = watch(
            jax.jit(_step, **jit_kw), name="serving::unified_step",
            abstract_args=self.step_args(sharding=self._replicated))

    # ------------------------------------------------------------- submit
    def add_request(self, prompt, sampling: SamplingParams = None, *,
                    trace_context=None):
        """Queue a prompt (list of token ids).  Returns the Request;
        state is REJECTED immediately when it can never be served, and
        a shed request carries ``retry_after_s`` (the live drain
        estimate) next to its RETRY_AFTER state.  ``trace_context`` (a
        :class:`~..observability.tracing.TraceContext` or its dict form)
        continues a caller's trace — the router hands its dispatch
        span's context over, so the request's whole engine lifecycle
        records under the fleet trace instead of a fresh local one."""
        # fault site: a stall here is an admission wedge (the RPC thread
        # of a real deployment hanging in submit); an io_error is the
        # transport refusing the request.  The fleet router detects both.
        fault_point("serving.admit")
        sampling = sampling or SamplingParams()
        req = Request(id=self._next_id, prompt=list(prompt),
                      sampling=sampling, t_submit=self._clock())
        self._next_id += 1
        req.tokens = list(req.prompt)
        ttl = sampling.ttl_s if sampling.ttl_s is not None \
            else self.default_ttl_s
        if ttl is not None:
            req.deadline = req.t_submit + float(ttl)
        self.metrics.requests_submitted.inc()
        req._span = self.tracer.start_trace(
            f"request#{req.id}", start_s=req.t_submit,
            attributes={"request_id": req.id,
                        "prompt_len": len(req.prompt),
                        "max_new_tokens": sampling.max_new_tokens},
            context=trace_context)

        # chunked prefill admits any prompt the model itself can hold —
        # there is deliberately NO prompt-length gate below max_seq_len
        total = len(req.prompt) + sampling.max_new_tokens
        reason = None
        if not req.prompt:
            reason = "empty prompt"
        elif total > self.cfg.max_seq_len:
            reason = (f"prompt + max_new_tokens = {total} exceeds "
                      f"max_seq_len {self.cfg.max_seq_len}")
        elif self.cache.pages_for(total) > self.cache.num_pages:
            reason = (f"{total} tokens need "
                      f"{self.cache.pages_for(total)} pages; the pool has "
                      f"{self.cache.num_pages} — page pool exhausted")
        if reason is not None:
            req.state = RequestState.REJECTED
            req.finish_reason = reason
            self.metrics.requests_rejected.inc()
            self._end_trace(req)
            return req
        if self._update_shedding():
            # soft rejection: the request IS feasible, the engine is
            # just saturated — back off ~retry_after_s and resubmit
            req.state = RequestState.RETRY_AFTER
            req.retry_after_s = self._retry_after()
            req.finish_reason = (
                f"load shed: occupancy {self.cache.occupancy():.2f}, "
                f"queue depth {len(self._queue)} — retry in "
                f"{req.retry_after_s:.3f}s")
            self.metrics.requests_shed.inc()
            self.metrics.estimated_drain_s.set(req.retry_after_s)
            self._end_trace(req)
            return req
        self._queue.append(req)
        req._phase = self.tracer.start_span("queued", req._span,
                                            start_s=req.t_submit)
        self.metrics.queue_depth.set(len(self._queue))
        self._update_shedding()
        return req

    # ----------------------------------------------------- flight recorder
    def _end_phase(self, req, end_s=None, **attrs):
        if req._phase is not None:
            req._phase.set_attributes(attrs)
            req._phase.end(end_s)
            req._phase = None

    def _end_trace(self, req, end_s=None):
        """Terminal span bookkeeping: close the open phase (if any) and
        the request root, stamping the final state / reason / output
        size and the pool occupancy at that instant."""
        if req._span is None:
            return
        self._end_phase(req, end_s)
        req._span.set_attributes({
            "state": req.state, "finish_reason": req.finish_reason,
            "tokens_out": len(req.output),
            "page_occupancy": round(self.cache.occupancy(), 4)})
        if req.retry_after_s is not None:
            req._span.set_attribute("retry_after_s", req.retry_after_s)
        req._span.end(end_s)

    # ------------------------------------------------------ drain estimate
    def pending_decode_tokens(self):
        """Decode tokens still owed to queued + running requests (the
        backlog the drain estimate is over)."""
        owed = sum(r.sampling.max_new_tokens - len(r.output)
                   for r in self._queue)
        owed += sum(max(0, r.sampling.max_new_tokens - len(r.output))
                    for r in self._running())
        return owed

    def decode_rate(self):
        """EWMA decode throughput in tok/s (None before the first
        decode step)."""
        return self._decode_rate_ewma

    def estimated_drain_s(self):
        """Seconds to decode the current backlog at the measured rate —
        the machine-readable retry-after hint (ROADMAP: "estimated
        drain time from queue depth × decode rate").  Before the first
        decode measurement the rate falls back to ASSUMED_DECODE_RATE
        and the estimate never reports below ``drain_floor_s``: a
        cold/freshly-restarted engine has no evidence it drains fast,
        and advertising 0 would invite a router to dump the whole
        fleet's backlog on it at once."""
        tokens = self.pending_decode_tokens()
        if self._decode_rate_ewma is None:
            assumed = tokens / self.ASSUMED_DECODE_RATE
            return max(assumed, self.drain_floor_s)
        if tokens <= 0:
            return 0.0
        return tokens / max(self._decode_rate_ewma, 1e-9)

    def _retry_after(self):
        """Finite, strictly positive back-off for a shed request: at
        least one decode-step's worth even when the backlog estimate
        rounds to zero."""
        rate = self._decode_rate_ewma or self.ASSUMED_DECODE_RATE
        return max(self.estimated_drain_s(), 1.0 / max(rate, 1e-9))

    # ----------------------------------------------------- load shedding
    def _update_shedding(self):
        """High/low-watermark hysteresis over page-pool occupancy and
        queue depth; mirrors into the health gauge.  Returns the current
        shedding state."""
        occ, q = self.cache.occupancy(), len(self._queue)
        high = ((self.shed_occupancy_high is not None
                 and occ >= self.shed_occupancy_high)
                or (self.shed_queue_high is not None
                    and q >= self.shed_queue_high))
        low = ((self.shed_occupancy_low is None
                or occ <= self.shed_occupancy_low)
               and (self.shed_queue_low is None
                    or q <= self.shed_queue_low))
        if not self._shedding and high:
            self._shedding = True
        elif self._shedding and low and not high:
            self._shedding = False
        self.metrics.engine_healthy.set(0 if self._shedding else 1)
        return self._shedding

    # -------------------------------------------------- deadline eviction
    def _evict(self, req, now):
        """Terminal deadline eviction: pages freed, partial output kept."""
        if req in self._slots:
            self.cache.free(req.id)
            self._slots[self._slots.index(req)] = None
        req.state = RequestState.EVICTED
        req.finish_reason = "deadline"
        req.t_finished = now
        self.metrics.deadline_evictions.inc()
        self._end_trace(req, end_s=now)
        self._just_finished.append(req)

    def _fail(self, req, exc):
        """Per-row failure isolation: an exception raised while packing
        or committing ONE row is that request's fault, not the
        engine's — the row is retired terminal FAILED with its pages
        freed and its trace closed on the error, and every co-batched
        request keeps running.  Only exceptions that cannot be pinned
        to a row (the jitted step itself, the top-of-step fault site)
        escalate to the caller — the fleet router's replica-failure
        path."""
        if req in self._slots:
            self.cache.free(req.id)
            self._slots[self._slots.index(req)] = None
        req.state = RequestState.FAILED
        req.finish_reason = f"row failure: {exc!r}"
        req.t_finished = self._clock()
        self.metrics.requests_failed.inc()
        if req._span is not None:
            req._span.set_attribute("error", repr(exc))
        self._end_trace(req, end_s=req.t_finished)
        self._just_finished.append(req)

    def _evict_expired(self):
        """Evict every request (running OR still queued) whose deadline
        has passed — run at step start so freed pages are available to
        this step's admissions."""
        now = self._clock()
        for req in self._running():
            if req.deadline is not None and now > req.deadline:
                self._evict(req, now)
        expired = [r for r in self._queue
                   if r.deadline is not None and now > r.deadline]
        for req in expired:
            self._queue.remove(req)
            self._evict(req, now)

    # -------------------------------------------------------------- admit
    def _free_slot(self):
        for i, r in enumerate(self._slots):
            if r is None:
                return i
        return None

    def _try_admit(self):
        while self._queue:
            slot = self._free_slot()
            if slot is None:
                return
            req = self._queue[0]
            # chunk-granularity admission: pages for the FIRST chunk
            # only — later chunks extend the table step by step.  With
            # the prefix cache on, the radix walk happens here: the
            # longest cached prefix of the prompt is mapped in
            # read-only (refcount bump) and chunked prefill starts at
            # the first uncached token
            if self.prefix_cache:
                matched = self.cache.allocate_prefixed(
                    req.id, req.prompt, self.chunk_len, slot=slot)
                if matched is None:
                    return                   # FIFO: no queue-jumping
            else:
                matched = 0
                first = min(self.chunk_len, len(req.prompt))
                if not self.cache.allocate(req.id, first, slot=slot):
                    return                   # FIFO: no queue-jumping
            req.prompt_pos = matched
            self._queue.popleft()
            now = self._clock()
            req.state = RequestState.RUNNING
            req.t_admitted = now
            req._admit_seq = self._admit_seq
            self._admit_seq += 1
            self._slots[slot] = req
            entry = slot_entry(req.sampling, len(req.prompt))
            if entry != self._slot_sampling[slot]:
                self._slot_sampling[slot] = entry
                self._sampling_table = None      # sent with the next step
            self.metrics.requests_admitted.inc()
            self.metrics.queue_wait.observe(now - req.t_submit)
            self._end_phase(req, end_s=now)      # queued → admitted
            if req._span is not None:
                req._span.set_attributes({
                    "batch_slot": slot,
                    "prefix_hit_tokens": matched,
                    "occupancy_at_admit":
                        round(self.cache.occupancy(), 4)})

    # -------------------------------------------------------- unified step
    def _running(self):
        return [r for r in self._slots if r is not None]

    def _preempt(self, req):
        """Free req's pages and push it back to the queue head for
        recompute (memory pressure, never an error)."""
        self.cache.free(req.id)
        self._slots[self._slots.index(req)] = None
        req._reset_for_recompute()
        self._queue.appendleft(req)
        self.metrics.requests_preempted.inc()
        # lifecycle rewinds with the tokens: close the open phase and
        # re-enter "queued" so the trace shows the preemption gap
        self._end_phase(req, preempted=True)
        if req._span is not None:
            req._span.attributes["preemptions"] = \
                req._span.attributes.get("preemptions", 0) + 1
            req._phase = self.tracer.start_span("queued", req._span)

    def _last_token_pending(self, req):
        """Is the token in flight for ``req`` the one it ends on, by a
        count the host holds (``max_new_tokens``, ``max_seq_len``)?  Such
        a row is not scheduled again: the finish costs no step.  A stop
        token cannot be foreseen and is over-run by one step."""
        return req._pending > 0 and (
            len(req.output) + req._pending >= req.sampling.max_new_tokens
            or len(req.tokens) + req._pending >= self.cfg.max_seq_len)

    def _plan_rows(self):
        """{batch slot: query tokens this step} under token_budget, from
        what has been scheduled so far (committed or in flight).
        Decode rows always get their one token, but for a row whose last
        token is already in flight; mid-prefill rows then
        split the remaining budget fairly (ceil-share, admission order)
        so a short prompt admitted behind a long one still makes
        progress toward its TTFT instead of starving."""
        plan = {}
        budget = self.token_budget
        chunkers = []
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            if req.prompt_pos < len(req.prompt):
                chunkers.append(i)
            elif not self._last_token_pending(req):
                plan[i] = 1
                budget -= 1
        chunkers.sort(key=lambda i: self._slots[i]._admit_seq)
        for n, i in enumerate(chunkers):
            if budget <= 0:
                break
            req = self._slots[i]
            fair = -(-budget // (len(chunkers) - n))          # ceil share
            q = min(self.chunk_len, len(req.prompt) - req.prompt_pos,
                    fair)
            if q > 0:
                plan[i] = q
                budget -= q
        return plan

    def _ensure_capacity(self):
        """Pages for every planned row's post-step context — the chunk a
        mid-prefill row is about to write, or the token decode is about
        to append (the one in flight counted in); preempt youngest-first
        (mid-prefill rows included) when the pool runs dry.  Returns the
        final, feasible plan — or None, having preempted nothing, when
        the pool runs dry while a step is in flight: the caller settles
        that step first (what it finishes frees pages, and a preemption
        rewinds committed state) and asks again."""
        while True:
            plan = self._plan_rows()
            stable = True
            for i in sorted(plan, key=lambda i: self._slots[i]._admit_seq
                            if self._slots[i] is not None else 0):
                req = self._slots[i]
                if req is None:
                    continue                 # preempted earlier this pass
                if req.prompt_pos < len(req.prompt):
                    target = req.prompt_pos + plan[i]
                else:
                    target = len(req.tokens) + req._pending
                if self.window:
                    # the step's first token for this row sits at
                    # target - plan[i] and reads `window` positions back
                    self.cache.release_window(
                        req.id, target - plan[i] - self.window + 1)
                while req in self._slots and \
                        not self.cache.extend(req.id, target):
                    if self._inflight is not None:
                        return None
                    victim = max(self._running(),
                                 key=lambda r: r._admit_seq)
                    self._preempt(victim)
                    stable = False
                    if victim is req:
                        break
            if stable:
                return plan

    def step_args(self, params=None, state=None, *, sharding=None):
        """The jitted step's arguments, for ``_step_fn.lower(*...)``:
        the parameters and state pools (the engine's own, or the given
        abstract ones), then the ragged batch, the sampling table and the
        previous step's ids as ``ShapeDtypeStruct``s on ``sharding``.
        The one place that knows the step's signature besides
        ``__init__`` and the dispatch."""
        B = self.max_batch_size
        return (self.params if params is None else params,
                *(self.cache.state_arrays() if state is None else state),
                batch_shapes(*self.batch_dims, sharding=sharding,
                             window_tables=self._window_tables),
                jax.ShapeDtypeStruct((B, len(GREEDY)), jnp.uint32,
                                     sharding=sharding),
                jax.ShapeDtypeStruct(self._prev_ids.shape, jnp.int32,
                                     sharding=sharding))

    def _dispatch(self, batch, sched, phases):
        """Send the packed batch to the one jitted program — it ends by
        choosing a token for every row — and leave it running: the
        ``dispatch`` phase.  What the scheduler plans from advances here,
        not at commit: the pools (futures, chained through programs by
        donation), the ids the next program resolves its pending tokens
        from, every row's ``prompt_pos`` and ``_pending``, and a completed
        prompt's full pages in the radix tree (any program that reads
        them is ordered after this one).  Returns the ``_InFlight``."""
        # phase attribution for the sampling profiler: a step with any
        # mid-prefill row is a prefill chunk, else pure decode
        kind = "prefill_chunk" if any(
            ctx - q < len(req.prompt) for _, req, q, ctx in sched) \
            else "decode"
        ahead = self._inflight is not None
        t0 = self._clock()
        with phases.phase("dispatch", kind):
            if self._sampling_table is None:
                table = np.array(self._slot_sampling, np.uint32)
                self._sampling_table = (
                    jnp.asarray(table) if self._replicated is None
                    else jax.device_put(table, self._replicated))
            ids, logits, *state = self._step_fn(
                self.params, *self.cache.state_arrays(),
                type(batch)(*(jnp.asarray(a) for a in batch)),
                self._sampling_table, self._prev_ids)
            # the 64 bytes follow the program to the host on their
            # own: a read begun only after the wait costs one more
            # round trip to the device (0.45 ms on a v5e, PERF.md)
            ids.copy_to_host_async()
        self.cache.set_state(state)
        self._prev_ids = ids
        (self.metrics.steps_ahead if ahead
         else self.metrics.steps_not_ahead).inc()
        for _, req, q, ctx in sched:
            n = len(req.prompt)
            if ctx - q < n:
                req.prompt_pos = ctx
                # prompt complete: its FULL pages are reusable K/V once
                # this program has run — register them in the radix tree
                # so the next request sharing this prefix skips the
                # prefill FLOPs (the partial final page keeps taking
                # decode writes and is never shared)
                if ctx >= n and self.prefix_cache:
                    self.cache.insert_prefix(req.id, req.prompt)
            if ctx >= n:
                req._pending += 1            # the program owes it a token
        return _InFlight(sched, ids, logits, t0, kind)

    def _collect(self, step, phases):
        """Wait for a dispatched program and read its ``[B]`` ids: the
        ``device_wait`` and ``fetch`` phases.  Returns (ids, the engine
        clock once they are read)."""
        with phases.phase("device_wait", step.kind):
            step.ids.block_until_ready()
        with phases.phase("fetch", step.kind):
            ids = np.asarray(step.ids).tolist()
        if len(ids) > self.max_batch_size:
            # what the model's step counted, behind the rows' ids
            self.model.record_stats(self.metrics,
                                    ids[self.max_batch_size:])
        return ids, self._clock()

    def _drain(self, reason, phases=None):
        """Settle the step in flight now — wait, read, sample, commit —
        because what comes next has to see committed state.  A no-op
        with nothing in flight.  Outside a ``step()`` call the phases'
        events are recorded and no sample of the series is."""
        step, self._inflight = self._inflight, None
        if step is None:
            return
        if phases is None:
            phases = _StepPhases(self.metrics.step_phases)   # never closed
        self.metrics.pipeline_drains.labels(reason=reason).inc()
        ids, t1 = self._collect(step, phases)
        with phases.phase("sample"):
            sampled = self._sample_rows(ids, step)
        with phases.phase("commit"):
            self._commit(step, sampled, t1)

    def _pack(self, plan):
        """The planned rows packed row-major into the step's host
        ``RaggedBatch`` (``models/ragged.py`` has the contract):
        ``(batch, sched)`` with ``sched`` the ``(slot, req, q, new ctx)``
        of every packed row, or None when no row is left to run.  A
        decode row sends its newest token, or, while that token is still
        on the device, the marker that names it."""
        batch = empty_batch(*self.batch_dims,
                            window_tables=self._window_tables)
        tokens, rows, slots, qlens, ctxs, tables, *window_tables = batch
        sched = []                               # (slot, req, q, new ctx)
        off = 0
        for i in range(self.max_batch_size):     # packing is row-major
            req = self._slots[i]
            q = plan.get(i, 0)
            if req is None or q <= 0:
                continue
            try:
                if req.prompt_pos < len(req.prompt):
                    chunk = req.prompt[req.prompt_pos:req.prompt_pos + q]
                    ctx = req.prompt_pos + q
                else:
                    chunk = (pending_token(i) if req._pending
                             else req.tokens[-1])
                    ctx = len(req.tokens) + req._pending
                table = self.cache.page_table(req.id)
                if window_tables:
                    window_tables[0][i] = self.cache.window_page_table(
                        req.id)
            except Exception as e:
                # row-attributable plan failure: THIS row dies, the
                # batch (arrays untouched for it) runs without it
                self._fail(req, e)
                continue
            tokens[off:off + q] = chunk
            rows[off:off + q] = i
            slots[off:off + q] = np.arange(q)
            qlens[i], ctxs[i] = q, ctx
            tables[i] = table
            sched.append((i, req, q, ctx))
            off += q
        if not sched:
            return None
        return batch, sched

    def _sample_rows(self, ids, step):
        """{batch slot: next token} for every row of ``step`` whose
        context now covers its prompt (a decode row, or the chunk that
        completed a prompt), from the ids the device chose.  A row whose
        request is no longer running (it ended while this step was in
        flight: a stop token, a deadline, a failure) has no entry: the
        step over-ran it, and the result is dropped.  A row whose hook
        raises is retired FAILED here and has no entry, like any other
        row-attributable failure.  Counts the step under the path the
        program took: the ``cond``'s predicate, from what the plan holds
        (``sampling.any_stochastic``)."""
        self.step_logits = step.logits
        sampled = {}
        stochastic = False
        overrun = 0
        for i, req, _, ctx in step.sched:
            if req.state != RequestState.RUNNING:
                overrun += 1
                continue
            if ctx < len(req.prompt):
                continue                     # more chunks to go
            stochastic = stochastic or req.sampling.temperature > 0.0
            try:
                sampled[i] = self._sample_token(ids[i], req)
            except Exception as e:
                self._fail(req, e)
        if overrun:
            self.metrics.overrun_rows.inc(overrun)
        (self.metrics.sample_steps_stochastic if stochastic
         else self.metrics.sample_steps_greedy).inc()
        return sampled

    def _commit(self, step, sampled, t1):
        """Fold one step's results into each request's lifecycle:
        counters, flight-recorder spans, the sampled token, finish.
        ``t1`` is when the step's ids were read; the spans run from the
        program's own dispatch to it, and the decode rate is tokens over
        the time since the step before was read (or since this one's
        dispatch, when nothing was in flight before it): with a step
        always in flight, dispatch to read spans two programs."""
        sched, t0 = step.sched, step.t0
        dt = t1 - max(t0, self._t_settled)
        self._t_settled = t1
        occ = round(self.cache.occupancy(), 4)
        n_rows = len(sched)
        committed = 0
        in_context = read = resets = chunk_rows = decode_rows = 0
        for i, req, q, ctx in sched:
            # what the step's attention layers had to read for this row,
            # from the lengths alone (no device read); a row that began
            # at position 0 had its recurrent state zeroed in the step
            a, b = self.model.attention_positions(ctx, q)
            in_context, read = in_context + a, read + b
            resets += self.model.recurrent and ctx == q
            mid_prefill = ctx - q < len(req.prompt)
            if self.model.recurrent:
                # the step advanced this row's state by a chunk or a token
                if mid_prefill:
                    chunk_rows += 1
                else:
                    decode_rows += 1
            if req.state != RequestState.RUNNING:
                continue             # over-run, or failed while sampling
            # per-row commit isolation: anything this row's
            # bookkeeping raises is ITS failure — the row retires
            # FAILED, every other row in the batch commits normally
            try:
                if mid_prefill:
                    self.metrics.prefill_tokens.inc(q)
                    self.metrics.prefill_chunks.inc()
                    if req._span is not None:
                        self.tracer.start_span(
                            f"chunk[{req._chunks_done}]", req._span,
                            start_s=t0,
                            attributes={"tokens": q, "prefilled": ctx,
                                        "batch_slot": i,
                                        "batch_size": n_rows,
                                        "page_occupancy": occ}).end(t1)
                    req._chunks_done += 1
                    if ctx < len(req.prompt):
                        continue             # more chunks to go
                    # the chunk that completed the prompt falls through
                    # and commits the request's first token — TTFT
                req._pending -= 1
                req.tokens.append(sampled[i])
                committed += 1
                self.metrics.tokens_generated.inc()
                if req.t_first_token is None:
                    # time-to-first-SAMPLED-token: stamped when the last
                    # prompt chunk completes, not when prefill starts
                    req.t_first_token = t1
                    # exemplar: this observation's trace — the /metrics
                    # p99 bucket then names a trace the ring retains
                    self.metrics.ttft.observe(
                        t1 - req.t_submit,
                        exemplar=getattr(req._span, "trace_id", None))
                if not mid_prefill:
                    self.metrics.decode_token.observe(dt / n_rows)
                    if req._span is not None:
                        # retroactive span over the batched step this
                        # request rode in — one decode[i] per token
                        self.tracer.start_span(
                            f"decode[{len(req.output) - 1}]", req._span,
                            start_s=t0,
                            attributes={"batch_slot": i,
                                        "batch_size": n_rows,
                                        "page_occupancy": occ}).end(t1)
                self._maybe_finish(req)
            except Exception as e:
                self._fail(req, e)
        self.metrics.attention_context.inc(in_context)
        self.metrics.attention_selected.inc(read)
        if resets:
            self.metrics.state_resets.inc(resets)
        if chunk_rows:
            self.metrics.state_row_steps_chunk.inc(chunk_rows)
        if decode_rows:
            self.metrics.state_row_steps_decode.inc(decode_rows)
        if dt > 0 and committed:
            # EWMA decode throughput feeds the drain/retry-after hint
            inst = committed / dt
            a = self._ewma_alpha
            self._decode_rate_ewma = (
                inst if self._decode_rate_ewma is None
                else a * inst + (1 - a) * self._decode_rate_ewma)

    # ------------------------------------------------------------ sampling
    def _sample_token(self, token_id, req):
        """The per-row seam of the ``sample`` phase: receives the id the
        device chose for ``req``'s row and returns the token that is
        committed to ``req.tokens``; ``self.step_logits`` is then the
        logits that id was chosen from.  Raising here retires that row
        FAILED and no other (its result in the step already in flight is
        dropped).  The hook runs one step behind the device: the row's
        next step was dispatched before this call and continues from the
        id the device chose, whatever is returned here.  What a request
        continues from is the sampling table's business, on the device
        (``serving/sampling.py``); this hook sees and records."""
        return token_id

    # ------------------------------------------------------------- finish
    def _maybe_finish(self, req):
        sp = req.sampling
        reason = None
        if req.tokens[-1] in sp.stop_token_ids:
            reason = "stop"
        elif len(req.output) >= sp.max_new_tokens:
            reason = "length"
        elif len(req.tokens) >= self.cfg.max_seq_len:
            reason = "length"
        if reason is None:
            return
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.t_finished = self._clock()
        self.cache.free(req.id)
        if req in self._slots:
            self._slots[self._slots.index(req)] = None
        self.metrics.requests_finished.inc()
        self._end_trace(req, end_s=req.t_finished)
        self._just_finished.append(req)

    # --------------------------------------------------------------- drive
    def has_work(self):
        """Anything queued, running, or dispatched and not yet committed:
        ``while eng.has_work(): eng.step()`` ends with every token
        committed."""
        return (bool(self._queue) or self._inflight is not None
                or any(r is not None for r in self._slots))

    def step(self):
        """One scheduler iteration: evict past-deadline requests, admit,
        plan and dispatch the unified ragged step (prompt chunks + decode
        rows in one batch) — and then, while that program runs, wait for
        the one the call before dispatched, read its ids and commit them;
        update gauges.  Returns the requests whose finish (or eviction)
        this call committed.

        A program's tokens are therefore visible one call after the call
        that dispatched it (a call with nothing left to plan only commits
        the step in flight), and a request's finish is seen one call
        late: by then a row that ended on a stop token has ridden one
        more program, whose result for it is dropped
        (``serving_overrun_rows_total``); a row that ends by a count the
        host holds is never scheduled past its last token.

        The call is cut into ``STEP_PHASES`` (``_StepPhases``): one
        ``serving::step`` event around one ``serving::step/<phase>`` event
        per phase reached, and one observation of
        ``serving_step_phase_seconds`` per phase, also when it raises."""
        phases = _StepPhases(self.metrics.step_phases)
        with RecordEvent("serving::step"):
            try:
                return self._step(phases)
            finally:
                phases.close()

    def _step(self, phases):
        if self._inflight is not None and fault_armed("serving.step"):
            # the fault site below stands for the whole replica failing
            # "before any request state mutates", reads every request's
            # committed stream and may rewrite the pools: it sees settled
            # state, as it did when the loop was synchronous
            self._drain("fault_injection", phases)
        with phases.phase("admit", "admission"):
            # fault site: an io_error here is the whole step failing the
            # way a crashed replica's RPC would — before any request
            # state mutates, so a router can re-dispatch losslessly.
            # tree= exposes the live KV page pool to the bitflip kind
            # (silent corruption of serving state) and tokens= exposes
            # every in-flight request's stream to poison_request (the
            # query-of-death: a seed-chosen pattern that kills whichever
            # replica it is aboard — deliberately NOT row-attributable)
            kv = dict(self.cache.arrays)
            fault_point("serving.step", tree=kv,
                        tokens=[r.tokens for r in self._running()]
                        + [r.tokens for r in self._queue])
            self.cache.arrays.update(kv)
            self._evict_expired()
            self._try_admit()
        with phases.phase("plan", "admission"):
            plan = self._ensure_capacity()
        if plan is None:
            # the pool cannot cover the plan while a step is in flight:
            # settle it, then plan (and, if it must be, preempt) on
            # committed state
            self._drain("memory", phases)
            with phases.phase("plan", "admission"):
                plan = self._ensure_capacity()
        packed = None
        if plan:
            with phases.phase("pack"):
                packed = self._pack(plan)
        before, ran = self._inflight, None
        if packed is not None or before is not None:
            with RecordEvent("serving::unified_step"):
                # program k goes out while k-1 runs; only then is k-1
                # waited for.  With nothing planned the call just settles
                # the step in flight
                self._inflight = (self._dispatch(*packed, phases)
                                  if packed is not None else None)
                if before is not None:
                    ids, t1 = self._collect(before, phases)
            if before is not None:
                with phases.phase("sample"):
                    ran = before, self._sample_rows(ids, before), t1
        with phases.phase("commit"):
            if ran is not None:
                self._commit(*ran)
            self._update_shedding()
            self.metrics.page_occupancy.set(self.cache.occupancy())
            if self._window_tables:
                self._sync_window_metrics()
            self.metrics.queue_depth.set(len(self._queue))
            self.metrics.estimated_drain_s.set(self.estimated_drain_s())
            self._sync_prefix_metrics()
            done, self._just_finished = self._just_finished, []
        return done

    def _sync_prefix_metrics(self):
        """Fold the cache's monotonic prefix counters into the
        serving_prefix_* registry series (delta sync: the cache doesn't
        know about metrics, the registry wants monotonic counters)."""
        stats = self.cache.prefix_stats()
        m = self.metrics
        for key, counter in (("hits", m.prefix_cache_hits),
                             ("hit_tokens", m.prefix_hit_tokens),
                             ("evictions", m.prefix_cache_evictions)):
            delta = stats[key] - self._prefix_seen[key]
            if delta:
                counter.inc(delta)
                self._prefix_seen[key] = stats[key]
        m.prefix_cache_pages.set(stats["cached_pages"])

    def _sync_window_metrics(self):
        """The two pools' pages in use and the window pages given back
        since the last call (the cache counts, the registry wants a
        monotonic counter)."""
        m, cache = self.metrics, self.cache
        m.pages_in_use_full.set(cache.num_used_pages)
        m.pages_in_use_window.set(cache.num_used_window_pages)
        delta = cache.window_pages_released - self._window_released_seen
        if delta:
            m.window_pages_released.inc(delta)
            self._window_released_seen = cache.window_pages_released

    def prefix_summary(self, max_entries=32):
        """Bounded radix-tree summary for cache-aware routing — the
        per-replica payload the fleet gossips (root hashes + hit
        stats).  See ``PagedKVCache.prefix_summary``."""
        out = self.cache.prefix_summary(max_entries=max_entries)
        out["enabled"] = self.prefix_cache
        return out

    def evacuate(self):
        """Pull EVERY in-flight request off this engine — running
        (mid-prefill or decoding) and queued — free their pages, and
        return them with their sampled tokens intact, in admission
        order (running first, then the queue).

        The fleet router's failover/drain primitive: the caller
        re-enqueues each request elsewhere as an ordinary admission
        (prompt + already-sampled tokens), so this engine's paged KV
        state is never trusted again.  Each request leaves in state
        ``EVACUATED`` with its trace closed; partial output is
        preserved — nothing is re-sampled here, nothing is lost.  A
        step in flight is settled first, so its tokens leave with their
        requests (a request it finishes is finished, not evacuated); if
        the device cannot be waited for any more, that step is dropped
        and its tokens are drawn again wherever the requests land — a
        draw is keyed by (seed, position)."""
        try:
            self._drain("evacuate")
        except Exception:
            pass    # silent-ok: a dead device; what was committed leaves
        now = self._clock()
        running = sorted(self._running(), key=lambda r: r._admit_seq)
        for req in running:
            self.cache.free(req.id)
            self._slots[self._slots.index(req)] = None
        queued = list(self._queue)
        self._queue.clear()
        out = running + queued
        for req in out:
            req.state = RequestState.EVACUATED
            req.finish_reason = "evacuated"
            self._end_trace(req, end_s=now)
        self.metrics.queue_depth.set(0)
        self.metrics.page_occupancy.set(self.cache.occupancy())
        return out

    def health(self):
        """Live scheduler health — the ``/healthz`` payload: shedding
        flag, queue depth, in-flight batch, pool occupancy, and the
        drain estimate a cooperating front-end should back off by.
        Host state only, and never a wait: the tokens of a step in
        flight count as still owed."""
        return {"healthy": not self._shedding,
                "queue_depth": len(self._queue),
                "running": len(self._running()),
                "page_occupancy": self.cache.occupancy(),
                "estimated_drain_s": self.estimated_drain_s(),
                "decode_rate_tok_s": self._decode_rate_ewma,
                "prefix_cache": {"enabled": self.prefix_cache,
                                 **self.cache.prefix_stats()}}

    def generate(self, prompts, sampling=None):
        """Batch convenience: submit all prompts, drive the scheduler to
        completion, return each request's generated tokens (submit
        order; rejected requests yield [])."""
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling] * len(prompts)
        reqs = [self.add_request(p, s) for p, s in zip(prompts, sampling)]
        while self.has_work():
            self.step()
        return [r.output for r in reqs]

    def warmup(self, prompt_len=4, max_new_tokens=2):
        """Pre-rotation warmup: run one tiny request end-to-end so the
        unified step compiles now, not on the first real request —
        then RESET the decode-rate EWMA.  The warmup steps time jit
        compilation, not steady-state decode, so their rate samples
        are garbage; discarding them keeps ``drain_floor_s``
        advertised (``estimated_drain_s`` stays on the cold-start
        floor, ``health()['decode_rate_tok_s']`` stays None) until the
        first *real* decode step measures the true rate.  The
        autoscaler reads that None as "warming, not capacity yet"."""
        n = max(1, min(int(prompt_len), self.cfg.max_seq_len // 2))
        prompt = list(range(1, n + 1))
        self.generate([prompt],
                      SamplingParams(max_new_tokens=int(max_new_tokens)))
        self._decode_rate_ewma = None
        return self
