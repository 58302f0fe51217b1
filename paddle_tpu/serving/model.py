"""What the serving engine needs to know of a model: its ragged step and
the state that step carries.

``Engine`` serves any object with this interface; it never names a model.

- ``cfg``: ``max_seq_len``, ``vocab_size`` and ``jdtype()`` are read.
- ``init_params()``: fresh parameters (tests, benches).
- ``state_spec(num_pages=, page_size=, max_batch_size=)``: the pools the
  cache manager builds, in the order the step takes and returns them, as
  ``(name, shape, dtype, kind)``; kind ``"pages"`` has the physical page
  on axis 1 (allocated, shared, copied and compacted page by page), kind
  ``"slots"`` the batch row (one fixed state per in-flight request),
  kind ``"window_pages"`` a page axis of its own size
  (``num_window_pages=``, passed to a model whose ``window`` is set): the
  pools of sliding-window layers, with a page table of their own whose
  pages behind the window are given back.
- ``make_step(max_q=, mesh=)``: ``step(params, state, batch) -> (logits
  [B, V], state)`` with ``state`` the tuple of pools and ``batch`` the
  scheduler's ``RaggedBatch`` (``models/ragged.py`` owns the format);
  jitted by the engine with every pool donated.  A model returns logits
  and never samples: the engine's own jitted wrapper chooses each row's
  token from them in the same program (``serving/sampling.py``), once for
  every served model, and hands the host ``[B]`` ids.
- ``recurrent``: the model keeps per-row state that is a function of
  every token the row has seen.  Pages of a cached prefix say nothing of
  that state, so the engine serves such a model cold (no prefix reuse).
- ``attention_positions(ctx, q)``: for a row that ran ``q`` tokens and
  now holds ``ctx``, the positions those tokens had in context and the
  positions the model's attention layers read for them (fewer, where it
  selects), per attention layer: host arithmetic for the
  ``serving_attention_positions_total`` counter.
- ``shard(params, mesh)``: GSPMD serving, where the model has it
  (``make_step(mesh=...)`` raises ``NotImplementedError`` where not).
- optional: ``window`` (positions a sliding-window layer reads; the
  engine then keeps the second page table and budget), and ``step_stats``
  (names of int32 numbers the step counts on the device and returns after
  its state; they reach the host behind the ids and are handed to
  ``record_stats(metrics, values)``).
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["GPTServed", "HybridServed", "SSMServed", "MoEWindowServed",
           "as_served"]


class GPTServed:
    """The dense GPT family behind the engine's interface."""

    recurrent = False

    def __init__(self, cfg):
        self.cfg = cfg

    def init_params(self):
        from ..models.gpt import gpt_init

        return gpt_init(self.cfg)

    def state_spec(self, *, num_pages, page_size, max_batch_size):
        cfg = self.cfg
        pool = (cfg.num_layers, num_pages, page_size, cfg.num_heads,
                cfg.head_dim)
        return [("k_pages", pool, cfg.jdtype(), "pages"),
                ("v_pages", pool, cfg.jdtype(), "pages")]

    def make_step(self, *, max_q, mesh=None):
        from ..models.gpt import gpt_ragged_step

        cfg = self.cfg

        def step(params, state, batch):
            logits, *state = gpt_ragged_step(cfg, params, batch, *state,
                                             max_q=max_q, mesh=mesh)
            return logits, tuple(state)

        # jitted here, where the step is named: the engine's own jit
        # inlines it, and the trace-purity pass (tools/analysis) follows
        # jit entry points, not a callable handed over at run time
        return jax.jit(step)

    def attention_positions(self, ctx, q):
        # token at position p attends over p + 1 positions, all of them
        n = q * ctx - q * (q - 1) // 2
        return n, n

    def shard(self, params, mesh):
        """``(sharded params, their sharding tree, the PartitionSpec of a
        page pool)``: params by the mesh.py GPT rule table, the pools
        ``[L, P, ps, H, hd]`` on their head axis along "mp"."""
        from jax.sharding import PartitionSpec as P

        from ..distributed import mesh as mesh_mod

        params = mesh_mod.shard_params(params, mesh)
        return (params, mesh_mod.sharding_tree(params, mesh),
                P(None, None, None, "mp"))


class HybridServed:
    """The sparse-plus-lightning decoder (``models/hybrid.py``)."""

    recurrent = True
    step_stats = ("attention_items", "attention_item_pages")

    def __init__(self, cfg, *, dense_only=False):
        self.cfg = cfg
        self.dense_only = dense_only

    def init_params(self):
        from ..models.hybrid import hybrid_init

        return hybrid_init(self.cfg)

    def state_spec(self, **sizes):
        from ..models.hybrid import hybrid_state_spec

        return hybrid_state_spec(self.cfg, **sizes)

    def make_step(self, *, max_q, mesh=None):
        from ..models.hybrid import hybrid_ragged_step

        if mesh is not None:
            raise NotImplementedError(
                "Engine(mesh=...) with a model of two layer stacks: the "
                "sparse layers' pools shard by key/value head, the "
                "lightning layers' state by head, and neither has a rule "
                "table or a shard_map around its kernel yet (PERF.md, open "
                "questions)")
        cfg, dense_only = self.cfg, self.dense_only

        def step(params, state, batch):
            logits, *state, stats = hybrid_ragged_step(
                cfg, params, batch, *state, max_q=max_q,
                dense_only=dense_only)
            return logits, tuple(state), stats

        return jax.jit(step)       # as GPTServed.make_step

    def attention_positions(self, ctx, q):
        cfg = self.cfg
        n = np.arange(ctx - q + 1, ctx + 1, dtype=np.int64)   # contexts
        context = int(n.sum())
        if self.dense_only:
            return context, context
        # past dense_len: topk blocks, the token's own one read up to it
        bs = cfg.block_size
        sparse = np.minimum(n, (cfg.topk - 1) * bs + (n - 1) % bs + 1)
        return context, int(np.where(n <= cfg.dense_len, n, sparse).sum())

    def record_stats(self, metrics, values):
        items, pages = values
        metrics.attention_items.inc(items)
        metrics.attention_item_pages.inc(pages)


class SSMServed:
    """The parallel-mixer decoder (``models/ssm.py``): a state-space mixer
    and grouped-query attention in every block, so every layer holds
    pages, a convolution window and a scan state."""

    recurrent = True

    def __init__(self, cfg):
        self.cfg = cfg

    def init_params(self):
        from ..models.ssm import ssm_init

        return ssm_init(self.cfg)

    def state_spec(self, **sizes):
        from ..models.ssm import ssm_state_spec

        return ssm_state_spec(self.cfg, **sizes)

    def make_step(self, *, max_q, mesh=None):
        from ..models.ssm import ssm_ragged_step

        if mesh is not None:
            raise NotImplementedError(
                "Engine(mesh=...) with a state-space mixer beside "
                "attention: the pages shard by key/value head, the scan "
                "state by head and the convolution window by channel, and "
                "none has a rule table or a shard_map around its kernel "
                "yet (PERF.md, open questions)")
        cfg = self.cfg

        def step(params, state, batch):
            logits, *state = ssm_ragged_step(cfg, params, batch, *state,
                                             max_q=max_q)
            return logits, tuple(state)

        return jax.jit(step)       # as GPTServed.make_step

    def attention_positions(self, ctx, q):
        # dense: token at position p attends over p + 1 positions
        n = q * ctx - q * (q - 1) // 2
        return n, n


class MoEWindowServed:
    """The sparse-expert decoder with sliding-window layers beside full
    ones (``models/moe_window.py``): two groups of page pools, and an
    expert layer that holds a share of the experts.  Served cold: a cached
    prefix whose window pages were given back cannot be resumed."""

    recurrent = False
    step_stats = ("expert_pairs", "expert_rows_fullest", "experts_read",
                  "expert_tile_rows")

    def __init__(self, cfg):
        self.cfg = cfg
        self.window = cfg.window

    def init_params(self):
        from ..models.moe_window import moe_window_init

        return moe_window_init(self.cfg)

    def state_spec(self, **sizes):
        from ..models.moe_window import moe_window_state_spec

        return moe_window_state_spec(self.cfg, **sizes)

    def make_step(self, *, max_q, mesh=None):
        from ..models.moe_window import moe_window_ragged_step

        if mesh is not None:
            raise NotImplementedError(
                "Engine(mesh=...) with sparse experts and window layers: "
                "the experts shard by expert (an `ep` axis the mesh does "
                "not have, and an exchange of tokens between holders), "
                "the two groups of pools by key/value head, and neither "
                "has a rule table or a shard_map around its kernel yet "
                "(PERF.md, open questions)")
        cfg = self.cfg

        def step(params, state, batch):
            logits, *state, stats = moe_window_ragged_step(
                cfg, params, batch, *state, max_q=max_q)
            return logits, tuple(state), stats

        return jax.jit(step)       # as GPTServed.make_step

    def attention_positions(self, ctx, q):
        # per layer: a full layer's token at position p reads p + 1
        # positions, a window layer's the last `window` of them
        n = np.arange(ctx - q + 1, ctx + 1, dtype=np.int64)
        return int(n.sum()), int(np.minimum(n, self.window).sum())

    def record_stats(self, metrics, values):
        from ..models.moe_window import SPARSE

        pairs, fullest, read, tile_rows = values
        metrics.expert_pairs.inc(pairs)
        metrics.expert_tile_rows.inc(tile_rows)
        metrics.expert_weight_reads.inc(read)
        slots = self.cfg.mlp_types.count(SPARSE) * self.cfg.experts_held[1]
        metrics.expert_rows_max.set(fullest * slots / pairs if pairs else 0)


def as_served(model):
    """``model`` if it already has the interface, else the served form of
    a known config object."""
    if hasattr(model, "make_step"):
        return model
    from ..models.gpt import GPTConfig
    from ..models.hybrid import HybridConfig

    if isinstance(model, GPTConfig):
        return GPTServed(model)
    if isinstance(model, HybridConfig):
        return HybridServed(model)
    from ..models.ssm import SSMConfig

    if isinstance(model, SSMConfig):
        return SSMServed(model)
    from ..models.moe_window import MoEWindowConfig

    if isinstance(model, MoEWindowConfig):
        return MoEWindowServed(model)
    raise TypeError(f"Engine cannot serve a {type(model).__name__}: pass a "
                    f"served-model object (paddle_tpu.serving.model)")
