"""Model adapters — the stage protocol HybridEngine trains against.

Reference role: ``fleet.distributed_model`` wraps ANY Layer
(python/paddle/distributed/fleet/base/fleet_base.py:937,1043-1069) and
PipelineLayer/LayerDesc describe arbitrary stage stacks
(meta_parallel/parallel_layers/pp_layers.py:159).  Here the same
generality is a small functional protocol: a model family hands the
engine

  - ``init``        — the params pytree; block params STACKED on a
                      leading [num_layers, ...] axis under the top-level
                      key "blocks" (the scan/pipeline axis), everything
                      else ("aux" params: embeddings, final norms, heads)
                      at the top level
  - ``param_specs`` — a same-structure PartitionSpec tree (the TP/ZeRO
                      layout)
  - ``embed``       — inputs  -> [b, s_local, D] activations
  - ``block``       — one stage block: (bp, x, key) -> (x, aux_loss)
  - ``head_loss``   — activations + labels -> (sum_loss, count)

and the engine owns everything parallel: the mesh, the scan/pipeline
schedules (GPipe and 1F1B), ZeRO chunking/gather, remat, the optimizer,
collectives.  ``engine`` is passed to each apply fn so adapters can use
the engine's parallel helpers (sequence-parallel attention, chunked
vocab-CE, psum-by-vma).

Adapters for nn.Layer stacks: ``pp_layers.PipelineEngine`` trains
arbitrary LayerDesc/PipelineLayer models SPMD; this protocol is the
flagship perf path for families with a homogeneous stacked block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ModelAdapter", "GPTAdapter", "BertAdapter"]


class ModelAdapter:
    """Base stage protocol.  Subclasses own the model math; the config
    object must expose: num_layers, hidden, num_heads, head_dim,
    ffn_hidden, vocab_size, max_seq_len, dropout, dtype/jdtype(), remat,
    seq_parallel, moe_experts, tie_embeddings."""

    cfg = None
    causal = True

    # ---- structure ----
    def validate(self, engine):
        cfg = self.cfg
        assert cfg.num_layers % engine.pp == 0, "layers must divide pp"
        assert cfg.hidden % engine.mp == 0
        assert cfg.ffn_hidden % engine.mp == 0
        assert cfg.num_heads % engine.mp == 0
        assert cfg.vocab_size % engine.mp == 0
        if engine.sep > 1 and cfg.seq_parallel == "ulysses":
            assert (cfg.num_heads // engine.mp) % engine.sep == 0, \
                "Ulysses needs local heads divisible by sep " \
                "(use seq_parallel='ring' to lift the head cap)"

    def init(self, key):
        raise NotImplementedError

    def param_specs(self, engine):
        raise NotImplementedError

    # ---- apply fns ----
    def embed(self, engine, aux, tokens):
        """aux: the non-"blocks" params (z3-gathered).  -> [b, s, D]."""
        raise NotImplementedError

    def block(self, engine, bp, x, key):
        raise NotImplementedError

    def head_loss(self, engine, aux, x, labels):
        raise NotImplementedError

    # ---- policies ----
    def decay_this(self, path):
        """Weight-decay mask by param path (reference AdamW apply_decay_
        param_fun): skip norms and biases."""
        leaf = path.split("/")[-1]
        return ("ln" not in leaf) and not path.endswith("_b")

    def reference_loss(self, params, tokens, labels):
        """Single-device loss with the same math — the parity oracle."""
        raise NotImplementedError

    # ---- shared building blocks for subclasses ----
    def tp_transformer_block(self, engine, bp, x, key):
        """Megatron TP pre-LN transformer block over local shards
        (column-split qkv/up, row-split proj/down -> one psum per
        residual write), flash attention via the engine's sequence-
        parallel attention helper.  Shared by GPT (causal) and BERT
        (bidirectional) through ``self.causal``."""
        cfg, mp = self.cfg, engine.mp
        B, s_local, D = x.shape
        H_local = cfg.num_heads // mp
        hd = cfg.head_dim
        from ..models.gpt import _dropout, _layer_norm
        from .engine import _psum_varying

        k_attn = k_ffn = None
        if key is not None and cfg.dropout > 0.0:
            k_attn, k_ffn = jax.random.split(key)

        with jax.named_scope("attn"):
            h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
            qkv = jnp.einsum("bsd,de->bse", h, bp["qkv_w"]) + bp["qkv_b"]
            # global qkv column order is head-major [H, 3, hd] so an mp
            # shard is a whole group of heads (models/gpt.py uses the
            # same layout)
            qkv = qkv.reshape(B, s_local, H_local, 3, hd)
            q = qkv[:, :, :, 0].transpose(0, 2, 1, 3)
            k = qkv[:, :, :, 1].transpose(0, 2, 1, 3)
            v = qkv[:, :, :, 2].transpose(0, 2, 1, 3)
            attn = engine._attention(q, k, v, causal=self.causal)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, s_local,
                                                      H_local * hd)
            proj = jnp.einsum("bse,ed->bsd", attn, bp["proj_w"])
            proj = _psum_varying(proj, ("mp",))
            x = x + _dropout(proj + bp["proj_b"], cfg.dropout, k_attn)

        with jax.named_scope("mlp"):
            return self._tp_mlp(engine, bp, x, k_ffn)

    def _tp_mlp(self, engine, bp, x, k_ffn):
        """The block's second half: pre-LN FFN (dense or MoE) and its
        residual.  Returns (x, aux_loss)."""
        cfg = self.cfg
        from ..models.gpt import _dropout, _layer_norm
        from .engine import _psum_varying

        h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
        if getattr(cfg, "moe_experts", 0):
            from .moe import moe_layer

            y, aux = moe_layer(
                {"gate_w": bp["gate_w"], "up_w": bp["up_w"],
                 "up_b": bp["up_b"], "down_w": bp["down_w"],
                 "down_b": bp["down_b"]},
                h, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                ep_axis="ep" if engine.ep > 1 else None)
            return x + _dropout(y, cfg.dropout, k_ffn), aux
        h = jnp.einsum("bsd,df->bsf", h, bp["up_w"]) + bp["up_b"]
        h = jax.nn.gelu(h, approximate=True)
        down = jnp.einsum("bsf,fd->bsd", h, bp["down_w"])
        down = _psum_varying(down, ("mp",))
        return x + _dropout(down + bp["down_b"], cfg.dropout, k_ffn), \
            jnp.zeros((), jnp.float32)

    def block_specs(self, z):
        """Specs for the shared TP block layout (dense FFN)."""
        return {
            "ln1_g": P("pp", None), "ln1_b": P("pp", None),
            "qkv_w": P("pp", z, "mp"), "qkv_b": P("pp", "mp"),
            "proj_w": P("pp", "mp", z), "proj_b": P("pp", None),
            "ln2_g": P("pp", None), "ln2_b": P("pp", None),
            "up_w": P("pp", z, "mp"), "up_b": P("pp", "mp"),
            "down_w": P("pp", "mp", z), "down_b": P("pp", None),
        }


class GPTAdapter(ModelAdapter):
    """The decoder-LM family (flagship): vocab-parallel tied embedding,
    causal TP blocks, final-LN + tied-vocab CE head."""

    causal = True

    def __init__(self, cfg):
        self.cfg = cfg

    def validate(self, engine):
        super().validate(engine)
        cfg = self.cfg
        if engine.ep > 1:
            assert cfg.moe_experts > 0, "ep>1 needs a MoE model"
        if cfg.moe_experts:
            assert cfg.moe_experts % engine.ep == 0, \
                "experts must divide ep"

    def init(self, key):
        from ..models.gpt import gpt_init

        return gpt_init(self.cfg, key)

    def param_specs(self, engine):
        z = ("sharding" if engine.ec.zero_stage >= 3 and engine.zr > 1
             else None)
        blocks = self.block_specs(z)
        if self.cfg.moe_experts:
            for k in ("up_w", "up_b", "down_w", "down_b"):
                blocks.pop(k)
            blocks.update({
                # Mixtral-style EP: experts sharded over "ep"; the expert
                # FFN inner dim stays unsharded (ep takes mp's role)
                "gate_w": P("pp", None, None),
                "up_w": P("pp", "ep", z, None), "up_b": P("pp", "ep", None),
                "down_w": P("pp", "ep", z, None),
                "down_b": P("pp", "ep", None),
            })
        return {
            "wte": P("mp", z),                        # vocab-parallel
            "wpe": P(None, None),
            "blocks": blocks,
            "lnf_g": P(None), "lnf_b": P(None),
        }

    def embed(self, engine, aux, tokens):
        return engine._embed_core(aux["wte"], aux["wpe"], tokens)

    def block(self, engine, bp, x, key):
        return self.tp_transformer_block(engine, bp, x, key)

    def head_loss(self, engine, aux, x, labels):
        from ..models.gpt import _layer_norm

        x = _layer_norm(x, aux["lnf_g"], aux["lnf_b"])
        return engine.tied_vocab_ce(x, aux["wte"], labels)

    def reference_loss(self, params, tokens, labels):
        from ..models.gpt import gpt_loss

        return gpt_loss(self.cfg, params, tokens, labels)


class BertAdapter(ModelAdapter):
    """Bidirectional encoder with an MLM head (reference role:
    python/paddle/text's BERT-style pretrain path; architecture per
    Devlin et al., pre-LN variant).  Proves the engine's stage protocol
    carries a second family: different attention (bidirectional),
    different embedding (token types), different head (MLM transform:
    dense+gelu+LN before the tied vocab projection).

    step inputs: tokens = corrupted input ids, labels = original ids at
    masked positions, -100 elsewhere — the (tokens, labels) contract the
    engine already speaks."""

    causal = False

    def __init__(self, cfg):
        self.cfg = cfg

    def init(self, key):
        from ..models.bert import bert_init

        return bert_init(self.cfg, key)

    def param_specs(self, engine):
        z = ("sharding" if engine.ec.zero_stage >= 3 and engine.zr > 1
             else None)
        return {
            "wte": P("mp", z),
            "wpe": P(None, None),
            "wtt": P(None, None),          # token-type embedding
            "emb_ln_g": P(None), "emb_ln_b": P(None),
            "blocks": self.block_specs(z),
            # MLM transform kept replicated over mp (a D x D dense is
            # negligible next to the blocks; a column split would shard
            # the hidden dim the tied vocab head needs whole)
            "mlm_w": P(z, None),
            "mlm_b": P(None),
            "mlm_ln_g": P(None), "mlm_ln_b": P(None),
        }

    def embed(self, engine, aux, tokens):
        from ..models.bert import bert_embed

        return bert_embed(self.cfg, aux, tokens, engine=engine)

    def block(self, engine, bp, x, key):
        return self.tp_transformer_block(engine, bp, x, key)

    def head_loss(self, engine, aux, x, labels):
        from ..models.bert import bert_mlm_transform

        x = bert_mlm_transform(self.cfg, aux, x)
        return engine.tied_vocab_ce(x, aux["wte"], labels)

    def reference_loss(self, params, tokens, labels):
        from ..models.bert import bert_loss

        return bert_loss(self.cfg, params, tokens, labels)
