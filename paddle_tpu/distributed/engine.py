"""Hybrid-parallel training engine — the Fleet replacement on TPU.

Reference parity: this one file replaces the cooperating pieces of the
reference's hybrid stack — HybridCommunicateGroup wiring (topology.py:133),
TP layers' collectives (mp_layers.py), PipelineParallel's 1F1B tick loop
(pipeline_parallel.py:81), sharding stage-2's reduce-scatter/allgather
bookkeeping (group_sharded_optimizer_stage2.py:48), HybridParallelClipGrad
(hybrid_parallel_optimizer.py:45) and the DDP grad sync — executed not by
four Python wrapper classes over NCCL but by ONE shard_map'd train step over
a 6-axis mesh ("dp","pp","sharding","sep","ep","mp") whose collectives XLA
schedules on ICI.

Manual-SPMD design (vs GSPMD auto-sharding) is deliberate: the Pallas flash
kernel must run per-device anyway, pipeline ticks need explicit ppermute,
and explicit collectives make the comm schedule auditable the way the
reference's c_* ops are.

Per-device program (step_local):
  tokens [B/(dp·zr), S/sep] → vocab-parallel embedding (psum over mp)
  → pp pipeline ticks (ppermute ring, AD transposes it for backward)
      each stage: lax.scan over its L/pp blocks
      block: Megatron TP (column qkv/up, row proj/down → 2 psum(mp))
             + Ulysses sequence parallel (all_to_all seq↔heads around
               flash attention when sep>1)
  → vocab-parallel CE (psum over mp), loss psum over (dp,zr,sep[,pp])
  → grads via jax.value_and_grad under shard_map(check_vma=True): the vma
    type system makes AD insert the exact psums the reference's TP layers
    hand-write (mp_layers.py:97,170 identity-fwd/allreduce-bwd pairs) —
    pvary's transpose is psum — so grads arrive fully synced over every
    axis their param is replicated on (dp, sharding, sep, and mp for the
    mp-replicated leaves)
  → ZeRO-2: each rank keeps its 1/zr chunk of the synced grad; XLA's
    reduce-scatter-creator pass fuses the AD psum + own-chunk slice into a
    reduce_scatter on ICI
  → global-norm clip (psum over sharding of chunk norms)
  → Adam on the local 1/zr optimizer-state chunk → all_gather(params)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..profiler.profiler import RecordEvent
from .topology import build_mesh

__all__ = ["HybridEngine", "EngineConfig"]

DATA_AXES = ("dp", "sharding", "ep")   # axes that split the batch
ALL_AXES = ("dp", "pp", "sharding", "sep", "ep", "mp")


def _1f1b_schedule(pp, num_micro):
    """Static 1F1B tick grid (host-side simulation of the reference's
    forward_backward_pipeline state machine, pipeline_parallel.py:81).

    Returns (fwd, bwd): int32 arrays [T, pp] where fwd[t, i] is the
    microbatch stage i runs forward at tick t (-1 = idle), same for bwd.
    Invariants encoded:
      - stage i never holds more than (pp - i) in-flight microbatches
        (the 1F1B memory bound; stage 0 peaks at pp, the last at 1)
      - activations/cotangents travel between stages via ppermute, so a
        dependency must be satisfied in a strictly earlier tick — except
        the last stage, whose backward may consume its own same-tick
        forward output (fwd runs before bwd inside a tick)
    """
    M = num_micro
    fwd_done = [[False] * M for _ in range(pp)]
    bwd_done = [[False] * M for _ in range(pp)]
    fwd_next = [0] * pp
    bwd_next = [0] * pp
    fwd_rows, bwd_rows = [], []
    for _ in range(4 * (M + pp) + 8):
        if all(b >= M for b in bwd_next):
            break
        fwd_t = [-1] * pp
        bwd_t = [-1] * pp
        for i in range(pp):
            m = fwd_next[i]
            if m < M and (m - bwd_next[i]) < (pp - i) and \
                    (i == 0 or fwd_done[i - 1][m]):
                fwd_t[i] = m
        for i in range(pp):
            m = bwd_next[i]
            if m < M:
                if i == pp - 1:
                    ok = fwd_done[i][m] or fwd_t[i] == m
                else:
                    ok = bwd_done[i + 1][m]
                if ok:
                    bwd_t[i] = m
        for i in range(pp):
            if fwd_t[i] >= 0:
                fwd_done[i][fwd_t[i]] = True
                fwd_next[i] += 1
            if bwd_t[i] >= 0:
                bwd_done[i][bwd_t[i]] = True
                bwd_next[i] += 1
        fwd_rows.append(fwd_t)
        bwd_rows.append(bwd_t)
    else:  # pragma: no cover
        raise AssertionError(f"1f1b schedule did not converge pp={pp} M={M}")
    fwd = np.asarray(fwd_rows, np.int32)
    bwd = np.asarray(bwd_rows, np.int32)
    _check_mailboxes(pp, fwd, bwd)
    return fwd, bwd


def _check_mailboxes(pp, fwd, bwd):
    """The device code gives each stage ONE sticky mailbox per direction
    (an activation sent at tick t is readable from t+1 until the sender
    sends again).  Assert the schedule never needs more: a second send
    must not arrive before the first was consumed."""
    T = fwd.shape[0]
    for arr, src_of, dst_of in ((fwd, lambda i: i - 1, lambda i: i + 1),
                                (bwd, lambda i: i + 1, lambda i: i - 1)):
        for i in range(pp):
            j = dst_of(i)
            if not (0 <= j < pp):
                continue
            pending = None   # micro sent by i, not yet consumed by j
            for t in range(T):
                if pending is not None and arr[t][j] == pending[0] \
                        and t > pending[1]:
                    pending = None
                if arr[t][i] >= 0:
                    assert pending is None, (
                        f"mailbox overflow: stage {i} sends micro "
                        f"{arr[t][i]} at tick {t} before stage {j} "
                        f"consumed micro {pending[0]}")
                    pending = (arr[t][i], t)


def _psum_varying(x, axes=ALL_AXES):
    """psum ``x`` over exactly the mesh axes it is device-varying on.

    Under check_vma the varying-axis set lives in the aval; reducing only
    those axes keeps the sum correct whether an upstream collective (e.g.
    parallel CE's psum over 'mp') already de-varied an axis or not."""
    vma = jax.typeof(x).vma
    ax = tuple(a for a in axes if a in vma)
    return jax.lax.psum(x, ax) if ax else x


@dataclasses.dataclass
class EngineConfig:
    lr: float = 1e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    num_microbatches: int = 1       # pipeline microbatches (must be >= pp)
    # ZeRO stage over the "sharding" axis (reference: group_sharded_stage2/3):
    #   2 — optimizer state + grads sharded, bf16 params replicated (the
    #       reduce-scatter + param-allgather path)
    #   3 — additionally shard the params themselves; each block's weights
    #       are all_gather'd just-in-time inside the (rematted) layer scan
    #       and re-gathered in backward (group_sharded_stage3.py:58)
    zero_stage: int = 2
    # gradient accumulation (reference: gradient_merge_optimizer): split the
    #   batch into accum_steps micro-batches, run fwd/bwd per chunk under a
    #   lax.scan, average the fp32 grads, then apply ONE optimizer step
    accum_steps: int = 1
    # optimizer slot dtype: "float32" keeps a full-precision master +
    # moments (the reference Adam's multi_precision=True); "bfloat16"
    # stores master/m/v in bf16 (multi_precision=False parity) — update
    # math still runs in fp32 — cutting steady state from 14 to 8
    # bytes/param so GPT-1.3B-class models fit one 16 GB chip
    opt_dtype: str = "float32"
    # keep a separate master-weight slot (the reference Adam's
    # multi_precision).  None = auto: a master is stored only when
    # opt_dtype differs from the model dtype — when they match, the param
    # IS the master bit-for-bit and a second copy buys nothing (2 fewer
    # bytes/param: the difference between GPT-1.3B-class models fitting
    # one chip's HBM or not)
    master_weights: bool = None
    # fp32 working-set bound (in elements) for the optimizer update:
    # chunks larger than this update window-by-window (in-place
    # fori_loop) so peak HLO-temp memory stays O(window) instead of
    # O(largest leaf).  Default 134M: gpt2-medium's 100M-element leaves
    # go one-shot (windowing measured ~3% step cost), GPT-1.3B's
    # 300-400M leaves split 3-way (~2.7 GB fp32 temps, fits the 1.3B
    # single-chip budget)
    opt_update_window: int = 1 << 27

    # fp32 logits-block budget (elements) for the tied-vocab CE head:
    # above it the head runs in sequence chunks under lax.map +
    # jax.checkpoint so the [b, s, V] fp32 logits/softmax never fully
    # materialize.  Default tuned on v5e: gpt2-medium's 412M-element head
    # is FASTER unchunked (chunking cost it 6.8% throughput) and fits;
    # GPT-1.3B's 824M-element head (3.3 GB fp32 logits) must chunk.
    ce_block_elems: int = 1 << 29
    # pipeline schedule (reference: pipeline_parallel.py forward_backward_
    # pipeline vs the interleaved/GPipe variants; DistributedStrategy
    # pipeline_configs["schedule_mode"]):
    #   "1f1b"  — memory-bounded: each stage holds at most (pp - stage)
    #             in-flight microbatch activations; backward ticks are
    #             interleaved with forward ticks (hand-scheduled vjp)
    #   "gpipe" — fill-then-drain: all num_microbatches activations live
    #             until AD's reverse pass (simplest; O(num_micro) memory)
    pipeline_schedule: str = "1f1b"

    def __post_init__(self):
        if self.opt_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"opt_dtype must be 'float32' or 'bfloat16', got "
                f"{self.opt_dtype!r}")
        if self.pipeline_schedule not in ("1f1b", "gpipe"):
            raise ValueError(
                f"pipeline_schedule must be '1f1b' or 'gpipe', got "
                f"{self.pipeline_schedule!r}")


class HybridEngine:
    def __init__(self, cfg, dp=1, pp=1, sharding=1, sep=1, mp=1,
                 ep=1, engine_cfg: EngineConfig = None, mesh: Mesh = None,
                 devices=None):
        """``cfg``: a model config (GPTConfig trains through GPTAdapter)
        or any distributed.model_adapter.ModelAdapter instance — the
        stage protocol that lets a second architecture train through the
        same engine (reference: fleet.distributed_model wraps any Layer,
        fleet_base.py:937)."""
        from .model_adapter import GPTAdapter, ModelAdapter

        if isinstance(cfg, ModelAdapter):
            self.model = cfg
        else:
            self.model = GPTAdapter(cfg)
        cfg = self.model.cfg
        self.cfg = cfg
        self.ec = engine_cfg or EngineConfig()
        self.dp, self.pp, self.zr, self.sep, self.mp = \
            dp, pp, sharding, sep, mp
        self.ep = ep
        assert cfg.seq_parallel in ("ulysses", "ring"), \
            f"unknown seq_parallel {cfg.seq_parallel!r}"
        if pp > 1:
            assert self.ec.num_microbatches >= pp, \
                "need microbatches >= pp for the pipeline"
        if self.ec.zero_stage >= 3 and sharding > 1:
            assert cfg.hidden % sharding == 0, \
                "ZeRO-3 shards the hidden dim: hidden %% sharding == 0"
            if cfg.moe_experts:
                assert cfg.ffn_hidden % sharding == 0, \
                    "ZeRO-3 MoE shards ffn_hidden over 'sharding'"
        self.model.validate(self)
        self.mesh = mesh if mesh is not None else build_mesh(
            dp=dp, pp=pp, sharding=sharding, sep=sep, mp=mp, ep=ep,
            devices=devices)
        self._step_fn = None

    # ------------------------------------------------------------ shardings
    def param_specs(self):
        """Manual-mode layout from the model adapter: blocks pp-sharded
        on the layer axis, Megatron column/row splits on mp, everything
        else replicated.  ZeRO-3 additionally shards each matrix leaf's
        free dim over 'sharding' (small vectors stay replicated — stage-2
        handles their opt state)."""
        return self.model.param_specs(self)

    def _use_1f1b(self):
        """The 1F1B path serves pp>1 tied-embedding dense models; MoE and
        untied heads fall back to the GPipe tick loop (still correct,
        O(num_micro) activation memory)."""
        return (self.pp > 1 and self.ec.pipeline_schedule == "1f1b"
                and not self.cfg.moe_experts and self.cfg.tie_embeddings)

    # ----------------------------------------------------- ZeRO-3 gathering
    def _z3(self):
        return self.ec.zero_stage >= 3 and self.zr > 1

    @staticmethod
    def _z3_gather_leaf(x, spec, skip_leading=0):
        """all_gather ``x`` along the dim its spec shards over 'sharding'.
        ``skip_leading`` drops leading spec entries already consumed (the
        scan eats the pp-stacked layer dim)."""
        for i, entry in enumerate(tuple(spec)[skip_leading:]):
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            if "sharding" in names:
                return jax.lax.all_gather(x, "sharding", axis=i, tiled=True)
        return x

    def _z3_gather_block(self, bp):
        """JIT param gather for one block (stage-3 pre-forward allgather,
        group_sharded_stage3.py semantics).  Runs INSIDE the remat so
        backward re-gathers instead of keeping full params live."""
        if not self._z3():
            return bp
        specs = self.param_specs()["blocks"]
        return {k: self._z3_gather_leaf(v, specs[k], skip_leading=1)
                for k, v in bp.items()}

    @staticmethod
    def _aux_params(params):
        """The non-"blocks" params (embeddings, norms, heads) — what the
        adapter's embed/head_loss consume."""
        return {k: v for k, v in params.items() if k != "blocks"}

    def _aux_gathered(self, aux):
        """aux params with stage-3 shards gathered (JIT, inside remat/vjp
        scopes so backward re-gathers instead of keeping them live)."""
        if not self._z3():
            return aux
        specs = self.param_specs()
        return {k: self._z3_gather_leaf(v, specs[k])
                for k, v in aux.items()}

    # Slot storage geometry: each rank's flat chunk is padded to a multiple
    # of _SLOT_LANE and stored as [..., rows, _SLOT_LANE].  The trailing
    # 2-d block keeps a dense TPU tiling — a trailing [1, chunk] bf16
    # array gets sublane-pair tiling (2, 1) with the pair dim unfilled,
    # silently DOUBLING its HBM footprint (measured: 17.16 GiB of step
    # arguments for GPT-1.3B where 9.8 GiB were designed).
    _SLOT_LANE = 512

    def _chunk_elems(self, n, z3=False):
        """Per-rank flat chunk length for an n-element leaf (lane-padded).
        z3 leaves are already sharded — no zr division."""
        c = n if z3 else -(-n // self.zr)
        return -(-c // self._SLOT_LANE) * self._SLOT_LANE

    def _adam_window(self, C):
        """Largest lane-multiple window <= opt_update_window that divides
        the C-element chunk evenly (C == window means: update in one
        shot).  Falls back to one shot when C only factors into too many
        windows — GPT dims are power-of-two rich, so in practice the
        split is 2^k."""
        Wmax = max(int(self.ec.opt_update_window), self._SLOT_LANE)
        if C <= Wmax:
            return C
        rows = C // self._SLOT_LANE
        k = -(-C // Wmax)
        while k <= min(rows, 256) and rows % k:
            k += 1
        if k > min(rows, 256):
            return C
        return C // k

    def _has_master(self):
        if self.ec.master_weights is not None:
            return self.ec.master_weights
        return self.ec.opt_dtype != self.cfg.dtype

    def _slot_keys(self):
        return ("m", "v", "master") if self._has_master() else ("m", "v")

    def batch_spec(self):
        return P(DATA_AXES, "sep")

    # ---------------------------------------------------------------- init
    def init(self, seed=0):
        """Build sharded params + optimizer state (master + moments per
        opt_dtype/master_weights, each ZeRO-sharded over 'sharding')."""
        specs = self.param_specs()

        def make_params(key):
            return self.model.init(key)

        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, P))
        params = jax.jit(make_params, out_shardings=shardings)(
            jax.random.key(seed))

        opt_state = self._init_opt(params)
        return params, opt_state

    def _opt_jdt(self):
        return (jnp.bfloat16 if self.ec.opt_dtype == "bfloat16"
                else jnp.float32)

    @staticmethod
    def _leaf_axes(spec):
        names = set()
        for entry in spec:
            if entry is None:
                continue
            if isinstance(entry, (tuple, list)):
                names.update(entry)
            else:
                names.add(entry)
        return names

    def _opt_leaf_spec(self, spec):
        names = self._leaf_axes(spec)
        # slot layout [pp?, mp-or-ep?, zr, rows, lane]; no leaf carries
        # both mp and ep (experts are not tensor-parallel)
        second = "mp" if "mp" in names else ("ep" if "ep" in names else None)
        s = P("pp" if "pp" in names else None, second, "sharding", None, None)
        return {k: s for k in self._slot_keys()}

    def opt_specs(self):
        specs = self.param_specs()
        return {
            "step": P(),
            "slots": jax.tree_util.tree_map(
                self._opt_leaf_spec, specs,
                is_leaf=lambda x: isinstance(x, P)),
        }

    def _slot_shape(self, chunk):
        return (1, 1, 1, chunk // self._SLOT_LANE, self._SLOT_LANE)

    def _param_chunk(self, p_local, z3, dtype=None):
        """This rank's lane-padded flat chunk of a param leaf."""
        n = int(np.prod(p_local.shape))
        chunk = self._chunk_elems(n, z3)
        flat = p_local.reshape(-1)
        if dtype is not None:
            flat = flat.astype(dtype)
        if z3:
            return jnp.pad(flat, (0, chunk - n))
        flat = jnp.pad(flat, (0, self.zr * chunk - n))
        # local zr axis is mapped over 'sharding': pick own row (axis_index
        # even at zr==1 so the result is sharding-varying, matching the
        # opt spec's 'sharding' entry under check_vma)
        idx = jax.lax.axis_index("sharding")
        return jax.lax.dynamic_slice_in_dim(
            flat.reshape(self.zr, chunk), idx, 1, axis=0)[0]

    def _init_opt(self, params):
        """Opt state is built per LOCAL param shard (ZeRO chunks partition
        the local flattened param).  Leaf layout: [pp?, mp?, zr, rows,
        lane] (see _SLOT_LANE)."""
        from jax import shard_map

        specs = self.param_specs()
        odt = self._opt_jdt()
        has_master = self._has_master()

        def init_local(params_local):
            def build(p_local, spec):
                z3 = self._z3() and "sharding" in self._leaf_axes(spec)
                n = int(np.prod(p_local.shape))
                chunk = self._chunk_elems(n, z3)
                shape = self._slot_shape(chunk)
                z = jnp.zeros(shape, odt)
                slot = {"m": z, "v": z}
                if has_master:
                    slot["master"] = self._param_chunk(
                        p_local, z3, odt).reshape(shape)
                return slot

            return jax.tree_util.tree_map(build, params_local, specs)

        slots_specs = jax.tree_util.tree_map(
            self._opt_leaf_spec, specs, is_leaf=lambda x: isinstance(x, P))
        mapped = shard_map(init_local, mesh=self.mesh, in_specs=(specs,),
                           out_specs=slots_specs, check_vma=True)
        state = jax.jit(mapped)(params)
        return {"step": self.step_counter(0), "slots": state}

    def step_counter(self, value):
        """The optimizer's step count, placed as the jitted step returns
        it (replicated over the mesh).  A plain host scalar here is a
        different jit-cache key from the step's own output, so the SECOND
        train step would retrace and recompile the whole program."""
        return jax.device_put(jnp.asarray(value, jnp.int32),
                              NamedSharding(self.mesh, P()))

    # ------------------------------------------------ opt-state canonical
    # The optimizer's [pp?, mp/ep?, zr, chunk] flat-chunk layout is
    # topology-dependent; checkpoints store the TOPOLOGY-NEUTRAL form:
    # m/v/master as param-shaped global arrays.  dist_saver/converter
    # (auto_parallel/converter.py) solve the same problem by re-sharding
    # host-side; here both directions are one shard_map program.

    def opt_canonical(self):
        """Returns a jitted (slots, params) → {'m','v','master'} trees of
        param-shaped global arrays."""
        from jax import shard_map

        specs = self.param_specs()
        zr = self.zr

        odt = self._opt_jdt()

        def local(slots, params_local):
            def un(slot_leaf, p_local, spec):
                flat = slot_leaf[0, 0, 0].reshape(-1)
                if not (self._z3() and "sharding" in self._leaf_axes(spec)):
                    # scatter-own-chunk + psum = the varying→invariant
                    # all_gather (same idiom as the step's param rebuild)
                    chunk = flat.shape[0]
                    idx = jax.lax.axis_index("sharding")
                    full = jnp.zeros((zr * chunk,), flat.dtype)
                    full = jax.lax.dynamic_update_slice(
                        full, flat, (idx * chunk,))
                    flat = jax.lax.psum(full, "sharding")
                n = int(np.prod(p_local.shape))
                return flat[:n].reshape(p_local.shape)

            is_slot = lambda x: isinstance(x, dict) and \
                set(x) == set(self._slot_keys())
            out = {}
            for name in self._slot_keys():
                out[name] = jax.tree_util.tree_map(
                    lambda s, p, sp, name=name: un(s[name], p, sp),
                    slots, params_local, specs, is_leaf=is_slot)
            if not self._has_master():
                # master-less mode: the param IS the master bit-for-bit
                out["master"] = jax.tree_util.tree_map(
                    lambda p: p.astype(odt), params_local)
            return out

        out_specs = {k: specs for k in ("m", "v", "master")}
        slots_specs = jax.tree_util.tree_map(
            self._opt_leaf_spec, specs, is_leaf=lambda x: isinstance(x, P))
        mapped = shard_map(local, mesh=self.mesh,
                           in_specs=(slots_specs, specs),
                           out_specs=out_specs, check_vma=True)
        return jax.jit(mapped)

    def opt_from_canonical(self):
        """Inverse: param-shaped m/v/master → this engine's chunked slots
        (the _init_opt layout on THIS mesh/zr/zero_stage)."""
        from jax import shard_map

        specs = self.param_specs()
        zr = self.zr

        odt = self._opt_jdt()

        def local(canon):
            def chunk(val, spec):
                z3 = self._z3() and "sharding" in self._leaf_axes(spec)
                n = int(np.prod(val.shape))
                c = self._chunk_elems(n, z3)
                shape = self._slot_shape(c)
                if z3:
                    return jnp.pad(val.reshape(-1).astype(odt),
                                   (0, c - n)).reshape(shape)
                flat = jnp.pad(val.reshape(-1).astype(odt),
                               (0, zr * c - n))
                idx = jax.lax.axis_index("sharding")
                mine = jax.lax.dynamic_slice_in_dim(
                    flat.reshape(zr, c), idx, 1, axis=0)
                return mine.reshape(shape)

            def build(m, v, master, spec):
                slot = {"m": chunk(m, spec), "v": chunk(v, spec)}
                if self._has_master():
                    slot["master"] = chunk(master, spec)
                return slot

            return jax.tree_util.tree_map(
                build, canon["m"], canon["v"], canon["master"], specs)

        slots_specs = jax.tree_util.tree_map(
            self._opt_leaf_spec, specs, is_leaf=lambda x: isinstance(x, P))
        in_specs = {k: specs for k in ("m", "v", "master")}
        mapped = shard_map(local, mesh=self.mesh, in_specs=(in_specs,),
                           out_specs=slots_specs, check_vma=True)
        return jax.jit(mapped)

    def state_template(self):
        """Shape/dtype/sharding templates for (params, canonical-opt)
        WITHOUT allocating anything — the restore target for
        checkpoint.load_engine_state on this topology."""
        import types

        specs = self.param_specs()
        shapes = jax.eval_shape(self.model.init, jax.random.key(0))

        def tmpl(sds, spec, dtype=None):
            return types.SimpleNamespace(
                shape=tuple(sds.shape), dtype=dtype or sds.dtype,
                sharding=NamedSharding(self.mesh, spec))

        params_t = jax.tree_util.tree_map(
            tmpl, shapes, specs,
            is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
        odt = self._opt_jdt()
        canon_t = {
            name: jax.tree_util.tree_map(
                lambda s, sp: tmpl(s, sp, odt), shapes, specs,
                is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
            for name in ("m", "v", "master")
        }
        return params_t, canon_t

    # ------------------------------------------------------- forward pieces
    def _embed(self, params, tokens):
        return self.model.embed(
            self, self._aux_gathered(self._aux_params(params)), tokens)

    def _embed_core(self, wte, wpe, tokens):
        """Vocab-parallel embedding + position embedding.
        tokens: [b, s_local]; wte local (gathered over z3): [V/mp, D]."""
        cfg, mp, sep = self.cfg, self.mp, self.sep
        vpp = cfg.vocab_size // mp
        mp_idx = jax.lax.axis_index("mp") if mp > 1 else 0
        local_ids = tokens - mp_idx * vpp
        in_shard = (local_ids >= 0) & (local_ids < vpp)
        safe = jnp.clip(local_ids, 0, vpp - 1)
        emb = jnp.take(wte, safe, axis=0)
        emb = jnp.where(in_shard[..., None], emb, 0.0)
        # vma-driven: real psum at mp>1, free varying→invariant type cast
        # at mp==1 (a size-1 axis still marks values mp-varying, which
        # would poison fixed-carry scans downstream)
        emb = _psum_varying(emb, ("mp",))
        s_local = tokens.shape[1]
        sep_idx = jax.lax.axis_index("sep") if sep > 1 else 0
        pos = jax.lax.dynamic_slice_in_dim(
            wpe, sep_idx * s_local, s_local, axis=0)
        return (emb + pos).astype(self.cfg.jdtype())

    def _attention(self, q, k, v, causal=True):
        """Flash attention with sequence parallelism (Ulysses or ring).
        q/k/v: [B, H_local, s_local, hd]."""
        sep = self.sep
        if sep > 1 and self.cfg.seq_parallel == "ring":
            from ..kernels.ring_attention import ring_attention

            return ring_attention(q, k, v, "sep", causal=causal)
        if sep > 1:
            # all_to_all: gather sequence, scatter heads → [B, H/sep, S, hd]
            q, k, v = (jax.lax.all_to_all(t, "sep", split_axis=1,
                                          concat_axis=2, tiled=True)
                       for t in (q, k, v))
        out = self._flash(q, k, v, causal)
        if sep > 1:
            out = jax.lax.all_to_all(out, "sep", split_axis=2, concat_axis=1,
                                     tiled=True)
        return out

    def _flash(self, q, k, v, causal=True):
        from ..kernels.flash_attention import (flash_attention,
                                               flash_attention_available)

        if self.cfg.use_flash and flash_attention_available(q, k, v, None,
                                                            causal=causal):
            return flash_attention(q, k, v, causal=causal)
        from ..ops.attention import _naive_attention

        return _naive_attention(q, k, v, causal=causal, training=False)

    def _stage(self, blocks_local, x, key=None):
        """Scan this pipeline stage's blocks with per-block remat.
        Returns (x, aux_sum) — the stage's summed MoE aux loss.  ``key``
        (optional) drives dropout; each block folds its GLOBAL layer index
        so stages never share masks, and remat replays identical masks in
        backward (explicit key = the reference's RNG-state preservation)."""
        from .recompute import checkpoint_policy

        block_fn = lambda bp, x, k: self.model.block(
            self, self._z3_gather_block(bp), x, k)
        if self.cfg.remat != "nothing":
            block_fn = jax.checkpoint(
                block_fn, policy=checkpoint_policy(self.cfg.remat),
                prevent_cse=False)

        n_local = self.cfg.num_layers // self.pp
        layer0 = (jax.lax.axis_index("pp") * n_local) if self.pp > 1 else 0

        def body(carry, xs):
            x, aux_sum = carry
            bp, i = xs
            k = (jax.random.fold_in(key, layer0 + i)
                 if key is not None else None)
            x, aux = block_fn(bp, x, k)
            return (x, aux_sum + aux), None

        # blocks are pp-varying, so each block application makes the carry
        # pp-varying: lift the init to keep scan's carry type fixed
        if "pp" not in jax.typeof(x).vma:
            x = jax.lax.pcast(x, ("pp",), to="varying")
        aux0 = jnp.zeros((), jnp.float32) + 0.0 * x.mean().astype(jnp.float32)
        (out, aux_sum), _ = jax.lax.scan(
            body, (x, aux0), (blocks_local, jnp.arange(n_local)))
        return out, aux_sum

    def tied_vocab_ce(self, x, wte, labels):
        """Chunked vocab-parallel CE against the (tied) embedding —
        the shared loss-head building block for model adapters.
        x: [b, s_local, D]; wte local: [V/mp, D]; labels: [b, s_local]
        with -100 = ignore.  Returns (sum_loss, count)."""
        with jax.named_scope("ce_head"):
            return self._tied_vocab_ce(x, wte, labels)

    def _tied_vocab_ce(self, x, wte, labels):
        mp = self.mp
        from .mp_layers import parallel_cross_entropy

        def ce_chunk(xc, lc):
            logits = jnp.einsum("bsd,vd->bsv", xc,
                                wte).astype(jnp.float32)
            if mp > 1:
                loss_tok = parallel_cross_entropy(logits, lc, mp_axis="mp")
            else:
                logp = jax.nn.log_softmax(logits, axis=-1)
                safe = jnp.maximum(lc, 0)
                loss_tok = -jnp.take_along_axis(
                    logp, safe[..., None], -1)[..., 0]
            mask = (lc != -100).astype(jnp.float32)
            # de-vary mp: at mp==1 the tied wte is typed mp-varying and
            # would otherwise mark the loss mp-varying too
            return _psum_varying((loss_tok * mask).sum(), ("mp",)), \
                mask.sum()

        b, s, _ = x.shape
        v_local = wte.shape[0]
        nchunk = 1
        while (b * s * v_local) // nchunk > self.ec.ce_block_elems \
                and s % (2 * nchunk) == 0:
            nchunk *= 2
        if nchunk == 1:
            return ce_chunk(x, labels)
        sc = s // nchunk
        xc = x.reshape(b, nchunk, sc, x.shape[-1]).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, nchunk, sc).transpose(1, 0, 2)
        # checkpoint: backward re-runs the chunk (one extra head matmul)
        # instead of keeping each chunk's fp32 softmax residuals live
        s_sum, c_sum = jax.lax.map(
            jax.checkpoint(lambda a: ce_chunk(*a), prevent_cse=False),
            (xc, lc))
        return s_sum.sum(), c_sum.sum()

    def _aux_mean(self, aux):
        """Reduce a per-shard MoE aux loss to the global batch value: SUM
        over pp (stages partition the layers) and MEAN over the data/seq
        shards (each gates a disjoint token slice), matching gpt_loss's
        full-batch aux (models/gpt.py:270-273)."""
        vma = jax.typeof(aux).vma
        total = _psum_varying(aux)
        denom = 1
        for name, size in (("dp", self.dp), ("sharding", self.zr),
                           ("ep", self.ep), ("sep", self.sep),
                           ("mp", self.mp)):
            if name in vma:
                denom *= size
        return total / denom

    # --------------------------------------------------- 1F1B (hand vjp)
    def _head_raw(self, aux_raw, y, labels):
        """Adapter head over UN-gathered aux params (z3 gather inside, so
        vjp emits shard-formed cotangents directly)."""
        return self.model.head_loss(self, self._aux_gathered(aux_raw), y,
                                    labels)

    def _embed_raw(self, aux_raw, tokens, key):
        """Adapter embedding over UN-gathered aux params + per-micro
        embed dropout (inside the vjp'd fn so backward recomputes it)."""
        x = self.model.embed(self, self._aux_gathered(aux_raw), tokens)
        if key is not None:
            from ..models.gpt import _dropout

            x = _dropout(x, self.cfg.dropout, key)
        return x

    def _pipeline_1f1b(self, params, tokens, labels, key=None):
        """(loss, grads) via the memory-bounded 1F1B pipeline schedule.

        The GPipe tick loop (_local_loss) leaves the backward to AD, so
        every microbatch's stage input stays live until the reverse scan:
        O(num_microbatches) activation memory.  Here backward ticks are
        hand-scheduled (reference: forward_backward_pipeline,
        pipeline_parallel.py:81): each stage keeps a ring buffer of at
        most pp saved stage INPUTS, and a backward tick re-runs the stage
        under jax.vjp from the saved input (stage-granular recompute —
        the same total compute as remat='full', which is how the
        BASELINE-class configs run anyway).  Activations ride the forward
        ppermute ring; cotangents ride the reverse ring.

        The CE denominator (global non-ignored token count) is computed
        from labels BEFORE the loop, so each microbatch's head cotangent
        seed (1/total_cnt) is exact and backward can start mid-pipeline.

        Params consumed inside the tick conds are pre-lifted to the full
        carry vma (see the GPipe note below) AND to the data axes, so
        per-micro pullbacks accumulate device-local grads without
        inserting per-tick psums; grads are synced to their param's vma
        once, after the loop."""
        cfg, pp = self.cfg, self.pp
        assert not cfg.moe_experts and cfg.tie_embeddings, \
            "pipeline_schedule='1f1b' supports tied-embedding dense " \
            "models (use pipeline_schedule='gpipe' for MoE/untied)"
        M = self.ec.num_microbatches
        b, s_local = tokens.shape
        assert b % M == 0, "local batch must divide microbatches"
        mb = b // M
        D = cfg.hidden
        x_dtype = cfg.jdtype()

        pp_idx = jax.lax.axis_index("pp")
        fwd_np, bwd_np = _1f1b_schedule(pp, M)
        fwd_sched = jnp.asarray(fwd_np)
        bwd_sched = jnp.asarray(bwd_np)
        T = fwd_np.shape[0]
        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
        bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]

        from ..core.vma import lift_to, lifter, vma_of

        carry_axes = tuple(sorted(set(jax.typeof(tokens).vma) | {"pp"}))
        lift = lifter(*carry_axes)
        ltree = lambda t: jax.tree_util.tree_map(lift, t)

        def zlike(p):
            # grad accumulator: varying over the param's own axes (mp/…)
            # PLUS the carry axes, so the scan carry type is fixed from
            # tick 0 and per-micro pullbacks stay psum-free
            return lift_to(jnp.zeros_like(p),
                           tuple(sorted(set(vma_of(p)) | set(carry_axes))))

        # global CE denominator, known before the pipeline runs
        cnt_local = (labels != -100).astype(jnp.float32).sum()
        denom = jnp.maximum(_psum_varying(cnt_local), 1.0)
        seed = lift(1.0 / denom)

        blocks_l = ltree(params["blocks"])
        # ONE lifted dict of all non-block params: the embed and the head
        # each vjp against the whole dict (unused leaves get zero
        # cotangents), so tied leaves — e.g. GPT's wte in both embed and
        # head — accumulate into a single gradient with no special-casing
        aux_l = ltree(self._aux_params(params))
        tok_mb_l = lift(tokens.reshape(M, mb, s_local))
        lab_mb_l = lift(labels.reshape(M, mb, s_local))

        def stage_fn(bl, x, k):
            y, _aux = self._stage(bl, x, k)
            return y

        def zero_act():
            return lift(jnp.zeros((mb, s_local, D), x_dtype))

        zeros_g_bl = jax.tree_util.tree_map(zlike, params["blocks"])
        zeros_g_aux = jax.tree_util.tree_map(zlike, self._aux_params(params))
        zero = lambda: lift(jnp.zeros((), jnp.float32))

        def tick(carry, t):
            ring, x_next, ct_next, g_bl, g_aux, loss_sum = carry
            frow = jax.lax.dynamic_index_in_dim(fwd_sched, t, 0,
                                                keepdims=False)
            brow = jax.lax.dynamic_index_in_dim(bwd_sched, t, 0,
                                                keepdims=False)
            my_f = jnp.take(frow, pp_idx)
            my_b = jnp.take(brow, pp_idx)
            mf = jnp.clip(my_f, 0, M - 1)
            mbi = jnp.clip(my_b, 0, M - 1)
            kf = (jax.random.fold_in(key, mf) if key is not None else None)
            kb = (jax.random.fold_in(key, mbi) if key is not None else None)
            kef = (jax.random.fold_in(kf, 999983)
                   if key is not None else None)
            keb = (jax.random.fold_in(kb, 999983)
                   if key is not None else None)

            # ---------------- forward tick ----------------
            def run_fwd(ring, x_next):
                x0 = jax.lax.cond(
                    pp_idx == 0,
                    lambda: lift(self._embed_raw(aux_l, tok_mb_l[mf],
                                                 kef)),
                    lambda: x_next)
                y = lift(stage_fn(blocks_l, x0, kf))
                ring = jax.lax.dynamic_update_index_in_dim(
                    ring, x0, mf % pp, 0)
                return y, ring

            y, ring = jax.lax.cond(
                my_f >= 0, run_fwd, lambda r, xn: (zero_act(), r),
                ring, x_next)

            # ---------------- backward tick ----------------
            lab_b = lab_mb_l[mbi]
            x_saved = jax.lax.dynamic_index_in_dim(ring, mbi % pp, 0,
                                                   keepdims=False)

            def run_bwd(y, ct_next, g_bl, g_aux, loss_sum):
                # last stage: build the cotangent from the head's vjp at
                # this tick's own forward output (the schedule guarantees
                # my_b == my_f there); other stages take the arrived one
                def head_ct(y):
                    (s_m, c_m), pull = jax.vjp(
                        lambda a_, y_: self._head_raw(a_, y_, lab_b),
                        aux_l, y)
                    da, dy = pull((seed, jnp.zeros_like(c_m)))
                    return lift(dy), ltree(da), lift(s_m)

                def recv_ct(y):
                    return ct_next, zeros_g_aux, zero()

                dy, da, s_m = jax.lax.cond(pp_idx == pp - 1, head_ct,
                                           recv_ct, y)
                loss_sum = loss_sum + s_m
                g_aux = jax.tree_util.tree_map(jnp.add, g_aux, da)
                # stage vjp at the saved input (stage-granular recompute)
                _, pull = jax.vjp(
                    lambda bl, x: stage_fn(bl, x, kb), blocks_l, x_saved)
                dbl, dx = pull(dy)
                g_bl = jax.tree_util.tree_map(jnp.add, g_bl, ltree(dbl))
                dx = lift(dx)

                # first stage: fold the input cotangent into the
                # embedding's params instead of sending it further back
                def emb_bwd(dx):
                    _, epull = jax.vjp(
                        lambda a_: self._embed_raw(a_, tok_mb_l[mbi],
                                                   keb), aux_l)
                    (de,) = epull(dx)
                    return ltree(de)

                de = jax.lax.cond(pp_idx == 0, emb_bwd,
                                  lambda dx: zeros_g_aux, dx)
                g_aux = jax.tree_util.tree_map(jnp.add, g_aux, de)
                return dx, g_bl, g_aux, loss_sum

            dx_send, g_bl, g_aux, loss_sum = jax.lax.cond(
                my_b >= 0, run_bwd,
                lambda y, c, a, b_, c_: (zero_act(), a, b_, c_),
                y, ct_next, g_bl, g_aux, loss_sum)

            # sticky mailboxes: latch the arrived value ONLY when the
            # schedule says the sender was active this tick — an idle
            # sender's ppermute carries zeros and must not clobber a
            # not-yet-consumed activation (at pp>=3 the 1F1B in-flight
            # bound makes stages idle mid-stream; _check_mailboxes proves
            # one slot per direction is enough)
            x_arr = jax.lax.ppermute(y, "pp", fwd_perm)
            ct_arr = jax.lax.ppermute(dx_send, "pp", bwd_perm)
            x_from = jnp.take(frow, (pp_idx - 1) % pp) >= 0
            ct_from = jnp.take(brow, (pp_idx + 1) % pp) >= 0
            x_next = jnp.where(x_from, x_arr, x_next)
            ct_next = jnp.where(ct_from, ct_arr, ct_next)
            return (ring, x_next, ct_next, g_bl, g_aux, loss_sum), None

        ring0 = lift(jnp.zeros((pp, mb, s_local, D), x_dtype))
        carry0 = (ring0, zero_act(), zero_act(), zeros_g_bl, zeros_g_aux,
                  zero())
        (ring, _, _, g_bl, g_aux, loss_sum), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T))

        grads = dict(g_aux)
        grads["blocks"] = g_bl

        def sync(g, p):
            extra = tuple(a for a in jax.typeof(g).vma
                          if a not in jax.typeof(p).vma)
            return jax.lax.psum(g, extra) if extra else g

        grads = jax.tree_util.tree_map(sync, grads, params)
        loss = _psum_varying(loss_sum) / denom
        return loss, grads

    # ---------------------------------------------------------- loss (SPMD)
    def _local_loss(self, params, tokens, labels, key=None):
        """Per-device loss: pipeline over pp, everything else TP/SP local.
        ``key``: dropout key, already folded with the data-axis coords
        (mp-invariant, data-varying)."""
        cfg, pp = self.cfg, self.pp
        num_micro = self.ec.num_microbatches if pp > 1 else 1
        x = self._embed(params, tokens)          # [b, s_local, D]
        if key is not None:
            from ..models.gpt import _dropout

            x = _dropout(x, cfg.dropout, jax.random.fold_in(key, 999983))
        b = x.shape[0]
        assert b % num_micro == 0, "local batch must divide microbatches"
        mb = b // num_micro

        if pp == 1:
            out, aux = self._stage(params["blocks"], x, key)
            s, c = self.model.head_loss(
                self, self._aux_gathered(self._aux_params(params)), out,
                labels)
            total = _psum_varying(jnp.stack([s, c]))
            loss = total[0] / jnp.maximum(total[1], 1.0)
            if cfg.moe_experts:
                loss = loss + cfg.moe_aux_weight * self._aux_mean(aux) \
                    / cfg.num_layers
            return loss

        # ---- pipeline ticks (GPipe-fill then drain; backward is the AD
        # transpose of the ppermute ring = reverse pipeline) ----
        pp_idx = jax.lax.axis_index("pp")
        x_mb = x.reshape(num_micro, mb, *x.shape[1:])
        lab_mb = labels.reshape(num_micro, mb, labels.shape[1])
        num_ticks = num_micro + pp - 1
        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

        # carry init must already have the vma the loop body produces
        # (scan requires fixed carry avals; pvary lifts the zeros)
        from ..core.vma import lifter

        carry_axes = tuple(sorted(set(jax.typeof(x).vma) | {"pp"}))
        # cond branches must agree on the varying-axis type; values like
        # label-derived counts lack pp/mp while stage outputs carry them
        lift = lifter(*carry_axes)

        state0 = lift(jnp.zeros((mb,) + x.shape[1:], x.dtype))
        zero = lambda: lift(jnp.zeros((), jnp.float32))
        # CRITICAL: every pp-invariant value consumed INSIDE a cond branch
        # must be lifted to pp-varying OUT HERE — otherwise AD places the
        # de-varying psum over 'pp' inside the branch, where only the live
        # stages execute it → collective mismatch at runtime.  Lifting
        # outside puts the transpose psum on the all-ranks path.
        hp = jax.tree_util.tree_map(
            lift, self._aux_gathered(self._aux_params(params)))
        lab_mb_l = lift(lab_mb)

        def tick(carry, t):
            state, loss_sum, cnt_sum, aux_sum = carry
            inp = x_mb[jnp.clip(t, 0, num_micro - 1)]
            state = jnp.where(pp_idx == 0, inp, state)
            # a stage holds REAL data at tick t iff pp_idx <= t < pp_idx +
            # num_micro.  Bubble ticks SKIP the stage via lax.cond — legal
            # because the predicate varies only over 'pp', so every member
            # of an mp/sep/ep group takes the same branch and the TP
            # collectives inside the stage stay collective-safe.  This is
            # the fill-drain schedule's bubble compute, eliminated.
            is_live = (t >= pp_idx) & (t - pp_idx < num_micro)

            def live_stage(s):
                # mask depends on (microbatch, global layer): fold the
                # microbatch this stage holds at tick t
                k = (jax.random.fold_in(key, jnp.clip(t - pp_idx, 0,
                                                      num_micro - 1))
                     if key is not None else None)
                ys, a = self._stage(params["blocks"], s, k)
                return lift(ys), lift(a)

            y, aux = jax.lax.cond(
                is_live, live_stage, lambda s: (lift(s), zero()), state)
            aux_sum = aux_sum + aux
            m = t - (pp - 1)
            # the vocab-sized loss head runs ONLY on the last stage's live
            # output ticks (same pp-only-varying predicate argument)
            is_out = (pp_idx == pp - 1) & (m >= 0)
            lab = lab_mb_l[jnp.clip(m, 0, num_micro - 1)]

            def live_head(yy, ll):
                s_, c_ = self.model.head_loss(self, hp, yy, ll)
                return lift(s_), lift(c_)

            s, c = jax.lax.cond(
                is_out, live_head, lambda yy, ll: (zero(), zero()), y, lab)
            loss_sum = loss_sum + s
            cnt_sum = cnt_sum + c
            state = jax.lax.ppermute(y, "pp", fwd_perm)
            return (state, loss_sum, cnt_sum, aux_sum), None
        (state, loss_sum, cnt_sum, aux_sum), _ = jax.lax.scan(
            tick, (state0, zero(), zero(), zero()), jnp.arange(num_ticks))
        total = _psum_varying(jnp.stack([loss_sum, cnt_sum]))
        loss = total[0] / jnp.maximum(total[1], 1.0)
        if cfg.moe_experts:
            # aux_sum holds num_micro full passes over the layers: psum over
            # pp collects the stages, /num_micro averages the microbatches
            loss = loss + cfg.moe_aux_weight \
                * (self._aux_mean(aux_sum) / num_micro) / cfg.num_layers
        return loss

    # ------------------------------------------------------------- the step
    def _step_local(self, params, opt_state, tokens, labels, lr, seed):
        ec, zr = self.ec, self.zr
        accum = ec.accum_steps
        if self._use_1f1b():
            grad_fn = self._pipeline_1f1b
        else:
            grad_fn = jax.value_and_grad(self._local_loss)
        if self.cfg.dropout > 0.0:
            # distinct masks per data shard (fold each data-axis coord),
            # IDENTICAL masks across mp (never folded) — the reference's
            # local_seed/global_seed split (parallel_layers/random.py:32).
            # The optimizer step counter is folded in so a plain loop that
            # never passes dropout_seed still gets fresh masks every step.
            key = jax.random.fold_in(jax.random.key(seed),
                                     opt_state["step"])
            for ax, size in (("dp", self.dp), ("sharding", self.zr),
                             ("ep", self.ep), ("sep", self.sep)):
                if size > 1:
                    key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        else:
            key = None

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_slots = treedef.flatten_up_to(opt_state["slots"])
        flat_specs = treedef.flatten_up_to(self.param_specs())
        paths = [
            "/".join(str(getattr(k, "key", k)) for k in kp)
            for kp, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        zr_idx = jax.lax.axis_index("sharding")
        z3_leaf = [self._z3() and "sharding" in self._leaf_axes(s)
                   for s in flat_specs]

        def to_chunks(grads, dtype=jnp.float32):
            """ZeRO chunking per leaf.

            check_vma AD already psum'd every grad over the axes its param
            is replicated on — the vma type of each grad equals its
            param's.  Each rank keeps its own 1/zr chunk; XLA's
            reduce-scatter-creator fuses the AD all-reduce with this slice
            into a reduce_scatter over 'sharding'.  stage-3 leaves arrive
            already reduce-scattered (the all_gather transpose).

            ``dtype=None`` keeps each grad's own dtype — the single-step
            (accum=1) path uses it so bf16 grads stay bf16 end to end:
            the global-norm clip holds EVERY chunk live at once, and a
            blanket fp32 cast doubles that footprint (the difference
            between GPT-1.3B fitting one 16 GB chip or not); Adam's math
            upcasts per leaf anyway."""
            flat_g = treedef.flatten_up_to(grads)
            chunks = []
            for g, z3 in zip(flat_g, z3_leaf):
                dt = dtype or g.dtype
                n = int(np.prod(g.shape))
                chunk = self._chunk_elems(n, z3)
                if z3:
                    chunks.append(jnp.pad(g.reshape(-1).astype(dt),
                                          (0, chunk - n)))
                    continue
                gf = jnp.pad(g.reshape(-1).astype(dt),
                             (0, zr * chunk - n))
                chunks.append(jax.lax.dynamic_slice_in_dim(
                    gf.reshape(zr, chunk), zr_idx, 1, axis=0)[0])
            return chunks

        if accum == 1:
            with jax.named_scope("forward_backward"):
                loss, grads = grad_fn(params, tokens, labels, key)
                g_chunks = to_chunks(grads, dtype=None)
        else:
            # gradient merge (reference: gradient_merge_optimizer): scan
            # accum chunks of the local batch.  The carry holds only each
            # rank's 1/zr grad chunks, so per-iteration comm stays a
            # reduce_scatter and grad memory stays ZeRO-sharded.
            b = tokens.shape[0]
            assert b % accum == 0, "local batch must divide accum_steps"
            tok = tokens.reshape(accum, b // accum, tokens.shape[1])
            lab = labels.reshape(accum, b // accum, labels.shape[1])

            def acc_body(carry, xs):
                loss_sum, gsum = carry
                k = (jax.random.fold_in(key, xs[2])
                     if key is not None else None)
                with jax.named_scope("forward_backward"):
                    l, g = grad_fn(params, xs[0], xs[1], k)
                    gc = to_chunks(g)
                with jax.named_scope("grad_accumulate"):
                    return (loss_sum + l,
                            tuple(a + c for a, c in zip(gsum, gc))), None

            def chunk_zero(p, z3):
                n = int(np.prod(p.shape))
                size = self._chunk_elems(n, z3)
                vma = tuple(sorted(set(jax.typeof(p).vma) | {"sharding"}))
                return jax.lax.pcast(jnp.zeros((size,), jnp.float32), vma,
                                     to="varying")

            g0 = tuple(chunk_zero(p, z3)
                       for p, z3 in zip(flat_p, z3_leaf))
            (loss_sum, g_chunks), _ = jax.lax.scan(
                acc_body, (jnp.zeros((), jnp.float32), g0),
                (tok, lab, jnp.arange(accum)))
            with jax.named_scope("grad_accumulate"):
                loss = loss_sum / accum
                g_chunks = [g / accum for g in g_chunks]

        with jax.named_scope("optimizer"):
            new_params, new_opt = self._apply_grads(
                treedef, paths, flat_p, flat_slots, z3_leaf, zr_idx,
                g_chunks, opt_state["step"] + 1, lr)
        return new_params, new_opt, loss

    def _apply_grads(self, treedef, paths, flat_p, flat_slots, z3_leaf,
                     zr_idx, g_chunks, step, lr):
        """The optimizer's half of ``_step_local``: global-norm clip,
        Adam on each rank's chunks (windowed), weight decay, and the
        params rebuilt from the updated chunks.  Everything is flat, in
        ``treedef``'s leaf order.  Returns ``(params, opt_state)``."""
        ec, zr = self.ec, self.zr

        # --- global-norm clip over the sharded chunks ---
        # per-leaf vma-aware reduce: an mp-sharded leaf's chunks must be
        # summed over mp (disjoint shards) while an mp-replicated leaf's
        # must not (that would overcount by mp) — the reference's
        # HybridParallelClipGrad makes the same is_distributed distinction
        # (hybrid_parallel_optimizer.py:45)
        if ec.grad_clip and ec.grad_clip > 0:
            gn_sq = sum(_psum_varying(jnp.sum(jnp.square(
                            g.astype(jnp.float32))))
                        for g in g_chunks)
            gnorm = jnp.sqrt(gn_sq)
            scale = jnp.minimum(1.0, ec.grad_clip / jnp.maximum(gnorm, 1e-12))
            # keep each chunk's dtype: fp32 scale would promote bf16
            # chunks and double the all-chunks-live footprint
            g_chunks = [(g * scale).astype(g.dtype) for g in g_chunks]

        # --- Adam on local chunks + weight decay + allgather params ---
        new_flat_p, new_flat_slots = [], []
        b1, b2 = ec.beta1, ec.beta2
        stepf = step.astype(jnp.float32)
        odt = self._opt_jdt()
        has_master = self._has_master()
        bc1 = 1 - jnp.power(b1, stepf)
        bc2 = 1 - jnp.power(b2, stepf)
        for path, p, slots, g, z3 in zip(paths, flat_p, flat_slots, g_chunks,
                                         z3_leaf):
            decay = ec.weight_decay
            decay_on = bool(decay) and self.model.decay_this(path)
            w_store = (slots["master"] if has_master
                       else self._param_chunk(p, z3))

            def adam_win(g_w, m_w, v_w, w_w, p_dtype=p.dtype,
                         decay_on=decay_on):
                """One window of the update — math in fp32 regardless of
                storage dtype; returns storage-dtype results."""
                gf = g_w.astype(jnp.float32)
                m = b1 * m_w.astype(jnp.float32) + (1 - b1) * gf
                v = b2 * v_w.astype(jnp.float32) + (1 - b2) * gf * gf
                wf = w_w.astype(jnp.float32)
                upd = (m / bc1) / (jnp.sqrt(v / bc2) + ec.eps)
                if decay_on:
                    upd = upd + decay * wf
                w_new = wf - lr * upd
                out = (m.astype(odt), v.astype(odt),
                       w_new.astype(p_dtype))
                if has_master:
                    out = out + (w_new.astype(odt),)
                return out

            # the update runs NATIVELY on the [.., rows, lane] slot shape:
            # elementwise math is shape-agnostic, and flattening the 5-d
            # slots first would RETILE-copy every operand (T(8,128) ->
            # 1-d tiling is a physical copy on TPU — 6 x leaf-size of
            # pure copy traffic per step).  Only the grad chunk (born
            # flat) and the outgoing param chunk cross layouts.
            shape5 = slots["m"].shape
            C = int(np.prod(shape5))
            g5 = g.reshape(shape5)
            m5, v5 = slots["m"], slots["v"]
            w5 = w_store if has_master else w_store.reshape(shape5)
            W = self._adam_window(C)
            if W == C:
                outs = adam_win(g5, m5, v5, w5)
            else:
                # window along the rows axis with a fori_loop of dynamic
                # slices, updating the buffers IN PLACE: fp32 temps stay
                # O(window) and — unlike a pad+reshape+lax.map — no
                # stacked copy of g/m/v/w ever materializes (measured:
                # 6 x 768 MB of copies for a 302M-element leaf)
                wr = W // self._SLOT_LANE
                if w5.dtype == p.dtype:
                    w_out0 = w5
                else:
                    # fresh output buffer must already carry the vma the
                    # windows written into it will have (fori_loop needs
                    # a fixed carry type)
                    from ..core.vma import lift_to, vma_of

                    w_out0 = lift_to(jnp.zeros(shape5, p.dtype),
                                     vma_of(w5, g5))
                bufs0 = (m5, v5, w_out0) + ((w5,) if has_master else ())

                def win_body(i, bufs):
                    # reads come from the CARRY (windows are disjoint and
                    # each is read before it is written), so the original
                    # arrays are not loop operands and XLA can update the
                    # buffers genuinely in place
                    lo = i * wr
                    sl = lambda x: jax.lax.dynamic_slice_in_dim(
                        x, lo, wr, axis=3)
                    w_src = bufs[3] if has_master else bufs[2]
                    new = adam_win(sl(g5), sl(bufs[0]), sl(bufs[1]),
                                   sl(w_src))
                    return tuple(
                        jax.lax.dynamic_update_slice_in_dim(b, n, lo,
                                                            axis=3)
                        for b, n in zip(bufs, new))

                outs = jax.lax.fori_loop(0, C // W, win_body, bufs0)
            m_new, v_new = outs[0], outs[1]
            w_param = outs[2].reshape(-1)

            if z3:
                # stage-3: the param stays sharded — the updated chunk IS
                # the new local param (no allgather; the forward gathers
                # JIT).  Slice off the lane padding.
                n = int(np.prod(p.shape))
                new_p = w_param[:n].reshape(p.shape)
            elif zr == 1:
                # chunk == full param: psum over the size-1 axis is the
                # type-level varying→invariant cast and compiles to a copy
                n = int(np.prod(p.shape))
                new_p = jax.lax.psum(w_param, "sharding")[:n].reshape(
                    p.shape)
            else:
                # rebuild the full param (in its own dtype — the chunks
                # are disjoint, so combining via scatter+psum adds only
                # zeros and is exact in any dtype): psum is the only
                # varying→invariant cast, so this is the type-correct
                # all_gather
                full = jnp.zeros((zr * C,), w_param.dtype)
                full = jax.lax.dynamic_update_slice(
                    full, w_param, (zr_idx * C,))
                full = jax.lax.psum(full, "sharding")
                n = int(np.prod(p.shape))
                new_p = full[:n].reshape(p.shape)
            new_flat_p.append(new_p)
            shape5 = slots["m"].shape
            slot_new = {"m": m_new.reshape(shape5),
                        "v": v_new.reshape(shape5)}
            if has_master:
                slot_new["master"] = outs[3].reshape(shape5)
            new_flat_slots.append(slot_new)

        new_params = jax.tree_util.tree_unflatten(treedef, new_flat_p)
        new_slots = jax.tree_util.tree_unflatten(treedef, new_flat_slots)
        return new_params, {"step": step, "slots": new_slots}

    # ------------------------------------------------------------ build/jit
    def build_step(self):
        if self._step_fn is not None:
            return self._step_fn
        from jax import shard_map

        specs = self.param_specs()
        opt_specs = self.opt_specs()
        mapped = shard_map(
            self._step_local, mesh=self.mesh,
            in_specs=(specs, opt_specs, self.batch_spec(), self.batch_spec(),
                      P(), P()),
            out_specs=(specs, opt_specs, P()),
            check_vma=True,
        )
        # watchdog-wrapped: the hybrid step is the training hot loop —
        # one config compiles once; a recompile means a tokens/labels
        # shape or dtype drifted and the watchdog names the culprit
        from ..observability.compile_watchdog import watch

        self._step_fn = watch(jax.jit(mapped, donate_argnums=(0, 1)),
                              name="hybrid_engine::step")
        return self._step_fn

    def step(self, params, opt_state, tokens, labels, lr=None,
             dropout_seed=0):
        """One hybrid-parallel train step.  ``dropout_seed`` varies the
        dropout masks per step (ignored when cfg.dropout == 0)."""
        fn = self.build_step()
        lr = jnp.asarray(lr if lr is not None else self.ec.lr, jnp.float32)
        seed = jnp.asarray(dropout_seed, jnp.uint32)
        if fn.abstract_args is None:
            # batch and sequence are known only now: the first call, which
            # compiles anyway, describes the program by its operands'
            # shapes and shardings (``instruction_table``); no lowering
            fn.describe(params, opt_state, tokens, labels, lr, seed)
        # the host's part of a step: the dispatch (enqueue), not the wait
        with RecordEvent("hybrid_engine::step"):
            return fn(params, opt_state, tokens, labels, lr, seed)

    # ----------------------------------------------------------- eval/debug
    def loss_fn_reference(self, params_host, tokens, labels):
        """Single-device reference loss for parity tests (same math, no
        parallelism): delegates to the model adapter's functional form."""
        return self.model.reference_loss(params_host, tokens, labels)

    def gather_params(self, params):
        """Fetch full (host) params pytree from sharded arrays."""
        return jax.tree_util.tree_map(lambda a: jax.device_get(a), params)
