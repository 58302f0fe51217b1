"""Sharded checkpointing with cross-topology restore.

Reference parity: auto_parallel/dist_saver.py (per-rank shard dump) +
auto_parallel/converter.py (re-shard a checkpoint saved under one
(dp, mp, pp, sharding) layout onto a different one) + framework/io.py
``paddle.save/load`` semantics for the engine's state.

TPU-native design: what the reference does with host-side slice/concat
bookkeeping, jax does with array metadata — every saved shard records its
global index window, and restore builds the target-topology arrays with
``jax.make_array_from_callback``: XLA/jax asks for exactly the slices the
NEW sharding needs and the loader assembles them from whichever saved
shards overlap.  The optimizer's flat-chunk layout is converted through
the engine's topology-neutral canonical form (engine.opt_canonical /
opt_from_canonical — one shard_map program each way).

Layout on disk:
  <path>/manifest.json             tree structure, specs, mesh, step
  <path>/<leaf-id>/shard<k>.npy    one file per saved device shard

Crash safety: every shard is written through the resilience layer's
atomic tmp+rename helper with a running CRC32 recorded in its manifest
entry, and the manifest itself is written LAST (atomically) — so a
manifest's presence implies every shard it names was fully on disk
first.  ``resilience.CheckpointManager`` adds the directory-level
commit (step dir rename), retention, and checksum-verified restore
with fallback; the named fault sites below are what its
crash-consistency tests kill the process at.
"""
from __future__ import annotations

import json
import os
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..resilience.atomic import atomic_write
from ..resilience.faults import fault_point
from ..resilience.retry import Deadline

__all__ = ["save_sharded", "load_sharded", "save_engine_state",
           "load_engine_state", "CommitBarrier", "CommitBarrierError"]


# ------------------------------------------------------ commit barrier


class CommitBarrierError(RuntimeError):
    """The multi-host commit barrier did not complete: a rank failed to
    ack its shards (or the committer died) within the timeout.  The
    checkpoint was NOT committed — ``latest()`` still names the
    previous step on every rank."""


class CommitBarrier:
    """Multi-host checkpoint commit coordination over TCPStore.

    The single-process commit point (one ``os.replace``) does not
    survive multiple hosts: each host writes only its *addressable*
    shards, so a manifest committed by rank 0 while rank 3 is still
    writing (or dead) would name shards that never hit the shared
    filesystem.  The barrier serializes the commit:

    1. every rank writes its shards, then :meth:`ack`\\ s its shard
       CRCs (fault site ``checkpoint.shard_ack`` fires *before* the
       ack is published — a ``stall`` there is a slow rank, a ``kill``
       a rank dying pre-ack);
    2. rank 0's :meth:`commit` waits for all ``world_size`` acks, fires
       ``checkpoint.before_barrier_commit``, runs the commit function
       (the ``os.replace``), and publishes the committed marker;
    3. every other rank's :meth:`commit` blocks on that marker.

    A rank killed before its ack starves step 2: rank 0 times out with
    :class:`CommitBarrierError`, nothing is renamed, and ``latest()``
    on every survivor still resolves the previous checkpoint.  Tokens
    are generation-qualified (:meth:`begin`), so a retried save of the
    same step cannot be satisfied by a dead attempt's stale acks.
    """

    def __init__(self, store, rank, world_size, timeout=30.0,
                 key_prefix="ckpt_commit"):
        self.store = store
        self.rank = int(rank)
        self.world_size = int(world_size)
        self.timeout = float(timeout)
        self.key_prefix = key_prefix
        self._lock = threading.Lock()
        self._gen = {}       # guarded-by: self._lock  token -> generation
        self._acks = {}      # guarded-by: self._lock  token -> {rank: crcs}
        self._state = {}     # guarded-by: self._lock  token -> phase str

    def _key(self, token, gen, leaf):
        return f"{self.key_prefix}/{token}/g{int(gen)}/{leaf}"

    def begin(self, token, prepare=None):
        """Open a commit attempt for ``token``; returns its generation.

        Rank 0 bumps the generation counter, runs ``prepare`` (e.g.
        pre-cleaning a tmp directory — done HERE so no peer is mid-write
        in it yet), and publishes the generation; other ranks block on
        it before touching shared paths."""
        if self.rank == 0:
            gen = self.store.add(f"{self.key_prefix}/{token}/gen", 1)
            if prepare is not None:
                prepare()
            self.store.set(f"{self.key_prefix}/{token}/open",
                           str(gen))
        else:
            # ONE Deadline spans the whole join — the blocking get and
            # the stale-generation re-poll share it, so a dead rank 0
            # costs exactly self.timeout, never a stacked multiple,
            # and the miss surfaces as a CommitBarrierError (the
            # protocol's failure type), not a raw store timeout
            dl = Deadline(self.timeout)
            while True:   # lint-ok: bounded-retries Deadline-bounded poll
                try:
                    raw = self.store.get(
                        f"{self.key_prefix}/{token}/open",
                        blocking=True, timeout=dl.remaining())
                except TimeoutError:
                    raise CommitBarrierError(
                        f"commit barrier {token!r}: rank 0 never "
                        f"opened a generation within "
                        f"{self.timeout}s") from None
                gen = int(raw)
                with self._lock:
                    stale = self._gen.get(token)
                # a generation already committed or aborted is a DEAD
                # attempt's leftover (this process may have restarted
                # since): wait for rank 0 to open a fresh one
                if (stale is None or gen > stale) \
                        and not self._finished(token, gen):
                    break
                if dl.expired():
                    raise CommitBarrierError(
                        f"commit barrier {token!r}: no new generation "
                        f"within {self.timeout}s (stuck at g{gen})")
                dl.sleep(0.005)
        with self._lock:
            self._gen[token] = gen
            self._state[token] = "open"
        return gen

    def _finished(self, token, gen):
        for leaf in ("committed", "aborted"):
            try:
                self.store.get(self._key(token, gen, leaf),
                               blocking=False)
                return True
            except KeyError:
                pass
        return False

    def _abort(self, token, gen, why):
        """Mark a generation terminally failed so a later retry's
        joiners cannot mistake its leftovers for a live attempt; safe
        to race with a commit (joiners check committed first, and a
        set here never un-renames anything)."""
        try:
            self.store.set(self._key(token, gen, "aborted"), why)
        except (OSError, RuntimeError):
            pass    # silent-ok: best-effort tombstone while failing anyway
        with self._lock:
            self._state[token] = "failed"

    def _generation(self, token):
        with self._lock:
            gen = self._gen.get(token)
        if gen is None:
            gen = self.begin(token)
        return gen

    def ack(self, token, crcs):
        """Publish this rank's shard-CRC digest for ``token``.  The
        fault site fires BEFORE the store write: a fault here models a
        rank that finished writing shards but never told anyone."""
        gen = self._generation(token)
        fault_point("checkpoint.shard_ack")
        self.store.set(self._key(token, gen, f"ack/rank_{self.rank}"),
                       json.dumps({"rank": self.rank,
                                   "crcs": dict(crcs or {})}))
        with self._lock:
            self._state[token] = "acked"

    def _collect_acks(self, token, gen):
        """Gather every rank's ack under ONE shared Deadline: each get
        polls only the *remaining* budget (an expired deadline is one
        non-blocking probe, then abort) — previously every straggler
        after expiry still bought itself a fresh minimum wait, so a
        wedged fleet overshot the timeout by O(world_size)."""
        acks = {}
        dl = Deadline(self.timeout)
        for r in range(self.world_size):
            try:
                raw = self.store.get(
                    self._key(token, gen, f"ack/rank_{r}"),
                    blocking=True, timeout=dl.remaining())
            except (KeyError, TimeoutError):
                self._abort(token, gen, f"rank {r} never acked")
                raise CommitBarrierError(
                    f"commit barrier {token!r} (g{gen}): rank {r} never "
                    f"acked its shards within {self.timeout}s — "
                    f"checkpoint NOT committed") from None
            acks[r] = json.loads(raw)
        return acks

    def commit(self, token, fn=None):
        """Complete the barrier.  Rank 0: wait for every rank's ack,
        fire ``checkpoint.before_barrier_commit``, run ``fn`` (THE
        commit — e.g. the directory/manifest ``os.replace``), publish
        the committed marker, and return the collected acks.  Other
        ranks: block on the marker (``fn`` is ignored); timeout raises
        :class:`CommitBarrierError` with nothing committed anywhere."""
        gen = self._generation(token)
        if self.rank == 0:
            acks = self._collect_acks(token, gen)
            with self._lock:
                self._acks[token] = {r: a.get("crcs", {})
                                     for r, a in acks.items()}
            fault_point("checkpoint.before_barrier_commit")
            if fn is not None:
                fn()
            self.store.set(self._key(token, gen, "committed"),
                           json.dumps(sorted(acks)))
            with self._lock:
                self._state[token] = "committed"
            return acks
        try:
            self.store.get(self._key(token, gen, "committed"),
                           blocking=True, timeout=self.timeout)
        except (KeyError, TimeoutError):
            self._abort(token, gen, "commit marker never appeared")
            raise CommitBarrierError(
                f"commit barrier {token!r} (g{gen}): commit marker "
                f"never appeared within {self.timeout}s — rank 0 died "
                f"or a peer never acked; previous checkpoint remains "
                f"current") from None
        with self._lock:
            self._state[token] = "committed"
        return None

    def status(self):
        """Introspection snapshot (exporter/debug surface)."""
        with self._lock:
            return {"rank": self.rank, "world_size": self.world_size,
                    "tokens": dict(self._state),
                    "acked_ranks": {t: sorted(a)
                                    for t, a in self._acks.items()}}


def _leaf_id(path_str):
    return path_str.replace("/", ".")


def _np_dtype(name):
    """np.dtype that understands jax's extended dtypes (bfloat16 etc.)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _tree_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in kp)
             for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return flat, treedef, paths


def _index_to_json(index, shape):
    out = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def save_sharded(path, tree, step=None, extra=None, rank=None,
                 barrier=None):
    """Save a pytree of (possibly sharded) jax arrays: one .npy per
    addressable device shard + a manifest of index windows.  Duplicate
    windows (replicated axes) are written once.

    Multi-process: each process writes ONLY its addressable shards into
    rank-prefixed files and its own ``manifest.<rank>.json``
    (dist_saver's per-rank dump); loading unions every rank's manifest.

    ``barrier`` (a :class:`CommitBarrier`) makes the manifest commit
    globally consistent: every rank lands its manifest as a
    ``.pending`` file (invisible to :func:`load_sharded`'s glob), acks
    its shard CRCs through the store, and rank 0 renames ALL pending
    manifests to their final names only after the full ack set arrived
    — a rank killed pre-ack leaves the directory manifest-less (or the
    previous checkpoint's manifests intact) on every host.  ``rank``
    overrides ``jax.process_index()`` (multi-host simulation in tests;
    defaults to the barrier's rank when one is given)."""
    if rank is None:
        rank = barrier.rank if barrier is not None \
            else jax.process_index()
    rank = int(rank)
    tag = f"r{rank}"
    os.makedirs(path, exist_ok=True)
    flat, treedef, paths = _tree_paths(tree)

    def _write_shard(fpath, array):
        """One shard, atomically, returning the CRC32 of its bytes."""
        fault_point("checkpoint.before_shard", path=fpath)
        with atomic_write(fpath, "wb",
                          site="checkpoint.shard_write") as f:
            np.save(f, np.asarray(array))
            crc = f.crc32
        return crc

    leaves = []
    for pstr, arr in zip(paths, flat):
        arr = jnp.asarray(arr)
        lid = _leaf_id(pstr)
        ldir = os.path.join(path, lid)
        os.makedirs(ldir, exist_ok=True)
        shards, seen = [], set()
        if hasattr(arr, "addressable_shards") and arr.addressable_shards:
            # window → lowest owning process; only that process writes it,
            # so replicated leaves cost one copy total, not one per host
            owners = {}
            for g in getattr(arr, "global_shards", arr.addressable_shards):
                w = tuple(map(tuple, _index_to_json(g.index, arr.shape)))
                pidx = g.device.process_index
                owners[w] = min(owners.get(w, pidx), pidx)
            for shard in arr.addressable_shards:
                win = tuple(map(tuple, _index_to_json(shard.index,
                                                      arr.shape)))
                if win in seen or owners.get(win, rank) != rank:
                    continue
                seen.add(win)
                fname = f"shard{tag}_{len(shards)}.npy"
                crc = _write_shard(os.path.join(ldir, fname), shard.data)
                shards.append({"file": fname, "crc32": crc,
                               "index": [list(w) for w in win]})
        else:
            fname = f"shard{tag}_0.npy"
            crc = _write_shard(os.path.join(ldir, fname), arr)
            shards.append({"file": fname, "crc32": crc,
                           "index": _index_to_json(
                               (slice(None),) * arr.ndim, arr.shape)})
        leaves.append({"path": pstr, "id": lid,
                       "shape": list(arr.shape), "dtype": str(arr.dtype),
                       "shards": shards})
    manifest = {
        "format": "paddle_tpu.sharded_checkpoint.v2",   # v2: shard crc32
        "leaves": leaves,          # structure is restored via leaf paths
        "step": None if step is None else int(step),
        "extra": extra or {},
    }
    # written LAST and atomically: a readable manifest implies complete
    # shards (the commit point within this directory)
    fault_point("checkpoint.before_manifest", path=path)
    final_name = os.path.join(path, f"manifest.{rank}.json")
    if barrier is None:
        with atomic_write(final_name, "w",
                          site="checkpoint.manifest_write") as f:
            json.dump(manifest, f, indent=1)
        return manifest
    # barrier mode: manifests stay .pending (load_sharded cannot see
    # them) until rank 0 has every rank's CRC ack — then ONE rank
    # renames them all, atomically each, as THE commit
    with atomic_write(final_name + ".pending", "w",
                      site="checkpoint.manifest_write") as f:
        json.dump(manifest, f, indent=1)
    crcs = {f"{l['id']}/{s['file']}": s["crc32"]
            for l in leaves for s in l["shards"]}
    token = os.path.basename(os.path.normpath(path))
    barrier.ack(token, crcs)
    barrier.commit(token, fn=lambda: _commit_pending_manifests(path))
    return manifest


def _commit_pending_manifests(path):
    """Rank 0's barrier commit: publish every rank's pending manifest
    (each rename atomic; all shards are already acked on disk)."""
    import glob

    for pend in sorted(glob.glob(
            os.path.join(path, "manifest.*.json.pending"))):
        os.replace(pend, pend[:-len(".pending")])


def _load_manifest(path):
    """Union every rank's manifest (rank 0 provides the metadata)."""
    import glob

    files = sorted(glob.glob(os.path.join(path, "manifest.*.json")))
    if not files:
        # pre-multiprocess layout
        with open(os.path.join(path, "manifest.json")) as f:
            return json.load(f)
    with open(files[0]) as f:
        manifest = json.load(f)
    by_path = {l["path"]: l for l in manifest["leaves"]}
    for fn in files[1:]:
        with open(fn) as f:
            other = json.load(f)
        for leaf in other["leaves"]:
            mine = by_path.get(leaf["path"])
            if mine is None:
                manifest["leaves"].append(leaf)
                by_path[leaf["path"]] = leaf
                continue
            seen = {tuple(map(tuple, s["index"])) for s in mine["shards"]}
            for s in leaf["shards"]:
                if tuple(map(tuple, s["index"])) not in seen:
                    mine["shards"].append(s)
    return manifest


def _read_window(path, leaf, want_index):
    """Assemble the requested global-index window from the saved shards."""
    shape = leaf["shape"]
    want = []
    for sl, dim in zip(want_index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        want.append((start, stop))
    out = np.empty([b - a for a, b in want], dtype=_np_dtype(leaf["dtype"]))
    filled = 0
    for sh in leaf["shards"]:
        win = sh["index"]
        # overlap of want and win, in both coordinate frames
        src_sel, dst_sel, ok = [], [], True
        for (wa, wb), (sa, sb) in zip(want, win):
            lo, hi = max(wa, sa), min(wb, sb)
            if lo >= hi:
                ok = False
                break
            src_sel.append(slice(lo - sa, hi - sa))
            dst_sel.append(slice(lo - wa, hi - wa))
        if not ok:
            continue
        data = np.load(os.path.join(path, leaf["id"], sh["file"]))
        want_dt = _np_dtype(leaf["dtype"])
        if data.dtype != want_dt:
            # np.load returns raw void ('|V2') for ml_dtypes extended
            # dtypes (bfloat16 …): reinterpret via the manifest dtype
            data = data.view(want_dt)
        out[tuple(dst_sel)] = data[tuple(src_sel)]
        filled += int(np.prod([s.stop - s.start for s in dst_sel]))
    if filled < out.size:
        raise ValueError(
            f"checkpoint leaf {leaf['path']}: saved shards cover only "
            f"{filled}/{out.size} of the requested window")
    return out


def load_sharded(path, like_tree=None, shardings=None):
    """Load a sharded checkpoint.

    like_tree: a pytree with the SAME structure whose leaves carry target
    ``.sharding`` (e.g. the new engine's freshly-initialized state) — each
    leaf is rebuilt with make_array_from_callback so only the slices the
    new topology needs are read.  Without it, full host arrays return in a
    path→array dict.
    """
    manifest = _load_manifest(path)
    by_path = {l["path"]: l for l in manifest["leaves"]}

    if like_tree is None:
        return {p: _read_window(
            path, l, (slice(None),) * len(l["shape"]))
            for p, l in by_path.items()}, manifest

    flat, treedef, paths = _tree_paths(like_tree)
    out = []
    for pstr, ref in zip(paths, flat):
        leaf = by_path.get(pstr)
        if leaf is None:
            raise KeyError(f"checkpoint has no leaf {pstr!r}")
        if tuple(leaf["shape"]) != tuple(ref.shape):
            raise ValueError(
                f"leaf {pstr}: checkpoint shape {leaf['shape']} != target "
                f"{tuple(ref.shape)} — cross-topology restore reshards, it "
                f"does not reshape")
        sharding = ref.sharding
        arr = jax.make_array_from_callback(
            tuple(leaf["shape"]), sharding,
            lambda idx, leaf=leaf: _read_window(path, leaf, idx))
        out.append(arr.astype(ref.dtype))
    return jax.tree_util.tree_unflatten(treedef, out), manifest


# ---------------------------------------------------- engine state facade


def save_engine_state(path, engine, params, opt_state):
    """Save a HybridEngine's full training state topology-neutrally:
    params as-is (global arrays), optimizer via the canonical form."""
    canon = engine.opt_canonical()(opt_state["slots"], params)
    tree = {"params": params, "opt": canon}
    return save_sharded(path, tree, step=int(opt_state["step"]),
                        extra={"kind": "hybrid_engine"})


def load_engine_state(path, engine):
    """Restore onto ``engine``'s (possibly different) topology; returns
    (params, opt_state) ready for engine.step.  Target layouts come from
    shape-level templates — nothing is allocated besides the loaded
    state itself."""
    params_t, canon_t = engine.state_template()
    like = {"params": params_t, "opt": canon_t}
    tree, manifest = load_sharded(path, like_tree=like)
    slots = engine.opt_from_canonical()(tree["opt"])
    opt_state = {"step": engine.step_counter(manifest["step"] or 0),
                 "slots": slots}
    return tree["params"], opt_state
