"""JIT compile watchdog — the ragged-shape regression detector.

Unintended XLA recompilation is the silent TPU throughput killer: one
ragged batch (a tail batch, an un-padded prompt, a dtype drift) and a
"compiles once" step quietly compiles every call.  The watchdog wraps
the repo's ``jax.jit`` entry points (hapi ``_build_jit_step``, the
inference predictors, the serving engine's unified step, the hybrid
engine's train step, jit.to_static) and

- counts compilations and calls per function (labelled counters
  ``jit_compiles_total{fn=...}`` / ``jit_recompiles_total{fn=...}`` in
  the default :class:`~paddle_tpu.observability.metrics.MetricsRegistry`),
- records compile wall-time per function and, when the backend exposes
  it, HLO cost analysis (flops / bytes accessed) for the compiled
  program,
- logs a WARNING with the per-argument shape/dtype **diff** whenever a
  function recompiles after warmup (the first compile of a function is
  warmup and logs nothing; repeated same-signature calls log nothing).

Opt-in: wrapping is always installed but dormant — a disabled watchdog
adds one attribute check per call.  Enable per process with
:func:`enable_compile_watchdog` (or ``PADDLE_TPU_COMPILE_WATCHDOG=1`` in
the environment), scoped with ``with watchdog_enabled(): ...``.

A *compilation* is detected as a first-seen argument signature (the
pytree of shapes/dtypes + static values) — exactly jax.jit's executable
cache key, so the count matches XLA's behavior without reaching into
jax internals.  Compile wall-time is the first call's wall time (trace +
compile + run; on real programs run time is noise next to compile time).

**What a watched program is made of** (works with the watchdog disabled):
an engine that builds a step also hands :func:`watch` the step's
*abstract* arguments (``ShapeDtypeStruct``s with the real arrays'
shardings; :func:`abstract_like` makes them from a call's operands), and
the watchdog keeps, per watched name and for the life of the process,
only the jitted function and those shapes — no array, no engine; the
newest registration of a name wins.  Registering compiles and lowers
nothing.  :func:`instruction_table` then lowers with the shapes and
compiles — in the process that ran the step jax's own lowering cache
hands back the executable that ran (0.06 to 0.6 s on a v5e, no compile
logged); elsewhere a compile, or a read of the persistent compile cache —
and maps every instruction of ``compiled.as_text()`` that runs as a device
operation to the ``op_name`` path jax recorded for it — the
``jax.named_scope``s, transformations and primitive that made it::

    # after any jax.profiler trace of a serving or a training process
    table = instruction_table("serving::unified_step")   # or
    table = instruction_table("hybrid_engine::step")
    table["add_add_fusion.14"]
    # 'jit(_step)/jit(step)/while/body/closed_call/mlp/tf,fd->td/dot_general'

The trace's operation names are these instruction names, so the table
turns a profile into device time by scope: :func:`named_scopes` /
:func:`innermost_scope` give the scopes on a path, :func:`pass_of` says
whether it is the ``forward``, the ``recompute`` (a ``jax.checkpoint``
replay) or the ``backward`` pass of a differentiated program, and
:func:`leaf_primitive` the primitive (``dot_general``, ``scatter``,
``pallas_call``).  ``benchmark/scope_share.py`` reads it for the per-layer
metrics; it replaces the hand-made operation dumps of PRs 27 to 35.
"""
from __future__ import annotations

import contextlib
import logging
import os
import re
import threading
import time

__all__ = ["CompileWatchdog", "watch", "default_watchdog",
           "enable_compile_watchdog", "disable_compile_watchdog",
           "watchdog_enabled", "abstract_like", "instruction_table",
           "parse_instruction_table", "named_scopes", "innermost_scope",
           "pass_of", "leaf_primitive"]

logger = logging.getLogger("paddle_tpu.observability")


def _aval_str(leaf):
    """f32[8,128]-style rendering of one signature leaf."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return repr(leaf)
    short = {"float32": "f32", "float64": "f64", "bfloat16": "bf16",
             "float16": "f16", "int32": "i32", "int64": "i64",
             "int8": "i8", "uint32": "u32", "bool": "pred"}
    dt = short.get(str(dtype), str(dtype))
    return f"{dt}[{','.join(str(d) for d in shape)}]"


def _signature(args, kwargs):
    """((path, aval-string), ...) over the flattened call operands — the
    jit cache key rendered human-readably, so the stored signature IS the
    diffable artifact."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    return tuple((jax.tree_util.keystr(path), _aval_str(leaf))
                 for path, leaf in flat)


def _sig_diff(old, new):
    """Human-readable per-argument diff between two signatures."""
    old_d, new_d = dict(old), dict(new)
    lines = []
    for path, aval in new_d.items():
        prev = old_d.get(path)
        if prev is None:
            lines.append(f"  {path}: (new) {aval}")
        elif prev != aval:
            lines.append(f"  {path}: {prev} -> {aval}")
    for path, aval in old_d.items():
        if path not in new_d:
            lines.append(f"  {path}: {aval} -> (gone)")
    if not lines:
        lines.append("  (argument structure changed)")
    return "\n".join(lines)


def _cost_analysis(fn, args, kwargs, allow_compile=False):
    """flops/bytes from XLA's cost analysis when the backend exposes it;
    None otherwise.  Reads the Lowered stage (a retrace, no second
    compile); the ``lowered.compile()`` fallback is gated behind
    ``allow_compile`` because a second compile of a big program can cost
    minutes.  Never raises."""
    try:
        lowered = fn.lower(*args, **kwargs)
    except Exception:
        return None
    getters = [lambda: lowered.cost_analysis()]
    if allow_compile:
        getters.append(lambda: lowered.compile().cost_analysis())
    for get in getters:
        try:
            ca = get()
        except Exception:
            continue    # silent-ok: cost analysis is optional telemetry
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            continue
        out = {}
        if "flops" in ca:
            out["flops"] = float(ca["flops"])
        for key in ("bytes accessed", "bytes_accessed"):
            if key in ca:
                out["bytes_accessed"] = float(ca[key])
        if out:
            return out
    return None


# ---- what a compiled program is made of ---------------------------------

def abstract_like(tree):
    """``tree`` with every array leaf as a ``ShapeDtypeStruct``: its shape,
    dtype and weak type, and its sharding where the array is committed to
    one (an uncommitted array lowers as an unspecified sharding does) —
    what ``jit(...).lower`` needs to lower the program a call with these
    operands compiles, and nothing that holds a buffer."""
    import jax

    def leaf(x):
        aval = jax.typeof(x)
        sharding = getattr(x, "sharding", None)
        if isinstance(x, jax.Array) and not x.committed:
            sharding = None
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                    sharding=sharding,
                                    weak_type=aval.weak_type)

    return jax.tree_util.tree_map(leaf, tree)


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_CALLEE = re.compile(r"\b(calls|to_apply)=%([^\s,)}]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def parse_instruction_table(text):
    """``{instruction name: op_name path}`` from ``compiled.as_text()``,
    for every instruction that runs as a device operation: those of the
    entry computation, of loop bodies and conditions, of branches and of
    called computations — not the insides of a fusion or of a reducer
    (``calls=`` of a ``fusion``, ``to_apply=`` of anything but a
    ``call``), which never appear in a trace.  Instruction names are
    unique in a module.  A fusion that XLA left without metadata (the
    in-place page scatters of a large step) takes the path of its fused
    computation's root, else of the first instruction inside that has
    one; any other instruction without metadata maps to ``""``."""
    computations, roots, inside, fused = {}, {}, set(), {}
    current = None                      # the computation being read
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = head.group(1)
            computations[current] = {}
            continue
        ins = _INSTRUCTION.match(line)
        if ins is None or current is None:
            continue
        name = ins.group(1)
        at = line.rfind(" metadata={")
        found = _OP_NAME.search(line, at) if at >= 0 else None
        # XLA joins the paths of instructions it merged with ";"
        path = found.group(1).split(";")[0] if found else ""
        computations[current][name] = path
        if line.lstrip().startswith("ROOT "):
            roots[current] = path
        for attr, callee in _CALLEE.findall(line):
            if attr == "calls" and " fusion(" in line:
                inside.add(callee)
                if not path:
                    fused[name] = callee
            elif attr == "to_apply" and " call(" not in line:
                inside.add(callee)
    table = {}
    for computation, instructions in computations.items():
        if computation not in inside:
            table.update(instructions)
    for name, callee in fused.items():
        if name in table:
            table[name] = roots.get(callee) or next(
                (p for p in computations.get(callee, {}).values() if p), "")
    return table


# path components that jax's transformations and control flow put on an
# ``op_name`` path: everything else before the primitive is a scope
_STRUCTURAL = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "shard_map"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_IDENTIFIER = re.compile(r"^[A-Za-z_][\w.\-]*$")


def _components(path):
    """The path split at the slashes that are outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return [c for c in out if c]


def leaf_primitive(path):
    """The primitive that made the instruction: the path's last component
    (``dot_general``, ``scatter``, ``pallas_call``); ``""`` for ``""``."""
    parts = _components(path)
    return parts[-1] if parts else ""


_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")


def _scope_of(component):
    """The named scope one path component carries, or ``None``: the
    component itself, or what a transformation wraps (a scope opened
    directly under ``grad`` reads ``jvp(ce_head)`` and
    ``transpose(jvp(ce_head))``); a ``jit(f)`` carries a function's name,
    not a scope."""
    wrapped = _WRAPPED.match(component)
    while wrapped:
        if wrapped.group(1) in ("jit", "pjit"):
            return None
        component = wrapped.group(2)
        wrapped = _WRAPPED.match(component)
    if (_IDENTIFIER.match(component) and component not in _STRUCTURAL
            and not _BRANCH.match(component)):
        return component
    return None


def named_scopes(path):
    """The ``jax.named_scope``s on an ``op_name`` path, outermost first:
    its components before the primitive, but for transformations
    (``jit(f)``, ``jvp()``, ``vmap()``), control flow's own (``while``,
    ``body``, ``closed_call``, ``branch_1_fun``, ``checkpoint``, ...) and
    an einsum's spec."""
    found = (_scope_of(c) for c in _components(path)[:-1])
    return tuple(c for c in found if c)


def innermost_scope(path, names):
    """The innermost of the scopes ``names`` on ``path``, or ``None``."""
    for c in reversed(named_scopes(path)):
        if c in names:
            return c
    return None


def pass_of(path):
    """Which pass of a differentiated program the instruction belongs to:
    ``"recompute"`` (a ``jax.checkpoint`` replay: under
    ``rematted_computation``), ``"backward"`` (under ``transpose(jvp``),
    ``"forward"`` (under ``jvp(`` alone), or ``None`` (not differentiated:
    an optimizer, a serving step)."""
    parts = _components(path)
    if "rematted_computation" in parts:
        return "recompute"
    if any(c.startswith("transpose(") and "jvp(" in c for c in parts):
        return "backward"
    if any(c.startswith("jvp(") for c in parts):
        return "forward"
    return None


class _FnStats:
    __slots__ = ("name", "calls", "compiles", "recompiles",
                 "compile_time_s", "signatures", "last_signature",
                 "cost")

    def __init__(self, name):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.recompiles = 0
        self.compile_time_s = 0.0
        self.signatures = set()
        self.last_signature = None
        self.cost = None

    def as_dict(self):
        d = {"calls": self.calls, "compiles": self.compiles,
             "recompiles": self.recompiles,
             "compile_time_s": self.compile_time_s}
        if self.cost:
            d["cost_analysis"] = dict(self.cost)
        return d


class WatchedFunction:
    """Callable proxy over a jitted function.  Transparent to jax AOT
    introspection: unknown attributes (``lower``, ``trace``, ...) forward
    to the wrapped function, and ``__wrapped__`` exposes it for callers
    that need the raw PjitFunction (e.g. ``jax.export.export``)."""

    def __init__(self, fn, name, watchdog):
        self.__wrapped__ = fn
        self._name = name
        self._watchdog = watchdog
        #: ``(args, kwargs)`` of ``ShapeDtypeStruct``s once the program is
        #: described (``watch(..., abstract_args=)`` or :meth:`describe`)
        self.abstract_args = None
        self._table = None      # its instruction table, once asked for

    def describe(self, *args, **kwargs):
        """Register the program under its watched name with the abstract
        form of these operands (arrays or ``ShapeDtypeStruct``s), for
        :meth:`CompileWatchdog.instruction_table`.  No lowering, no
        compile, no device call; keeps shapes and shardings only."""
        self.abstract_args = abstract_like((args, kwargs))
        self._table = None
        with self._watchdog._lock:
            self._watchdog._programs[self._name] = self

    def __call__(self, *args, **kwargs):
        wd = self._watchdog
        if not wd.enabled:
            return self.__wrapped__(*args, **kwargs)
        return wd._record_call(self, args, kwargs)

    def __getattr__(self, attr):
        return getattr(self.__wrapped__, attr)


class CompileWatchdog:
    """Per-process compile telemetry over any number of watched
    functions.  ``report()`` returns {fn_name: {calls, compiles,
    recompiles, compile_time_s, cost_analysis?}}."""

    def __init__(self, registry=None, cost_analysis=True):
        # cost_analysis: False = skip, True = Lowered-stage only,
        # "full" = also allow a lowered.compile() fallback (a second
        # compile — only sane for small programs)
        self.enabled = os.environ.get(
            "PADDLE_TPU_COMPILE_WATCHDOG", "") not in ("", "0", "false")
        self.cost_analysis = cost_analysis
        self._registry = registry
        self._stats = {}        # guarded-by: self._lock
        # name -> the newest described WatchedFunction (a jitted function
        # and shapes): outlives the engine that built it, holds no array
        self._programs = {}     # guarded-by: self._lock
        self._lock = threading.Lock()

    # ---- lifecycle ------------------------------------------------------
    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def reset(self):
        with self._lock:
            self._stats.clear()

    def registry(self):
        if self._registry is None:
            from .metrics import default_registry

            self._registry = default_registry()
        return self._registry

    # ---- wrapping -------------------------------------------------------
    def watch(self, fn, name=None, abstract_args=None):
        """Wrap a jitted callable; returns a transparent proxy.
        ``abstract_args``: the positional arguments of a call as
        ``ShapeDtypeStruct``s (arrays are reduced to theirs), which
        registers the program for :meth:`instruction_table`."""
        if isinstance(fn, WatchedFunction):
            return fn
        name = name or getattr(fn, "__name__", repr(fn))
        watched = WatchedFunction(fn, name, self)
        if abstract_args is not None:
            watched.describe(*abstract_args)
        return watched

    def instruction_table(self, name):
        """``{instruction name: op_name path}`` of the newest program
        registered as ``name`` (:func:`parse_instruction_table`), or
        ``None`` for a name nobody described.  The first request lowers
        with the kept shapes and compiles (in the process that ran the
        step: the executable it ran, from jax's lowering cache) and the
        table is kept.  Never raises: a program that no longer lowers
        logs why."""
        with self._lock:
            watched = self._programs.get(name)
        if watched is None:
            return None
        if watched._table is None:
            args, kwargs = watched.abstract_args
            try:
                text = watched.__wrapped__.lower(
                    *args, **kwargs).compile().as_text()
            except Exception:
                logger.warning("instruction_table(%r): the program does "
                               "not lower from its abstract arguments",
                               name, exc_info=True)
                return None
            watched._table = parse_instruction_table(text)
        return watched._table

    def _record_call(self, watched, args, kwargs):
        sig = _signature(args, kwargs)
        with self._lock:
            st = self._stats.setdefault(
                watched._name, _FnStats(watched._name))
            st.calls += 1
            is_new = sig not in st.signatures
            prev_sig = st.last_signature
            n_prior = len(st.signatures)
            if is_new:
                st.signatures.add(sig)
            st.last_signature = sig
        if not is_new:
            return watched.__wrapped__(*args, **kwargs)

        t0 = time.perf_counter()
        out = watched.__wrapped__(*args, **kwargs)
        dt = time.perf_counter() - t0
        cost = (_cost_analysis(watched.__wrapped__, args, kwargs,
                               allow_compile=self.cost_analysis == "full")
                if self.cost_analysis else None)
        reg = self.registry()
        reg.counter("jit_compiles_total",
                    "XLA compilations per watched function",
                    labelnames=("fn",)).labels(fn=watched._name).inc()
        with self._lock:
            st.compiles += 1
            st.compile_time_s += dt
            if cost:
                st.cost = cost
        if n_prior > 0:                       # recompile after warmup
            with self._lock:
                st.recompiles += 1
            reg.counter("jit_recompiles_total",
                        "post-warmup XLA recompilations (shape/dtype "
                        "drift)", labelnames=("fn",)) \
                .labels(fn=watched._name).inc()
            logger.warning(
                "recompilation #%d of %s (%.2fs): argument "
                "signature changed\n%s",
                n_prior, watched._name, dt, _sig_diff(prev_sig, sig))
        else:
            logger.debug("first compile of %s: %.2fs", watched._name, dt)
        return out

    # ---- reporting ------------------------------------------------------
    def report(self):
        with self._lock:
            return {name: st.as_dict() for name, st in self._stats.items()}


_default = CompileWatchdog()


def default_watchdog() -> CompileWatchdog:
    return _default


def watch(fn, name=None, abstract_args=None):
    """Wrap ``fn`` under the default watchdog (dormant until enabled)."""
    return _default.watch(fn, name, abstract_args)


def instruction_table(name):
    """The default watchdog's :meth:`CompileWatchdog.instruction_table`."""
    return _default.instruction_table(name)


def enable_compile_watchdog():
    return _default.enable()


def disable_compile_watchdog():
    return _default.disable()


@contextlib.contextmanager
def watchdog_enabled(watchdog=None):
    wd = watchdog or _default
    prev = wd.enabled
    wd.enable()
    try:
        yield wd
    finally:
        wd.enabled = prev
