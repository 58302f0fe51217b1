"""Device seconds by the scope that made the instruction, from the reduced
trace and the program's own table of its compiled step.

``run["trace"]["ops"]`` holds every device operation's self seconds under
``trace.short``'s names: the compiled program's instruction names, with
``[mosaic]`` after a Pallas kernel's.  The program keeps, per watched step
(``paddle_tpu.observability.compile_watchdog``), what it takes to compile
that step again from shapes alone, and ``instruction_table(name)`` maps each
instruction to the ``op_name`` path jax recorded for it: the
``jax.named_scope``s it was traced under, the pass of a differentiated
program and the primitive.  Joining the two gives device time by scope.

The table is requested once a process, after the window, by the first
reader that wants it: a lowering and a read of the persistent compile cache
(the step compiled into it during set-up).  One line on standard error says
what the request cost and whether the cache hit, and the by-scope table
follows it (``PERF.md`` section 5 is made of these).

What it cannot see: an operation of another program of the window whose
instruction name is also one of the step's counts under the step's path
(bounded by those programs' seconds); a fusion carries the path of one of
the operations fused into it.

Every function returns ``None`` rather than raise for a program from before
the table, and for a run without a trace.
"""
from __future__ import annotations

import logging
import re
import sys
import time

#: ``under=ANY``: under at least one named scope
ANY = "*"
TRAIN_STEP = "hybrid_engine::step"
SERVE_STEP = "serving::unified_step"
#: the primitives whose instructions are matrix products
PRODUCTS = ("dot_general", "conv_general_dilated")
MOSAIC = "[mosaic]"


def _program():
    """The program's module with the table and the helpers on a path, or
    ``None`` for a program that has none."""
    try:
        from paddle_tpu.observability import compile_watchdog
    except ImportError:
        return None
    return compile_watchdog \
        if hasattr(compile_watchdog, "instruction_table") else None


class _CacheLog(logging.Handler):
    """jax's persistent compile cache's log while attached: the programs it
    found and the programs it had to compile, by name."""

    _PAT = re.compile(r"(cache hit|CACHE MISS) for '([^']+)'")
    _LOGGER = "jax._src.compiler"

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.hits, self.misses = [], []

    def emit(self, record):
        m = self._PAT.search(record.getMessage())
        if m:
            (self.hits if m.group(1) == "cache hit"
             else self.misses).append(m.group(2))

    def __enter__(self):
        log = logging.getLogger(self._LOGGER)
        self._level = log.level
        log.setLevel(logging.DEBUG)
        log.addHandler(self)
        return self

    def __exit__(self, *exc):
        log = logging.getLogger(self._LOGGER)
        log.removeHandler(self)
        log.setLevel(self._level)


def table(run):
    """``{instruction name: op_name path}`` of the step the cell's runner
    drives (the train step, or the serving engine's unified step), or
    ``None``.  Kept in ``run`` after the first request."""
    if "scope_table" in run:
        return run["scope_table"]
    program = _program()
    found = None
    if program is not None:
        name = TRAIN_STEP if run["cell"]["runner"] == "train" \
            else SERVE_STEP
        t0 = time.perf_counter()
        with _CacheLog() as log:
            found = program.instruction_table(name)
        if found is not None:
            print(f"scope_share: table of {name!r}: {len(found)} "
                  f"instructions in {time.perf_counter() - t0:.2f} s, "
                  f"compile cache hit {log.hits} miss {log.misses}",
                  file=sys.stderr)
    run["scope_table"] = found
    if found is not None and run.get("trace"):
        report(run)
    return found


def rows(run):
    """``[(instruction name, seconds, is a Mosaic call, path or None)]``
    for every operation of the traced window: ``None`` for an instruction
    that is not in the step's table.  ``None`` without trace or table."""
    tr, paths = run.get("trace"), table(run)
    if not tr or paths is None:
        return None
    out = []
    for name, sec in tr["ops"].items():
        mosaic = name.endswith(MOSAIC)
        if mosaic:
            name = name[:-len(MOSAIC)]
        out.append((name, sec, mosaic, paths.get(name)))
    return out


def seconds(run, under=None, mosaic=None, passes=None):
    """Device seconds of the window's operations whose instruction is in
    the step's table and, where given: lies under one of the named scopes
    ``under`` (``ANY``: under at least one), is (``True``) or is not
    (``False``) a Mosaic call, belongs to one of ``passes`` (``"forward"``,
    ``"recompute"``, ``"backward"``).  ``None`` without trace or table."""
    found = rows(run)
    if found is None:
        return None
    program = _program()
    total = 0.0
    for _, sec, is_mosaic, path in found:
        if path is None or (mosaic is not None and mosaic != is_mosaic):
            continue
        if under is not None:
            scopes = program.named_scopes(path)
            if not (scopes if under == ANY
                    else any(s in under for s in scopes)):
                continue
        if passes is not None and program.pass_of(path) not in passes:
            continue
        total += sec
    return total


def share(run, **which):
    """:func:`seconds` as per cent of the device's busy time in the traced
    window; ``None`` without trace, table or busy time."""
    spent = seconds(run, **which)
    if spent is None or run["trace"]["busy_s"] <= 0:
        return None
    return 100.0 * spent / run["trace"]["busy_s"]


def by_scope(run):
    """``{(scopes, pass): [Mosaic, products, other seconds]}``: the
    window's device seconds by the named scopes on the instruction's path
    (joined by ``/``; ``"(no scope)"``, ``"(not in the table)"``) and its
    pass, split by the leaf primitive.  ``None`` without trace or table."""
    found = rows(run)
    if found is None:
        return None
    program = _program()
    out = {}
    for _, sec, is_mosaic, path in found:
        if path is None:
            key = ("(not in the table)", None)
        else:
            scopes = program.named_scopes(path)
            if is_mosaic:
                # a kernel's own name is its innermost scope: its seconds
                # go in the Mosaic column of the scope that called it
                scopes = scopes[:-1]
            key = ("/".join(scopes) or "(no scope)", program.pass_of(path))
        kind = 0 if is_mosaic else \
            1 if program.leaf_primitive(path or "") in PRODUCTS else 2
        out.setdefault(key, [0.0, 0.0, 0.0])[kind] += sec
    return out


def report(run, top=40, file=None):
    """The by-scope table of the traced window, largest first, and the
    largest operations that no scope names (on standard error)."""
    file = file or sys.stderr
    grouped, busy = by_scope(run), run["trace"]["busy_s"]
    if not grouped or busy <= 0:
        return
    print(f"scope_share: busy {busy:.3f} s; scope | pass | seconds | % of "
          f"busy | mosaic | products | other", file=file)
    order = sorted(grouped.items(), key=lambda kv: -sum(kv[1]))
    for (scopes, which), (m, p, o) in order[:top]:
        print(f"scope_share: {scopes} | {which or '-'} | {m + p + o:.4f} | "
              f"{100 * (m + p + o) / busy:.2f} | {m:.4f} | {p:.4f} | "
              f"{o:.4f}", file=file)
    program = _program()
    unnamed = sorted(((sec, name, path) for name, sec, _, path in rows(run)
                      if path is None or not program.named_scopes(path)),
                     reverse=True)[:12]
    for sec, name, path in unnamed:
        print(f"scope_share: unnamed {name} {sec:.4f} s "
              f"{'(not in the table)' if path is None else repr(path)}",
              file=file)
