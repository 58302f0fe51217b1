"""From a profiler trace to numbers: the device's busy time, the time by
operation, and the longest idle gaps with what the host was doing in each.

``reduce`` works on plain intervals and is what the tests check;
``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
those intervals (jax alone, no TensorFlow).

    python benchmark/trace.py <file.xplane.pb>     prints what is in a trace
"""
from __future__ import annotations

import glob
import os
import sys

# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, window):
    """The idle stretches of ``window = (t0, t1)``: what the union of the
    intervals leaves uncovered, as ``(start, end)``."""
    t0, t1 = window
    out, at = [], t0
    for s, e in sorted(intervals):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def self_seconds(events):
    """name -> seconds, where an event that encloses others (a loop around
    its body's operations) counts only the time its children leave."""
    out = {}
    stack = []          # (end, name, [self seconds])
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out[n] = out.get(n, 0.0) + own[0]
        if stack:
            stack[-1][2][0] -= min(e, stack[-1][0]) - s
        stack.append((e, name, [e - s]))
    for end, n, own in stack:
        out[n] = out.get(n, 0.0) + own[0]
    return out


def label(gap, spans, default="between spans"):
    """The host span that covers most of the gap."""
    best, name = 0.0, default
    for s, e, n in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best:
            best, name = cover, n
    return name


def reduce(device_events, host_spans, window, top=10):
    """``device_events``: for each device a list of ``(start, end, name)``
    in seconds; ``host_spans``: the runner's ``(start, end, name)``;
    ``window``: the traced window ``(t0, t1)`` on the same clock.

    Busy seconds are averaged over the devices, operation times summed over
    them; the gaps are those of the first device."""
    t0, t1 = window
    clipped = [[(max(s, t0), min(e, t1), n) for s, e, n in evs
                if min(e, t1) > max(s, t0)] for evs in device_events]
    busy = [union_seconds([(s, e) for s, e, _ in evs]) for evs in clipped]
    ops = {}
    for evs in clipped:
        for n, sec in self_seconds(evs).items():
            ops[n] = ops.get(n, 0.0) + sec
    idle = {}
    first = clipped[0] if clipped else []
    idle_gaps = gaps([(s, e) for s, e, _ in first], window)
    for g in idle_gaps:
        n = label(g, host_spans)
        idle[n] = idle.get(n, 0.0) + g[1] - g[0]
    longest = sorted(idle_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "ops": ops,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_by_span": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        "longest_gaps": [(label(g, host_spans), g[1] - g[0])
                         for g in longest],
    }


# ------------------------------------------------------------ the file


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


MOSAIC = 'custom_call_target="tpu_custom_call"'


def short(name):
    """An operation's name as the breakdown carries it: the instruction's
    own name without the text of its operands, and ``[mosaic]`` after it
    where the instruction is a Pallas kernel (the trace carries no name of
    the kernel itself)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return head + "[mosaic]" if MOSAIC in name else head


def load_xplane(path, span_names):
    """``(device_events, host_spans)`` in seconds on the trace's clock.
    ``span_names``: the names the runner gave its TraceAnnotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, host_spans = [], []
    wanted = set(span_names)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events.append([
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         short(ev.name))
                        for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        host_spans.append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9,
                             ev.name))
    return device_events, host_spans


def describe(path, top=25):
    """What a trace holds, for a look by hand before code is written
    against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{(t1 - t0) * 1e-9:.3f} s from {t0 * 1e-9:.3f}")
            by = {}
            for e in events:
                d = by.setdefault(e.name, [0, 0])
                d[0] += 1
                d[1] += e.duration_ns
            for n, (c, ns) in sorted(by.items(),
                                     key=lambda kv: -kv[1][1])[:top]:
                print(f"    {ns * 1e-9:10.4f} s {c:7d} x {n[:100]}")
            if plane.name.startswith(DEVICE_PLANE):
                ev = max(events, key=lambda e: e.duration_ns)
                print(f"    stats of the longest: "
                      f"{[(k, str(v)[:80]) for k, v in ev.stats][:12]}")


if __name__ == "__main__":
    describe(sys.argv[1])
