"""From a profiler trace (`.xplane.pb`) to numbers: device busy time,
time by XLA module and op category, self time of host spans, and idle
gaps named by what the host was doing.

`load` turns the file into plain lists (read with `jax.profiler.
ProfileData`, nothing else); every reduction below works on those, so
it can be checked on a hand-written trace (`fixtures/`).

A trace is {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}, times in ns on one clock.
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, keep_line=None) -> dict:
    """Planes, lines and events of an `.xplane.pb` file.  ``keep_line``
    (plane name, line name) -> bool drops lines unread (a device plane
    has lines of per-core detail nobody reduces)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def wanted_line(plane: str, line: str) -> bool:
    """What the reductions read: ops and modules of the devices, every
    thread of the host."""
    if DEVICE_PLANE.match(plane):
        return line in (OPS_LINE, MODULES_LINE)
    return plane == HOST_PLANE


def device_planes(trace: dict) -> list:
    """[(device ordinal, plane)] sorted by ordinal."""
    out = []
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if m:
            out.append((int(m.group(1)), plane))
    return sorted(out, key=lambda t: t[0])


def line_events(plane: dict, line_name: str) -> list:
    return [ev for line in plane["lines"] if line["name"] == line_name
            for ev in line["events"]]


def clip(events, window):
    """Events cut to the window [t0, t1]; those outside are dropped."""
    t0, t1 = window
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append([name, s, e - s])
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda ev: ev[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(events, window) -> list:
    """[(start, end)] of the window not covered by any event."""
    t0, t1 = window
    out, end = [], t0
    for _, start, dur in sorted(clip(events, window), key=lambda ev: ev[1]):
        if start > end:
            out.append((end, start))
        end = max(end, start + dur)
    if t1 > end:
        out.append((end, t1))
    return out


def module_name(event_name: str) -> str:
    """`jit__dense_dot_only(1234)` -> `jit__dense_dot_only`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_label(op_name: str) -> str:
    """`%all-gather.3 = f32[8] all-gather(%fusion.1)` -> `all-gather.3`:
    the instruction's own name, without its operands' text."""
    return op_name.lstrip("%").split(" ")[0].split("=")[0]


def op_kind(op_name: str) -> str:
    """`%fusion.123 = ...` / `fusion.123` -> `fusion`: the HLO
    instruction's base name, which for an unfused op is its opcode."""
    name = op_label(op_name)
    return re.sub(r"[.\d]+$", "", name) or name


def matches(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def self_times(events) -> list:
    """[[name, start, self_ns], ...]: each event's length less what the
    events nested directly in it cover (a `while` and the ops of its
    body, a span and its child spans).  Self times of properly nested
    events add up to the length of their union."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    selfs = [ev[2] for ev in evs]
    stack: list = []
    for i, (_, start, dur) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            p = stack[-1]
            selfs[p] -= min(start + dur, evs[p][1] + evs[p][2]) - start
        stack.append(i)
    return [[ev[0], ev[1], max(s, 0.0)] for ev, s in zip(evs, selfs)]


def ops_by_module(plane: dict, window) -> list:
    """[(module, op name, self_ns)] for every op event inside the
    window, the module being the `XLA Modules` event that holds the
    op's start (a core runs one module at a time); `?` where none
    does."""
    mods = sorted(clip(line_events(plane, MODULES_LINE), window),
                  key=lambda ev: ev[1])
    starts = [ev[1] for ev in mods]
    out = []
    for name, start, self_ns in self_times(
            clip(line_events(plane, OPS_LINE), window)):
        i = bisect.bisect_right(starts, start) - 1
        mod = "?"
        if i >= 0 and start < mods[i][1] + mods[i][2]:
            mod = module_name(mods[i][0])
        out.append((mod, name, self_ns))
    return out


def device_seconds(plane: dict, window, modules=("*",), ops=("*",)) -> float:
    """Seconds of self time of the op events in the window whose module
    matches one of ``modules`` and whose own name (`op_label`) matches
    one of ``ops`` (shell patterns)."""
    return 1e-9 * sum(
        self_ns for mod, name, self_ns in ops_by_module(plane, window)
        if matches(mod, modules) and matches(op_label(name), ops))


def host_spans(trace: dict, patterns) -> list:
    """[(thread line name, [[name, start, dur], ...])] of host events
    whose name matches one of ``patterns``."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            evs = [ev for ev in line["events"] if matches(ev[0], patterns)]
            if evs:
                out.append((line["name"], evs))
    return out


def span_self_ns(trace: dict, name: str, family, window) -> list:
    """Self time of each span called ``name`` inside the window: its
    length less what spans of ``family`` (patterns) nested directly in
    it on the same thread cover."""
    out = []
    for _, evs in host_spans(trace, list(family) + [name]):
        out += [s for n, _, s in self_times(clip(evs, window)) if n == name]
    return out


def attribute_gaps(gap_list, trace: dict, family) -> dict:
    """{span name: ns} of device idle time, each piece of a gap given to
    the innermost host span of ``family`` open at that time (`host:
    none` where none is)."""
    spans = [ev for _, evs in host_spans(trace, family) for ev in evs]
    # innermost = the shortest span covering the instant; sweep over cuts
    out: dict = {}
    for g0, g1 in gap_list:
        cuts = {g0, g1}
        inside = [ev for ev in spans if ev[1] < g1 and ev[1] + ev[2] > g0]
        for _, s, d in inside:
            cuts.update(t for t in (s, s + d) if g0 < t < g1)
        cuts = sorted(cuts)
        for c0, c1 in zip(cuts, cuts[1:]):
            mid = 0.5 * (c0 + c1)
            open_ = [ev for ev in inside if ev[1] <= mid < ev[1] + ev[2]]
            name = (min(open_, key=lambda ev: ev[2])[0] if open_
                    else "host:none")
            out[name] = out.get(name, 0.0) + (c1 - c0)
    return out


def top(table: dict, n: int = 10, scale: float = 1e-9) -> list:
    """[[name, seconds], ...], largest first, at most ``n``."""
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[name, value * scale] for name, value in rows]


def describe(trace: dict, n: int = 12) -> list:
    """Lines that say what a trace holds: planes, lines, event counts
    and the commonest event names.  For reading one by hand."""
    out = []
    for plane in trace["planes"]:
        out.append(f"plane {plane['name']!r}")
        for line in plane["lines"]:
            evs = line["events"]
            tot: dict = {}
            for name, _, dur in evs:
                key = name[:100]
                c = tot.setdefault(key, [0, 0.0])
                c[0] += 1
                c[1] += dur
            out.append(f"  line {line['name']!r}: {len(evs)} events")
            for name, (cnt, dur) in sorted(
                    tot.items(), key=lambda kv: -kv[1][1])[:n]:
                out.append(f"    {dur * 1e-6:12.3f} ms {cnt:8d} x {name}")
    return out
