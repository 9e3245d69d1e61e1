"""From a profiler trace (`.xplane.pb`) to numbers: the reduction every PR
shares, so that no PR that claims a gain computes its own.

A `Trace` holds, per device, the events of the line on which the device's
operations run one after another, and the host spans the harness itself
wrote (`jax.profiler.TraceAnnotation` with a name that starts with
`SPAN_PREFIX`). Times are nanoseconds on the profiler's clock, shared by
host and device lines. Everything below `load` works on plain tuples, so a
test can build a `Trace` by hand.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from . import intervals as iv

SPAN_PREFIX = "bm:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line that holds one event per executed HLO operation, one after
# another; the line that holds asynchronous operations from their start to
# their done (copies, collectives in flight); one event per executed program
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
# collectives by instruction name. The TPU compiler turns most all-gathers
# and reduce-scatters into fusions named async-collective-start / -done; the
# time between such a start and its done is on no line of the trace, so only
# the two operations themselves count (collective-permutes, which a
# collective matmul is made of, do show in flight on the async line)
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all|async-collective|send|recv)"
)
# the trace names a device event by its whole HLO text:
#   %fusion.8 = (f32[2,4096]{...}, ...) fusion(...), kind=kOutput, ...
_HLO = re.compile(r"^%?([^\s=]+) = \(?([a-z0-9]+\[[\d,]*\])?")
_OPCODE = re.compile(r"[})\]] ([a-z][\w\-]*)\(")
MOSAIC = "tpu_custom_call"


def label(text: str) -> str:
    """A device event's HLO text cut to `<instruction> <opcode> <first result
    shape>`, with ` tpu_custom_call` appended for a Mosaic (Pallas) kernel.
    Text that is not HLO is kept as it is."""
    m = _HLO.match(text)
    if not m:
        return text
    op = _OPCODE.search(text, m.end(1))
    parts = [m.group(1), op.group(1) if op else "?", m.group(2) or "?"]
    if f'custom_call_target="{MOSAIC}"' in text:
        parts.append(MOSAIC)
    return " ".join(parts)


@dataclass
class Trace:
    # device plane name -> [(op label, start_ns, end_ns)], in start order
    devices: dict = field(default_factory=dict)
    # the same for asynchronous operations in flight, and for whole programs
    in_flight: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    # [(span name without the prefix, start_ns, end_ns)]
    spans: list = field(default_factory=list)

    def window(self):
        """(first start, last end) over the device operations."""
        starts = [ev[0][1] for ev in self.devices.values() if ev]
        ends = [max(e for _, _, e in ev) for ev in self.devices.values() if ev]
        if not starts:
            return None
        return min(starts), max(ends)


def find(trace_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` trace directory."""
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            kept = {OPS_LINE: [], ASYNC_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in kept:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        kept[line.name].append(
                            (label(ev.name), s, s + int(ev.duration_ns))
                        )
            for events in kept.values():
                events.sort(key=lambda e: e[1])
            trace.devices[plane.name] = kept[OPS_LINE]
            trace.in_flight[plane.name] = kept[ASYNC_LINE]
            trace.modules[plane.name] = kept[MODULES_LINE]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        trace.spans.append(
                            (ev.name[len(SPAN_PREFIX):], s, s + int(ev.duration_ns))
                        )
    trace.spans.sort(key=lambda e: e[1])
    return trace


def describe(path: str, top: int = 40) -> str:
    """Planes, lines and the heaviest event names of a trace: what to look
    at by hand before writing a pattern against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            total = defaultdict(lambda: [0, 0])
            n = 0
            for ev in line.events:
                t = total[ev.name]
                t[0] += int(ev.duration_ns)
                t[1] += 1
                n += 1
            out.append(f"  line {line.name!r}: {n} events, {len(total)} names")
            heavy = sorted(total.items(), key=lambda kv: -kv[1][0])[:top]
            for name, (ns, count) in heavy:
                out.append(f"    {ns / 1e6:12.3f} ms  x{count:<6d} {name[:160]}")
    return "\n".join(out)


def _ivs(events, pattern=None, negate=False):
    if pattern is None:
        return [(s, e) for _, s, e in events]
    rx = re.compile(pattern) if isinstance(pattern, str) else pattern
    return [(s, e) for n, s, e in events if bool(rx.search(n)) != negate]


def busy(trace: Trace) -> dict:
    """Per device: seconds in which an operation ran; and the window."""
    win = trace.window()
    if win is None:
        return {"window_s": 0.0, "busy_s": {}, "idle_share": {}}
    lo, hi = win
    busy_s = {
        d: iv.measure(_ivs(ev)) / 1e9 for d, ev in trace.devices.items()
    }
    w = (hi - lo) / 1e9
    return {
        "window_s": w,
        "busy_s": busy_s,
        "idle_share": {d: 1.0 - b / w for d, b in busy_s.items()},
    }


def time_matching(trace: Trace, pattern: str) -> dict:
    """Per device: seconds of operations whose name matches `pattern`, and
    how many such events there were."""
    rx = re.compile(pattern)
    out = {}
    for d, ev in trace.devices.items():
        hit = [(s, e) for n, s, e in ev if rx.search(n)]
        out[d] = {"seconds": sum(e - s for s, e in hit) / 1e9, "events": len(hit)}
    return out


def collectives(trace: Trace) -> dict:
    """Per device: seconds in which a collective was running or in flight
    (its own operations, and from its start to its done), and the part of
    them during which no other operation ran on that device (exposed)."""
    out = {}
    for d, ev in trace.devices.items():
        coll = _ivs(ev, COLLECTIVE) + _ivs(trace.in_flight.get(d, []), COLLECTIVE)
        comp = _ivs(ev, COLLECTIVE, negate=True)
        out[d] = {
            "collective_s": iv.measure(coll) / 1e9,
            "exposed_s": iv.measure(iv.subtract(coll, comp)) / 1e9,
        }
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time,
    averaged over devices. Instructions that differ only in their number
    (the same fusion in every layer) and give the same shape are one name."""
    total = defaultdict(float)
    for ev in trace.devices.values():
        for name, s, e in ev:
            instr, _, rest = name.partition(" ")
            stem = re.sub(r"\.\d+$", "", instr)
            total[f"{stem} {rest}" if rest else stem] += (e - s) / 1e9
    k = max(len(trace.devices), 1)
    heavy = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec / k] for name, sec in heavy]


def idle_gaps(trace: Trace, n: int = 5) -> list:
    """[[what the host was doing, seconds]] for the longest gaps in which
    NO device ran an operation, each named after the harness span that
    covers most of it ('no span' when none does)."""
    win = trace.window()
    if win is None:
        return []
    all_ops = [i for ev in trace.devices.values() for i in _ivs(ev)]
    # a microsecond and more: shorter ones are the seams between operations
    holes = [g for g in iv.gaps(all_ops, *win) if g[1] - g[0] >= 1000]
    holes = sorted(holes, key=lambda g: g[0] - g[1])[:n]
    out = []
    for lo, hi in holes:
        best, cover = "no span", 0.0
        for name, s, e in trace.spans:
            c = min(e, hi) - max(s, lo)
            if c > cover:
                best, cover = name, c
        out.append([best, (hi - lo) / 1e9])
    return out


def idle_per_span(trace: Trace, span_name: str):
    """Seconds in which no device ran an operation, per harness span of that
    name that starts inside the device window. The host and device clocks of
    a trace agree only to about a millisecond (on the recorded fixture the
    device starts 0.5 ms "before" its dispatch), so this is a mean over the
    slice, which needs no alignment, and not a per-span difference."""
    win = trace.window()
    if win is None:
        return None
    n = sum(1 for name, s, _ in trace.spans if name == span_name and win[0] <= s < win[1])
    if not n:
        return None
    all_ops = [i for ev in trace.devices.values() for i in _ivs(ev)]
    return iv.measure(iv.gaps(all_ops, *win)) / 1e9 / n


def module_seconds(trace: Trace) -> list:
    """[[program, seconds, runs]] per compiled program, mean over devices."""
    total = defaultdict(lambda: [0.0, 0])
    for ev in trace.modules.values():
        for name, s, e in ev:
            t = total[re.sub(r"\(\d+\)$", "", name)]
            t[0] += (e - s) / 1e9
            t[1] += 1
    k = max(len(trace.modules), 1)
    return sorted(([n, t[0] / k, t[1] // k] for n, t in total.items()),
                  key=lambda r: -r[1])
