"""Device time by what the PROGRAM called the work.

`xplane.load` names a device operation by what the compiler called the
result (`fusion.8 fusion f32[2,4096]`), which the next change to the
program renumbers or removes. The trace file also holds, for every
operation, the scope path under which the program traced it
(`jit(step)/TransformerLM/layers_3/attn/kv_gather/gather`: Flax module
names and `jax.named_scope`s) and the number of the program it belongs
to; `jax.profiler.ProfileData` gives neither out. This module reads them
from the same `.xplane.pb` with a plain reader of the protobuf wire format
(no tensorflow, no generated classes), and sums device time per program
run and per component.

Known limit: a fusion carries the path of ONE of its instructions (its
root), so a multi-output fusion that spans a norm and a matmul is booked
to one of them. Times are exact per operation and approximate per
component; `unscoped` says how much is not booked at all.

Times are picoseconds on the trace's clock, as the file has them
(`ProfileData` cuts them to whole nanoseconds).
"""

from __future__ import annotations

import bisect
import functools
import re
from collections import defaultdict
from dataclasses import dataclass, field

from .xplane import DEVICE_PLANE, MODULES_LINE, OPS_LINE

# field numbers of tsl/profiler/protobuf/xplane.proto, the ones read here;
# the entries of a protobuf map are messages with key = 1 and value = 2
FIELDS = {
    "XSpace": {"planes": 1},
    "XPlane": {"name": 2, "lines": 3, "event_metadata": 4, "stat_metadata": 5},
    "XLine": {"name": 2, "timestamp_ns": 3, "events": 4},
    "XEvent": {"metadata_id": 1, "offset_ps": 2, "duration_ps": 3},
    "XEventMetadata": {"id": 1, "name": 2, "stats": 5},
    "XStatMetadata": {"id": 1, "name": 2},
    "XStat": {"metadata_id": 1, "uint64_value": 3, "str_value": 5, "ref_value": 7},
}
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) for every field of one serialized message: an
    int for a varint, a memoryview (not copied) for anything with a length
    or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                width, i = _varint(buf, i)
            elif wire in (1, 5):
                width = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}: not an xplane file")
            value = buf[i:i + width]
            i += width
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for num, v in _fields(buf):
        if num == _MAP_KEY:
            key = v
        elif num == _MAP_VALUE:
            value = v
    return key, value


@dataclass
class Scopes:
    # device plane name -> [(scope path, program id, start_ps, duration_ps)],
    # one per event of the `XLA Ops` line, in start order
    ops: dict = field(default_factory=dict)
    # device plane name -> [(program name without its number, program id,
    # start_ps, duration_ps)], one per event of the `XLA Modules` line
    runs: dict = field(default_factory=dict)

    @functools.cached_property
    def sums(self) -> dict:
        """device -> {(program id, scope path): picoseconds}, what every
        reduction below works on: some thousands of paths, not every event.
        An operation counts for the run of its program it started in; one
        that started in none (a run cut by the edge of the trace leaves no
        event on the modules line) is kept under program id None."""
        out = {}
        for device, ops in self.ops.items():
            runs = self.runs.get(device, [])
            starts = [r[2] for r in runs]
            total = out[device] = defaultdict(int)
            for path, program_id, start, dur in ops:
                k = bisect.bisect_right(starts, start) - 1
                inside = (k >= 0 and start <= runs[k][2] + runs[k][3]
                          and runs[k][1] == program_id)
                total[(program_id if inside else None, path)] += dur
        return out


_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")


def _plane(buf):
    """(name, {line name: (timestamp_ns, [event bytes])}, event metadata
    bytes by id, stat names by id) of one XPlane, nothing inside decoded."""
    f = FIELDS["XPlane"]
    name, lines, event_md, stat_names = "", [], {}, {}
    for num, v in _fields(buf):
        if num == f["name"]:
            name = _text(v)
        elif num == f["lines"]:
            lines.append(v)
        elif num == f["event_metadata"]:
            key, value = _map_entry(v)
            event_md[key] = value
        elif num == f["stat_metadata"]:
            key, value = _map_entry(v)
            for n2, v2 in _fields(value):
                if n2 == FIELDS["XStatMetadata"]["name"]:
                    stat_names[key] = _text(v2)
    return name, lines, event_md, stat_names


def _line(buf):
    f = FIELDS["XLine"]
    name, t0_ns, events = "", 0, []
    for num, v in _fields(buf):
        if num == f["name"]:
            name = _text(v)
        elif num == f["timestamp_ns"]:
            t0_ns = v
        elif num == f["events"]:
            events.append(v)
    return name, t0_ns, events


def _metadata(buf, stat_names):
    """(name, tf_op or '', program_id or None) of one XEventMetadata."""
    f, s = FIELDS["XEventMetadata"], FIELDS["XStat"]
    name, tf_op, program_id = "", "", None
    for num, v in _fields(buf):
        if num == f["name"]:
            name = _text(v)
        elif num == f["stats"]:
            stat = dict(_fields(v))
            what = stat_names.get(stat.get(s["metadata_id"]))
            if what == "tf_op":
                if s["str_value"] in stat:
                    tf_op = _text(stat[s["str_value"]])
                else:  # a string the file holds once, among the stat names
                    tf_op = stat_names.get(stat.get(s["ref_value"]), "")
            elif what == "program_id":
                program_id = stat.get(s["uint64_value"])
    return name, tf_op, program_id


def read(path: str) -> Scopes:
    """The scope path, program and time of every device operation, and the
    runs of every program, of one `.xplane.pb`."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = Scopes()
    e = FIELDS["XEvent"]
    for num, plane_buf in _fields(data):
        if num != FIELDS["XSpace"]["planes"]:
            continue
        plane_name, lines, event_md, stat_names = _plane(plane_buf)
        if not DEVICE_PLANE.match(plane_name):
            continue
        decoded = {}
        ops, runs = [], []
        for line_buf in lines:
            line_name, t0_ns, events = _line(line_buf)
            if line_name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in events:
                got = dict(_fields(ev))
                md_id = got.get(e["metadata_id"])
                if md_id not in decoded:
                    decoded[md_id] = _metadata(event_md[md_id], stat_names)
                name, tf_op, program_id = decoded[md_id]
                start = 1000 * t0_ns + got.get(e["offset_ps"], 0)
                dur = got.get(e["duration_ps"], 0)
                if line_name == OPS_LINE:
                    ops.append((tf_op[:-1] if tf_op.endswith(":") else tf_op,
                                program_id, start, dur))
                else:
                    m = _PROGRAM.match(name)
                    runs.append((m.group(1), int(m.group(2)), start, dur) if m
                                else (name, None, start, dur))
        out.ops[plane_name] = sorted(ops, key=lambda o: o[2])
        out.runs[plane_name] = sorted(runs, key=lambda r: r[2])
    return out


# --- what the program named ------------------------------------------------

# `transform(inner)`: JAX wraps the first name traced under a transformation
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# segments that say how the work was traced, not what it is; the last is
# Flax's name for a method other than __call__ (`attn._decode_paged`)
_STRUCTURE = re.compile(
    r"^(checkpoint|rematted_computation|shard_map|TransformerLM|layers_\d+"
    r"|\w+\.\w+)$")
FORWARD, BACKWARD, RECOMPUTED = "forward", "backward", "recomputed"
UNSCOPED = "unscoped"


@functools.lru_cache(maxsize=1 << 16)
def names(path: str):
    """(names, phase). `names` is the path's segments with the wrappers of
    transformations peeled (`transpose(jvp(loss))` -> `loss`) and `jit(...)`
    segments and empty ones dropped; the last one is the primitive. `phase`
    is RECOMPUTED under `rematted_computation`, else BACKWARD under a
    `transpose(...)`, else FORWARD."""
    out, backward = [], False
    for seg in path.split("/"):
        while (m := _WRAPPED.match(seg)):
            if m.group(1) in ("jit", "pjit"):
                seg = ""
                break
            backward = backward or m.group(1) == "transpose"
            seg = m.group(2)
        if seg:
            out.append(seg)
    if "rematted_computation" in out:
        return tuple(out), RECOMPUTED
    return tuple(out), BACKWARD if backward else FORWARD


def component(path: str, depth: int = 1) -> str:
    """The first `depth` segments the program named, after everything that
    only says how the work was traced is cut, or UNSCOPED when the path
    holds nothing but that and the primitive's name."""
    named = [n for n in names(path)[0][:-1] if not _STRUCTURE.match(n)]
    return "/".join(named[:depth]) or UNSCOPED


def time_in(sc: Scopes, program: str, scope: str = None):
    """Device milliseconds per run of the programs whose name matches
    `program`, in the operations whose peeled path (`'/'.join(names(path))`)
    matches `scope` (None: every operation of the program); mean over the
    devices that ran the program. None when none did, or when `scope` is
    given and matches no operation."""
    prog_rx = re.compile(program)
    scope_rx = re.compile(scope) if scope is not None else None
    per_device, matched = [], False
    for device, sums in sc.sums.items():
        runs = sc.runs.get(device, [])
        ids = {r[1] for r in runs if prog_rx.search(r[0])}
        n_runs = sum(1 for r in runs if r[1] in ids)
        if not n_runs:
            continue
        hit = [ps for (pid, path), ps in sums.items() if pid in ids and (
            scope_rx is None or scope_rx.search("/".join(names(path)[0])))]
        matched = matched or bool(hit)
        per_device.append(sum(hit) / 1e9 / n_runs)
    if not per_device or (scope_rx is not None and not matched):
        return None
    return sum(per_device) / len(per_device)


def by_component(sc: Scopes, depth: int = 2) -> dict:
    """program name -> {"runs" (a device), "ms" (operations, per run),
    "module_ms" (the events of the modules line, per run), "components":
    {component: {phase: ms per run}}}, over all devices; and under the key
    None the operations that started in no run of their program ("ms": a
    device, in the whole slice)."""
    devices = [d for d in sc.ops if sc.runs.get(d)]
    k = max(len(devices), 1)
    out, stray = {}, 0
    for device in devices:
        name_of = {r[1]: r[0] for r in sc.runs[device]}
        for name, _, _, dur in sc.runs[device]:
            p = out.setdefault(name, {"runs": 0, "ms": 0.0, "module_ms": 0.0,
                                      "components": {}})
            p["runs"] += 1
            p["module_ms"] += dur / 1e9
        for (pid, path), ps in sc.sums[device].items():
            if pid is None:
                stray += ps
                continue
            p = out[name_of[pid]]
            p["ms"] += ps / 1e9
            phases = p["components"].setdefault(component(path, depth), defaultdict(float))
            phases[names(path)[1]] += ps / 1e9
    for p in out.values():
        runs = p["runs"]  # over all devices: what the sums are divided by
        p.update(runs=runs // k, ms=p["ms"] / runs, module_ms=p["module_ms"] / runs)
        for phases in p["components"].values():
            for ph in phases:
                phases[ph] /= runs
    out[None] = {"ms": stray / 1e9 / k}
    return out


def unscoped_share(sc: Scopes) -> float:
    """Share of device busy time (the operations of a device run one after
    another, so their sum) in operations no component claims."""
    total = lost = 0
    for sums in sc.sums.values():
        for (_, path), ps in sums.items():
            total += ps
            if component(path) == UNSCOPED:
                lost += ps
    return lost / total if total else 0.0


def table(src, depth: int = 2, least: float = 0.005) -> str:
    """What a person reads: per program its runs and device ms a run, and
    under it ms a run by component (forward, backward and recomputed side by
    side where the program has a backward pass). Components under `least`
    of their program are summed into one line. `src` is a trace file or a
    `Scopes`."""
    sc = read(src) if isinstance(src, str) else src
    progs = by_component(sc, depth)
    stray = progs.pop(None)
    lines = []
    for name, p in sorted(progs.items(), key=lambda kv: -kv[1]["ms"] * kv[1]["runs"]):
        lines.append(
            f"program {name}: {p['runs']} runs, {p['ms']:.3f} ms a run in "
            f"operations ({p['module_ms']:.3f} ms by the modules line)")
        comps = {c: dict(ph) for c, ph in p["components"].items()}
        rest = defaultdict(float)
        for c in [c for c, ph in comps.items()
                  if sum(ph.values()) < least * p["ms"] and c != UNSCOPED]:
            for ph, ms in comps.pop(c).items():
                rest[ph] += ms
        if rest:
            comps[f"(components under {100 * least:g} %)"] = dict(rest)
        phased = any(BACKWARD in ph or RECOMPUTED in ph for ph in comps.values())
        if phased:
            lines.append(f"  {'ms a run':>10} {'share':>7} {FORWARD:>10} "
                         f"{BACKWARD:>10} {RECOMPUTED:>10}  component")
        for c, ph in sorted(comps.items(), key=lambda kv: -sum(kv[1].values())):
            ms = sum(ph.values())
            row = f"  {ms:10.3f} {100 * ms / p['ms']:6.1f}%"
            if phased:
                row += "".join(f" {ph.get(x, 0.0):10.3f}"
                               for x in (FORWARD, BACKWARD, RECOMPUTED))
            lines.append(f"{row}  {c}")
    if stray["ms"]:
        lines.append(f"operations in no run of their program: {stray['ms']:.3f} ms")
    lines.append(f"unscoped: {100 * unscoped_share(sc):.2f} % of device busy time")
    return "\n".join(lines)


def main(argv=None) -> int:
    """`python3 -m bench_matrix.reduce.scopes <trace file or directory>
    [<regex on metric names>]`: the table of a trace, then the metrics of
    `layer_metrics_queued/` whose name matches (`^train_` for a train cell,
    `^(decode|prefill)_` for a serve cell: FSDP's step and the decode step are
    both `jit_step`) and that find something to read. After a `--trace 1` run
    of a cell the directory is `.bench_matrix_out/trace/<cell>`."""
    import os
    import sys

    from .. import spec
    from . import xplane

    src, *which = argv if argv is not None else sys.argv[1:]
    sc = read(xplane.find(src) if os.path.isdir(src) else src)
    print(table(sc))
    for name in spec.names("layer_metrics_queued"):
        if which and not re.search(which[0], name):
            continue
        m = spec.load("layer_metrics_queued", name)
        value = time_in(sc, m["args"]["program"], m["args"].get("scope"))
        if value is not None:
            print(f"{name} {value!r} {m['unit']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
