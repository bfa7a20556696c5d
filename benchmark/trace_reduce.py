"""From a profiler trace (``.xplane.pb``) to device intervals per scope.

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds (my
chip run, PR 24): one plane ``/device:TPU:<n>`` per chip, with a line
``XLA Ops`` whose events are the HLO operations that ran, each named by
its instruction's text (``%fusion.92 = f32[...] fusion(...)``) and
carrying no scope. The scope comes from the compiled step's own text,
where every instruction has ``metadata={op_name="..."}``, the path that
``jax.named_scope`` wrote: ``jit(step)/jvp(lstm0)/...`` forward,
``.../transpose(jvp(lstm0))/...`` backward (``scopes_from_hlo``). A
``while`` is one event that encloses its body's events on the same line,
so every sum here is a union of intervals, never a sum of durations.
Host planes hold one line per thread with the ``TraceAnnotation`` spans.

``reduce_file`` gives a ``Reduced``: busy seconds per device, seconds
per scope, the longest operations, the longest idle gaps named by the
host span that covers most of each, and the collective time during
which no other operation ran on that device.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]          # start, end, in seconds

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]+)\"", re.M)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
HOST_SPANS = ("bench_reader", "bench_feeder", "bench_event_handler")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``a`` (as a union) that no interval of ``b`` covers."""
    out, cover = [], union(b)
    for s, e in union(a):
        at = s
        for cs, ce in cover:
            if ce <= at:
                continue
            if cs >= e:
                break
            if cs > at:
                out.append((at, cs))
            at = max(at, ce)
        if at < e:
            out.append((at, e))
    return out


def exposed(collectives: Iterable[Interval],
            others: Iterable[Interval]) -> float:
    """Seconds of collective time during which no other operation runs."""
    return total(subtract(collectives, others))


@dataclasses.dataclass
class Op:
    name: str
    scope: str          # the op_name path, "" where the trace has none
    start: float
    end: float


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Op]

    def intervals(self, keep=None) -> List[Interval]:
        return [(o.start, o.end) for o in self.ops
                if keep is None or keep(o)]


@dataclasses.dataclass
class Reduced:
    devices: List[Device]
    host: Dict[str, List[Interval]]     # span name -> intervals
    window_s: float

    # ---------------------------------------------------------- shares
    def busy(self) -> List[float]:
        return [total(d.intervals()) for d in self.devices]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        b = self.busy()
        return sum(b) / len(b)

    def idle_share(self) -> float:
        """1 - busy over the window, on the worst device."""
        return 1.0 - min(self.busy()) / self.window_s

    def scope_seconds(self, pattern: str) -> float:
        """Union of the device time of every operation whose scope path
        matches, on the worst device."""
        rx = re.compile(pattern)
        return max(total(d.intervals(lambda o: rx.search(o.scope)))
                   for d in self.devices)

    def exposed_collective_s(self) -> float:
        worst = 0.0
        for d in self.devices:
            coll = d.intervals(lambda o: COLLECTIVE.match(o.name))
            rest = d.intervals(lambda o: not COLLECTIVE.match(o.name)
                               and not o.name.startswith("while"))
            worst = max(worst, exposed(coll, rest))
        return worst

    # ------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time on device 0, by
        kind of layer and direction where the compiled step names a
        layer, else by instruction;
        enclosing ``while`` events are left out (their bodies count)."""
        sums: Dict[str, float] = {}
        for o in self.devices[0].ops:
            if o.name.startswith("while"):
                continue
            key = scope_label(o.scope) or o.name
            sums[key] = sums.get(key, 0.0) + (o.end - o.start)
        return [[k, v] for k, v in sorted(sums.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps between operations on device 0, each named
        by the benchmark's host span that covers most of it."""
        busy = union(self.devices[0].intervals())
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
        out = []
        for length, s, e in sorted(gaps, reverse=True)[:n]:
            label, best = "trainer", 0.0
            for name, spans in self.host.items():
                cover = (e - s) - total(subtract([(s, e)], spans))
                if cover > best:
                    label, best = name, cover
            out.append([label, length])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def scope_label(path: str) -> str:
    """``jit(step)/jit(main)/transpose(jvp(lstm0))/while/body/dot`` ->
    ``lstm0.bwd``, ``.../jvp(res2b_a_conv)/conv`` -> ``conv.fwd``: the
    kind of layer and the direction. What carries a
    path but no layer (the optimizer's update, the metrics) is
    ``no_layer_scope``; what carries no path at all keeps its
    instruction's name."""
    if not path:
        return ""
    m = re.search(r"transpose\(jvp\(([^()]+)\)\)", path)
    if m:
        return layer_kind(m.group(1)) + ".bwd"
    m = re.search(r"jvp\(([^()]+)\)", path)
    if m:
        return layer_kind(m.group(1)) + ".fwd"
    return "no_layer_scope"


def layer_kind(layer: str) -> str:
    """``res2b_a_conv`` -> ``conv``, ``lstm1_proj`` -> ``proj``, ``lstm0``
    -> ``lstm0``: the last word of a layer's name, which in this repo's
    models says what kind of layer it is. A deep model has hundreds of
    scopes of a percent each; ten kinds cover its step."""
    return layer.rsplit("_", 1)[-1]


def scopes_from_hlo(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` from a compiled module's text."""
    return {name: scope for name, scope in INSTRUCTION.findall(text)}


def instruction(event_name: str) -> str:
    """``%fusion.92 = f32[...] fusion(...)`` -> ``fusion.92``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def reduce_file(path: str, *, chips: Optional[int] = None,
                window_s: Optional[float] = None,
                scopes: Optional[Dict[str, str]] = None) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    scopes = scopes or {}
    devices, host = [], {name: [] for name in HOST_SPANS}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = instruction(ev.name)
                    ops.append(Op(name, scopes.get(name, ""),
                                  ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
            devices.append(Device(int(m.group(2)), ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host:
                        host[ev.name].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    devices = sorted((d for d in devices if d.ops), key=lambda d: d.index)
    if chips is not None:
        devices = devices[:chips]
    if not devices:
        raise RuntimeError(f"{path}: no operation ran on a device")
    if window_s is None:                # first op's start to last op's end
        window_s = (max(o.end for d in devices for o in d.ops)
                    - min(o.start for d in devices for o in d.ops))
    return Reduced(devices, host, window_s)


def trace_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"{trace_dir}: expected one .xplane.pb, "
                           f"found {files}")
    return files[0]


def reduce_dir(trace_dir: str, **kw) -> Reduced:
    return reduce_file(trace_file(trace_dir), **kw)


def describe(path: str, out=sys.stdout) -> None:
    """What a trace holds: planes, lines, a few events with their
    statistics. For a look by hand before code is written against it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name, file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for ev in events[:6]:
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in ev.stats}
                print(f"    {ev.name[:60]!r} start {ev.start_ns:.0f} "
                      f"dur {ev.duration_ns:.0f} {stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
