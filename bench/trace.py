"""Reduce a profiler trace to device op intervals per chip and host spans.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``
and, beside it, ``<host>.trace.json.gz``.  The device timeline comes from
the ``.xplane.pb`` through ``jax.profiler.ProfileData``: on each
``/device:TPU:<n>`` plane, every event of the ``XLA Ops`` line is one
executed HLO instruction, named by its full HLO text.  Those events carry no
name stack there, so each op takes its JAX name stack (``tf_op``) from the
JSON export, joined on the device and the op's ``device_offset_ps``.  Host
spans are the ``jax.profiler.TraceAnnotation`` events whose names start with
``bench.``, which the benchmark opens around its own calls.  Device and host
events share one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r"= .*?\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class Op:
    text: str            # the HLO instruction, as the trace names the event
    start: int           # ns
    end: int             # ns
    stack: str = ""      # JAX name stack, "" where the export had none
    self_ns: int = 0     # the op's time less the ops nested inside it

    @property
    def name(self) -> str:
        """Instruction name, e.g. ``fusion.12``."""
        m = _INSTR.match(self.text)
        return m.group(1) if m else self.text

    @property
    def opcode(self) -> str:
        m = _OPCODE.search(self.text)
        return m.group(1) if m else ""


@dataclasses.dataclass
class Trace:
    chips: dict            # chip index -> [Op], sorted by start
    spans: list            # (name, start_ns, end_ns) of bench host spans
    window: tuple          # (start_ns, end_ns) of the span named ``window``

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def set_self_times(ops: list[Op]) -> None:
    """``self_ns`` = duration less the part covered by directly nested ops
    (a ``while`` contains the ops of its body on the same line)."""
    open_ops: list[Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        op.self_ns = op.end - op.start
        while open_ops and open_ops[-1].end <= op.start:
            open_ops.pop()
        if open_ops:
            parent = open_ops[-1]
            parent.self_ns -= min(op.end, parent.end) - op.start
        open_ops.append(op)


def union(intervals, lo: int | None = None, hi: int | None = None) -> list:
    """Merged, sorted ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def covered_ns(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(intervals, cover) -> list:
    """Parts of ``intervals`` (merged first) that ``cover`` does not hold."""
    cover = union(cover)
    out = []
    for s, e in union(intervals):
        cur = s
        for cs, ce in cover:
            if ce <= cur or cs >= e:
                continue
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo: int, hi: int) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` outside ``intervals``."""
    return subtract([(lo, hi)], intervals)


def _stacks(json_path: str) -> dict:
    """(chip, device_offset_ps) -> tf_op name stack, from the JSON export."""
    with gzip.open(json_path, "rt") as f:
        events = json.load(f)["traceEvents"]
    chip_of = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            m = DEVICE_PLANE.match(e.get("args", {}).get("name", ""))
            if m:
                chip_of[e["pid"]] = int(m.group(1))
    out = {}
    for e in events:
        args = e.get("args") or {}
        if e.get("pid") in chip_of and "tf_op" in args:
            out[(chip_of[e["pid"]], int(args["device_offset_ps"]))] = \
                args["tf_op"]
    return out


def find_files(trace_dir: str) -> tuple[str, str | None]:
    xplanes = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    if len(xplanes) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {xplanes}")
    stem = xplanes[0][: -len(".xplane.pb")]
    js = stem + ".trace.json.gz"
    return xplanes[0], (js if os.path.exists(js) else None)


def reduce(trace_dir: str, window: str = "window") -> Trace:
    """The reduced trace of the one profile session under ``trace_dir``."""
    from jax.profiler import ProfileData

    xplane, js = find_files(trace_dir)
    stacks = _stacks(js) if js else {}
    data = ProfileData.from_file(xplane)
    chips, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                ops = []
                for ev in line.events:
                    offset = dict(ev.stats).get("device_offset_ps")
                    start = int(ev.start_ns)
                    ops.append(Op(
                        ev.name, start, start + int(ev.duration_ns),
                        stacks.get((chip, offset), "")))
                set_self_times(ops)
                ops.sort(key=lambda o: o.start)
                chips[chip] = ops
            elif plane.name.startswith("/host"):
                spans.extend(
                    (ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    wins = [(s, e) for n, s, e in spans if n == window]
    if len(wins) != 1:
        raise ValueError(f"expected one host span {SPAN_PREFIX}{window}, "
                         f"found {len(wins)}")
    if not chips:
        raise ValueError(f"no {OPS_LINE} line on any TPU plane of {xplane}")
    return Trace(chips, spans, wins[0])


def busy_ns(trace: Trace, chip: int) -> int:
    lo, hi = trace.window
    return covered_ns(union(((o.start, o.end) for o in trace.chips[chip]),
                            lo, hi))


def span_at(trace: Trace, t: int) -> str:
    """The innermost bench host span open at ``t``, or ``none``."""
    best = None
    for name, s, e in trace.spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (self time summed per instruction
    name, averaged over chips) and the longest idle gaps, each labelled by
    the bench span the host had open."""
    lo, hi = trace.window
    per_op: dict[str, int] = {}
    idle = []
    for chip, ops in trace.chips.items():
        for o in ops:
            if lo <= o.start < hi:
                per_op[o.name] = per_op.get(o.name, 0) + o.self_ns
        for s, e in gaps(((o.start, o.end) for o in ops), lo, hi):
            idle.append((e - s, chip, s))
    n = len(trace.chips)
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(reverse=True)
    return {
        "device_ops": [[k, v / n / 1e9] for k, v in device_ops],
        "idle_gaps": [[f"{span_at(trace, s + d // 2)}@chip{c}", d / 1e9]
                      for d, c, s in idle[:top]],
    }
