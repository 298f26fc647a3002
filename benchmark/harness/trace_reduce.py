"""From the profiler's trace to numbers: the benchmark's own reduction.

``load(dir)`` turns the newest ``*.xplane.pb`` under a trace directory
into plain data (planes -> lines -> events, seconds), using nothing but
``jax.profiler.ProfileData``. Everything after that is arithmetic on
that plain data, so it is tested on a small recorded trace
(tests/fixtures/trace_small.json) without a chip.

Device planes are those whose name contains ``/device:TPU:``. On each,
the line named ``XLA Ops`` holds one event per executed HLO operation,
named by the instruction's whole text (``%paged_decode.5 = bf16[16,8,4,
128]{...} custom-call(...)``: a Pallas kernel's instruction carries its
``pallas_call`` name), and ``XLA Modules`` one per executed program
(``jit_<function>(<id>)``). A ``while`` or ``conditional`` is an event
that spans its body's events, so sums by operation leave those out;
the union that gives busy time is not affected.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = {"while", "conditional", "call"}
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def parse_op(text: str) -> dict:
    """An instruction's own name, opcode and result type from its text."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return {"op": text.lstrip("%"), "opcode": "", "shape": ""}
    found = _OPCODE.search(" " + rest)
    shape = "" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    return {"op": head.strip().lstrip("%"),
            "opcode": found.group(1) if found else "", "shape": shape}


def load(trace_dir: str) -> dict:
    """Plain data from the newest xplane file under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [{"name": ev.name, "start": ev.start_ns * 1e-9,
                       "dur": ev.duration_ns * 1e-9} for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if "/device:TPU:" in p["name"]]


def host_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/host:")]


def line_events(plane: dict, line_name: str) -> list[dict]:
    events = [ev for line in plane["lines"] if line["name"] == line_name
              for ev in line["events"]]
    if line_name == OPS_LINE:
        for ev in events:
            if "op" not in ev:
                ev.update(parse_op(ev["name"]))
    return events


def leaf_ops(plane: dict) -> list[dict]:
    """The plane's operations without the control-flow containers."""
    return [ev for ev in line_events(plane, OPS_LINE)
            if ev.get("opcode") not in CONTAINERS]


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by the intervals (overlaps counted once)."""
    total, end = 0.0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def gaps(intervals: list[tuple[float, float]], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    """The stretches of [t0, t1] that no interval covers."""
    out, cursor = [], t0
    for start, stop in sorted(intervals):
        if start > cursor:
            out.append((cursor, min(start, t1)))
        cursor = max(cursor, stop)
        if cursor >= t1:
            break
    if cursor < t1:
        out.append((cursor, t1))
    return [(a, b) for a, b in out if b > a]


def op_intervals(plane: dict) -> list[tuple[float, float]]:
    return [(ev["start"], ev["start"] + ev["dur"])
            for ev in line_events(plane, OPS_LINE)]


def traced_window(trace: dict) -> tuple[float, float]:
    """First start and last end of any device operation."""
    spans = [iv for plane in device_planes(trace)
             for iv in op_intervals(plane)]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(a for a, _ in spans), max(b for _, b in spans)


def busy(trace: dict) -> dict:
    """Seconds in which an operation ran on the device, averaged over
    the device planes, and the traced window's length."""
    t0, t1 = traced_window(trace)
    planes = device_planes(trace)
    per_device = [union_seconds(op_intervals(p)) for p in planes]
    return {"busy_s": sum(per_device) / len(per_device),
            "window_s": t1 - t0, "t0": t0, "t1": t1,
            "per_device_busy_s": per_device}


def op_seconds(trace: dict) -> dict[str, float]:
    """Device seconds by operation name, averaged over the devices."""
    planes = device_planes(trace)
    total: dict[str, float] = {}
    for plane in planes:
        for ev in leaf_ops(plane):
            label = f"{ev['op']} {ev['opcode']} {ev['shape']}"
            total[label] = total.get(label, 0.0) + ev["dur"]
    return {name: s / len(planes) for name, s in total.items()}


def seconds_matching(trace: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, events) of the operations whose own name matches
    `pattern`, on the first device plane (kernels run alike on all)."""
    rx = re.compile(pattern)
    events = [ev for ev in leaf_ops(device_planes(trace)[0])
              if rx.search(ev["op"])]
    return sum(ev["dur"] for ev in events), len(events)


def modules_running(trace: dict, op_pattern: str) -> list[dict]:
    """The executed programs (module events) in whose span an operation
    named like `op_pattern` ran: the decode program is the one that runs
    `paged_decode`, whatever jit calls it."""
    rx = re.compile(op_pattern)
    plane = device_planes(trace)[0]
    starts = sorted(ev["start"] for ev in leaf_ops(plane)
                    if rx.search(ev["op"]))
    out = []
    import bisect
    for mod in line_events(plane, MODULES_LINE):
        i = bisect.bisect_left(starts, mod["start"])
        if i < len(starts) and starts[i] <= mod["start"] + mod["dur"]:
            out.append(mod)
    return out


def module_events(trace: dict, pattern: str) -> list[dict]:
    rx = re.compile(pattern)
    return [ev for ev in line_events(device_planes(trace)[0], MODULES_LINE)
            if rx.search(ev["name"])]


def exposed_seconds(trace: dict, pattern: str) -> float:
    """Seconds in which an operation matching `pattern` (a collective)
    ran on a device while nothing else ran on it, averaged over devices."""
    rx = re.compile(pattern)
    per_device = []
    for plane in device_planes(trace):
        events = leaf_ops(plane)
        mine = [(e["start"], e["start"] + e["dur"]) for e in events
                if rx.search(e["op"])]
        other = [(e["start"], e["start"] + e["dur"]) for e in events
                 if not rx.search(e["op"])]
        covered = union_seconds(mine)
        # what of `mine` the others overlap: |mine| + |other| - |both|
        overlap = covered + union_seconds(other) - union_seconds(mine + other)
        per_device.append(covered - overlap)
    return sum(per_device) / len(per_device)


def _label(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]+", "_", name).strip("_")[:120]


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of the first device, each named by the host event that covers
    most of it (jax's own TraceMe events and the harness's
    TraceAnnotations on the host planes)."""
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    plane = device_planes(trace)[0]
    t0, t1 = traced_window(trace)
    idle = sorted(gaps(op_intervals(plane), t0, t1),
                  key=lambda g: g[0] - g[1])[:top]
    host = [(ev["start"], ev["start"] + ev["dur"], ev["name"])
            for hp in host_planes(trace) for line in hp["lines"]
            for ev in line["events"] if ev["dur"] > 0]
    named = []
    for a, b in idle:
        # The host event that covers most of the gap; of those that
        # cover it alike, the innermost (shortest).
        covering = [(min(b, stop) - max(a, start), start - stop, name)
                    for start, stop, name in host
                    if min(b, stop) > max(a, start)]
        name = max(covering)[2] if covering else "unattributed"
        named.append([_label("host:" + name), b - a])
    return {"device_ops": [[_label(n), s] for n, s in ops],
            "idle_gaps": named}
