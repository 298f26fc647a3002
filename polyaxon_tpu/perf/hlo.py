"""Collective accounting and overlap measurement over compiled HLO.

The sharded program GSPMD emits makes every byte of inter-device
traffic explicit as a collective instruction; parsing the
post-optimization module therefore gives an exact op census and a
shape-derived traffic estimate without running a single step. Wire
bytes use the standard ring-algorithm costs **per participant**:

    all-reduce          2 * B * (g-1)/g     (reduce-scatter + all-gather)
    all-gather          B_out * (g-1)/g     (B_out = gathered result)
    reduce-scatter      B_out * (g-1)       (receives (g-1)/g of input)
    all-to-all          B * (g-1)/g         (keeps 1/g locally)
    collective-permute  B                   (one hop per pair)

where ``g`` is the replica-group size. These are estimates of traffic
*volume* — topology (ICI hop count, DCN crossings) is out of scope; the
budget gate cares about op counts and byte deltas, both of which these
formulas rank faithfully.

Overlap measurement (ISSUE 12): counting collectives says nothing about
whether their latency is *hidden* — the same program swings multiples
depending on whether XLA schedules its collectives against independent
compute or serializes them (GSPMD §3.4; DeepSpeed-Ulysses makes the
same point for all-to-alls). Async collectives appear in three textual
encodings, all handled here:

- the classic ``-start``/``-done`` pair: the transfer is in flight
  between the two instructions, so everything scheduled between them
  is by construction independent of the payload (the ``-start`` result
  tuple is only consumable by its ``-done``);
- a sync-form instruction annotated with ``frontend_attributes={...
  async_collective_name=...}``: in flight until its first consumer;
- the TPU latency-hiding scheduler's **continuation fusions** in
  scheduled modules (``is_scheduled=true``): a
  ``%async-collective-start[.N] = (...) fusion(..., calls=%fc)`` whose
  callee issues the collective, paired by NAME SUFFIX with an
  ``%async-collective-done[.N]`` fusion that retires it. The transfer
  is in flight strictly between the two fusions.

Either way the *overlap window* of an async collective is the
instruction span from issue to retirement (first consumer for the
first two forms, the suffix-matched done fusion for the third), and
the compute FLOPs scheduled inside that span bound how much of the
transfer can hide. An unannotated sync collective in the schedule
spine has an empty window — 0 overlap. A collective fused WITH compute
(a plain fusion whose callee contains one) overlaps its own fusion's
compute: its window is that single fusion.

Census dedup rules for scheduled TPU modules (each logical transfer
appears in up to three fused computations): a transfer is counted AT
its ``async-collective-start*`` fusion only; ``async-collective-done*``
fusions and computations named ``async_collective_fusion*`` (the
compute-side continuations, which repeat the collective a third time)
are never censused. The schedule *spine* is every computation that is
not a fusion callee (``calls=`` target) — while bodies, branch
computations and ENTRY stay spine, so their collectives count exactly
once.

The time model converts both sides to seconds with two documented
v5e-class constants (``PEAK_FLOPS_PER_S``, ``ICI_BYTES_PER_S``):
``coll_time = wire_bytes / ICI_BYTES_PER_S`` and ``window_compute =
window_flops / PEAK_FLOPS_PER_S``; the hidden fraction of one op is
``min(coll_time, window_compute) / coll_time`` and a schedule's
``overlap_ratio`` is the hidden fraction of its TOTAL collective time.
The constants are a ranking model, not a profiler: budgets are floors
measured with the same model, so only consistency matters — but the
ratio is also dimensionally honest (a 1 MiB all-gather cannot be
"hidden" by two scalar adds).

FLOP attribution inside windows: ``dot`` counts
``2 * result_elements * K`` (K = the lhs contracting-dim product);
``convolution`` — which is what scheduled TPU modules turn every
matmul into — counts ``2 * result_elements * K`` with K = the product
of rhs dims whose ``dim_labels`` char is not ``o`` (input-feature and
kernel-spatial dims); ``fusion``/``call`` recurse into their callee
(memoized per computation); every other payload op counts its result
elements. Bookkeeping ops (parameter, constant, tuple plumbing,
bitcast, copies, custom-calls) and other collectives count zero (a
collective inside another's window is communication that overlaps on
its own account, not compute hiding this one).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Time-model constants (v5e class; see module docstring — a consistent
# ranking model shared by measurement and budget floors, not a profiler).
PEAK_FLOPS_PER_S = 1.97e14   # bf16 peak per chip
ICI_BYTES_PER_S = 4.5e10     # per-chip interconnect bandwidth


def require_modelled_device(device) -> None:
    """The overlap model's constants are one chip's. HLO compiled for
    the CPU mesh is ranked with them by design (the budget floors were
    taken the same way), but a TPU of another kind is refused rather
    than modelled with a v5e's peak."""
    from polyaxon_tpu.runtime.flops import peak_flops

    if device.platform == "tpu" and peak_flops(device) != PEAK_FLOPS_PER_S:
        raise ValueError(
            f"perf/hlo.py models overlap with v5e constants; HLO for "
            f"`{device.device_kind}` needs its own PEAK_FLOPS_PER_S / "
            "ICI_BYTES_PER_S")


# The named scope a pallas_call's `name=` opens sits right before
# `/pallas_call` in the custom call's op_name, wrapped by whatever
# transform traced it: `.../flash_fwd/pallas_call`,
# `.../jvp(flash_fwd)/pallas_call`,
# `.../transpose(jvp(flash_bwd_dq))/pallas_call`.
_PALLAS_CALL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?/(?:\w+\()*([\w\-]+)\)*/pallas_call"')


def pallas_kernels(hlo_text: str) -> dict[str, int]:
    """Mosaic kernels in a compiled module, by ``pallas_call`` name →
    number of call sites. This is what tells a program that runs the
    kernel from one that took a reference path: a kernel that gave way
    (or ran interpreted) leaves no ``tpu_custom_call``."""
    found: dict[str, int] = {}
    for line in hlo_text.splitlines():
        match = _PALLAS_CALL_RE.search(line)
        if match:
            found[match.group(1)] = found.get(match.group(1), 0) + 1
    return found

# f8 variants first so "f8e4m3fn" doesn't half-match "f8".
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%name = <result-type> <op>(`. The result type is everything between
# `=` and the op token — matched that way because TPU HLO layouts embed
# colons and parens (`bf16[4,2048]{2,1,0:T(2,128)(2,1)S(1)}`) that
# defeat any character-class spelling. The op token is the FIRST
# whitespace-preceded `word(` after the `=` (layout parens like
# `T(2,128)` follow `:` or `)`, never whitespace, so they can't match).
_ASSIGN_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w\.\-]+)\s*=\s*(?P<rest>.+)$")
_GENERIC_OP_RE = re.compile(r"(?:^|\s)(?P<op>[a-zA-Z][\w\-]*)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_PAIRS_RE = re.compile(r"source_target_pairs=\{\{")
_REF_RE = re.compile(r"%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w\.\-]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w\d]+)_([\w\d]+)->([\w\d]+)")
_HEADER_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)")

_COLLECTIVE_OP_TOKENS = frozenset(
    list(COLLECTIVE_KINDS) + [k + "-start" for k in COLLECTIVE_KINDS])

# Continuation-fusion naming in scheduled TPU modules (see module
# docstring census rules). Instruction-name prefixes for the paired
# start/done fusions; computation-name prefix for the compute-side
# continuations that must never be censused.
_ASYNC_START_PREFIX = "async-collective-start"
_ASYNC_DONE_PREFIX = "async-collective-done"
_ASYNC_CONT_COMP_PREFIX = "async_collective_fusion"

# Window ops that carry no arithmetic payload: plumbing, layout
# changes, async copy halves, opaque custom-calls (their cost is not
# shape-derivable; undercounting is the conservative direction for a
# floor), and collectives themselves.
_ZERO_FLOP_OPS = frozenset(
    ["parameter", "constant", "tuple", "get-tuple-element", "bitcast",
     "copy", "copy-start", "copy-done", "after-all", "partition-id",
     "replica-id", "opt-barrier", "broadcast", "iota", "reshape",
     "transpose", "slice", "dynamic-slice", "dynamic-update-slice",
     "pad", "send", "send-done", "recv", "recv-done", "custom-call"]
    + list(_COLLECTIVE_OP_TOKENS)
    + [k + "-done" for k in COLLECTIVE_KINDS])


@dataclasses.dataclass
class CollectiveOp:
    kind: str            # canonical kind (no -start suffix)
    name: str            # HLO instruction name
    result_bytes: int    # total bytes of the result shape(s)
    group_size: int      # replica-group participants
    wire_bytes: float    # estimated bytes on the wire per participant
    line: str            # the source line (diagnostics / report detail)
    is_async: bool = False      # -start form, annotated, or fused
    window_ops: int = 0         # instructions inside the overlap window
    window_flops: float = 0.0   # attributed compute FLOPs in the window
    overlap_ratio: float = 0.0  # hidden fraction of this op's wire time


@dataclasses.dataclass
class _Instr:
    name: str
    op: str
    result_type: str
    operands: tuple
    line: str
    args: str = ""  # raw operand span (shape extraction for dot/conv)


def _shape_bytes_list(type_str: str) -> list[int]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token[], opaque[] — carry no payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * size)
    return out


def _result_bytes(type_str: str, async_start: bool) -> int:
    """Payload bytes of a collective's result type.

    Sync form: the (possibly tuple) result IS the payload — sum it.
    ``-start`` form: the result tuple aliases (source, destination,
    context scalars); summing would double-count the transfer, so take
    the largest member (the destination — equal to the sync form's
    result for every kind)."""
    sizes = _shape_bytes_list(type_str)
    if not sizes:
        return 0
    return max(sizes) if async_start else sum(sizes)


def _group_size(line: str, n_devices: Optional[int]) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        first = [p for p in m.group(1).split(",") if p.strip()]
        return max(len(first), 1)
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    if _PAIRS_RE.search(line):
        return 2  # permute: pairwise
    return max(n_devices or 1, 1)


def _wire_bytes(kind: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * result_bytes * (g - 1) / g
    if kind == "all-gather":
        return result_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return float(result_bytes) * (g - 1)
    if kind == "all-to-all":
        return result_bytes * (g - 1) / g
    if kind == "collective-permute":
        return float(result_bytes)
    raise ValueError(f"unknown collective kind {kind!r}")


def _operand_span(rest: str, open_idx: int) -> str:
    """The operand list inside the op's balanced parens — attributes
    after the close paren (``to_apply=%sum``, ``calls=%fused``) must
    not read as dataflow consumers."""
    depth = 0
    for i in range(open_idx, len(rest)):
        c = rest[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return rest[open_idx + 1:i]
    return rest[open_idx + 1:]


def _parse_instruction(line: str) -> Optional[_Instr]:
    assign = _ASSIGN_RE.match(line)
    if not assign:
        return None
    rest = assign.group("rest")
    m = _GENERIC_OP_RE.search(rest)
    if not m:
        return None
    args = _operand_span(rest, m.end() - 1)
    return _Instr(
        name=assign.group("name"),
        op=m.group("op"),
        result_type=rest[: m.start()],
        operands=tuple(_REF_RE.findall(args)),
        line=line,
        args=args,
    )


def _computation_blocks(hlo_text: str) -> list[tuple[str, list[_Instr]]]:
    """(name, instruction list) per computation, in textual order
    (= schedule order for ``is_scheduled=true`` modules — the form the
    overlap windows are measured on). Header lines (`%comp (args) ->
    type {`) carry no `=` so they never parse as instructions; bare
    fixture text without braces lands in one implicit ``""`` block."""
    blocks: list[tuple[str, list[_Instr]]] = []
    orphans: list[_Instr] = []
    current: Optional[list[_Instr]] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("}"):
            current = None
            continue
        instr = _parse_instruction(line)
        if instr is None:
            if stripped.endswith("{") and "HloModule" not in stripped:
                header = _HEADER_NAME_RE.match(stripped)
                current = []
                blocks.append((header.group(1) if header else "", current))
            continue
        (orphans if current is None else current).append(instr)
    if orphans:
        blocks.append(("", orphans))
    return [(name, block) for name, block in blocks if block]


def _num_elements(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _operand_dims(args: str, index: int) -> Optional[list[int]]:
    """Dims of the index-th shaped operand (operand shapes appear in
    call order inside the operand span)."""
    shapes = _SHAPE_RE.findall(args)
    if index >= len(shapes):
        return None
    return [int(d) for d in shapes[index][1].split(",") if d]


class _ModuleGraph:
    """Computation index for one HLO module: fusion-callee detection
    (spine = not a ``calls=`` target), memoized per-computation FLOPs
    with fusion/call recursion, and deduped inner-collective lookup."""

    def __init__(self, blocks: list[tuple[str, list[_Instr]]]):
        self.comps: dict[str, list[_Instr]] = {}
        for name, block in blocks:
            self.comps.setdefault(name, block)
        self.fusion_targets: set[str] = set()
        for _, block in blocks:
            for instr in block:
                if instr.op == "fusion":
                    m = _CALLS_RE.search(instr.line)
                    if m:
                        self.fusion_targets.add(m.group(1))
        self._flops_memo: dict[str, float] = {}

    def instr_flops(self, instr: _Instr) -> float:
        """Attributed compute FLOPs of one instruction (module
        docstring: dot/conv = 2·result·K, fusion/call recurse, other
        payload ops = result elements, plumbing/collectives = 0)."""
        if instr.op in ("fusion", "call"):
            m = _CALLS_RE.search(instr.line) or _TO_APPLY_RE.search(instr.line)
            return self.comp_flops(m.group(1)) if m else 0.0
        if instr.op in _ZERO_FLOP_OPS:
            return 0.0
        elems = _num_elements(instr.result_type)
        if instr.op == "dot":
            m = _CONTRACT_RE.search(instr.line)
            lhs_dims = _operand_dims(instr.args, 0)
            if m is not None and lhs_dims is not None:
                k = 1
                for idx in (int(d) for d in m.group(1).split(",") if d):
                    if 0 <= idx < len(lhs_dims):
                        k *= lhs_dims[idx]
                return 2.0 * elems * k
            return 2.0 * elems
        if instr.op == "convolution":
            # K = product of rhs dims whose dim_labels char != 'o'
            # (input-feature + kernel-spatial): each output element is
            # a K-term dot product. Covers the `bf0_0oi->b0f` spelling
            # scheduled TPU modules lower every matmul to.
            m = _DIM_LABELS_RE.search(instr.line)
            rhs_dims = _operand_dims(instr.args, 1)
            if m is not None and rhs_dims is not None:
                k = 1
                for label, dim in zip(m.group(2), rhs_dims):
                    if label != "o":
                        k *= dim
                return 2.0 * elems * k
            return 2.0 * elems
        return float(elems)

    def comp_flops(self, name: str) -> float:
        if name in self._flops_memo:
            return self._flops_memo[name]
        self._flops_memo[name] = 0.0  # cycle guard (malformed input)
        block = self.comps.get(name)
        if block is not None:
            self._flops_memo[name] = sum(
                self.instr_flops(instr) for instr in block)
        return self._flops_memo[name]

    def inner_collectives(
            self, name: str, _seen: Optional[set] = None) -> list[_Instr]:
        """Collective instructions reachable from computation ``name``
        through nested fusions — EXCLUDING ``async_collective_fusion*``
        computations, whose collectives are compute-side repeats of a
        transfer censused at its start fusion (module docstring)."""
        if _seen is None:
            _seen = set()
        if (name in _seen or name not in self.comps
                or name.startswith(_ASYNC_CONT_COMP_PREFIX)):
            return []
        _seen.add(name)
        out: list[_Instr] = []
        for instr in self.comps[name]:
            if instr.op in _COLLECTIVE_OP_TOKENS:
                out.append(instr)
            elif instr.op == "fusion":
                m = _CALLS_RE.search(instr.line)
                if m:
                    out.extend(self.inner_collectives(m.group(1), _seen))
        return out


def _first_consumer(block: list[_Instr], i: int) -> int:
    name = block[i].name
    for j in range(i + 1, len(block)):
        if name in block[j].operands:
            return j
    return len(block)


def _make_op(graph: _ModuleGraph, coll: _Instr, is_async: bool,
             window: list[_Instr], n_devices: Optional[int]) -> CollectiveOp:
    async_start = coll.op.endswith("-start")
    kind = coll.op[: -len("-start")] if async_start else coll.op
    result_bytes = _result_bytes(coll.result_type, async_start)
    g = _group_size(coll.line, n_devices)
    wire = _wire_bytes(kind, result_bytes, g)
    window_flops = 0.0
    ratio = 0.0
    if is_async:
        window_flops = sum(graph.instr_flops(w) for w in window)
        coll_s = wire / ICI_BYTES_PER_S
        if coll_s > 0:
            ratio = min(coll_s, window_flops / PEAK_FLOPS_PER_S) / coll_s
    return CollectiveOp(
        kind=kind,
        name=coll.name,
        result_bytes=result_bytes,
        group_size=g,
        wire_bytes=wire,
        line=coll.line.strip(),
        is_async=is_async,
        window_ops=len(window) if is_async else 0,
        window_flops=window_flops,
        overlap_ratio=round(ratio, 6),
    )


def parse_collectives(hlo_text: str,
                      n_devices: Optional[int] = None) -> list[CollectiveOp]:
    """All logical collective transfers in a post-optimization HLO
    module, each annotated with its overlap-window measurement.

    Census (module docstring dedup rules): plain collectives in spine
    computations (async ``-start``/``-done`` pairs counted once at the
    ``-start``); transfers wrapped in continuation fusions counted at
    their ``async-collective-start*`` fusion with the window running to
    the suffix-matched ``async-collective-done*``; other fusions whose
    callees contain collectives counted with the fusion itself as the
    window (the transfer overlaps its own fusion's compute)."""
    blocks = _computation_blocks(hlo_text)
    graph = _ModuleGraph(blocks)
    ops: list[CollectiveOp] = []
    for comp_name, block in blocks:
        if comp_name in graph.fusion_targets:
            continue  # fusion callee: censused via its caller
        for i, instr in enumerate(block):
            if instr.op in _COLLECTIVE_OP_TOKENS:
                is_async = (instr.op.endswith("-start")
                            or "async_collective_name" in instr.line)
                window = block[i + 1:_first_consumer(block, i)]
                ops.append(_make_op(graph, instr, is_async, window, n_devices))
                continue
            if instr.op != "fusion":
                continue
            if instr.name.startswith(_ASYNC_DONE_PREFIX):
                continue  # retirement half: censused at its -start twin
            m = _CALLS_RE.search(instr.line)
            inner = graph.inner_collectives(m.group(1)) if m else []
            if not inner:
                continue
            if instr.name.startswith(_ASYNC_START_PREFIX):
                done = _ASYNC_DONE_PREFIX + instr.name[
                    len(_ASYNC_START_PREFIX):]
                j = next((k for k in range(i + 1, len(block))
                          if block[k].name == done), None)
                if j is None:
                    j = _first_consumer(block, i)
                window = block[i + 1:j]
                for coll in inner:
                    ops.append(_make_op(graph, coll, True, window, n_devices))
            else:
                # Collective fused with compute: the transfer's window
                # is its own fusion (its compute can hide it; a
                # compute-free wrapper honestly measures 0).
                for coll in inner:
                    ops.append(_make_op(graph, coll, True, [instr], n_devices))
    return ops


def summarize_collectives(ops: list[CollectiveOp]) -> dict:
    """Aggregate an op list into the budget-comparable report shape."""
    counts: dict[str, int] = {}
    bytes_by_kind: dict[str, int] = {}
    for op in ops:
        counts[op.kind] = counts.get(op.kind, 0) + 1
        bytes_by_kind[op.kind] = (
            bytes_by_kind.get(op.kind, 0) + int(op.wire_bytes))
    return {
        "counts": dict(sorted(counts.items())),
        "wire_bytes_by_kind": dict(sorted(bytes_by_kind.items())),
        "est_wire_bytes_per_step": int(sum(o.wire_bytes for o in ops)),
        "n_collectives": len(ops),
    }


def summarize_overlap(ops: list[CollectiveOp]) -> dict:
    """Schedule-level overlap report: the hidden fraction of TOTAL
    estimated collective time (sync collectives contribute full time
    and zero hiding). A program with no wire traffic has nothing to
    hide — ratio 1.0 by convention, so the budget gate never fails a
    schedule for being communication-free."""
    coll_s = 0.0
    hidden_s = 0.0
    async_by_kind: dict[str, int] = {}
    n_async = n_sync = 0
    for op in ops:
        t = op.wire_bytes / ICI_BYTES_PER_S
        if t <= 0:
            continue
        coll_s += t
        if op.is_async:
            n_async += 1
            async_by_kind[op.kind] = async_by_kind.get(op.kind, 0) + 1
            hidden_s += min(t, op.window_flops / PEAK_FLOPS_PER_S)
        else:
            n_sync += 1
    return {
        "overlap_ratio": round(hidden_s / coll_s, 4) if coll_s else 1.0,
        "n_async_collectives": n_async,
        "n_sync_collectives": n_sync,
        "async_by_kind": dict(sorted(async_by_kind.items())),
        "coll_time_us": round(coll_s * 1e6, 3),
        "hidden_time_us": round(hidden_s * 1e6, 3),
    }
