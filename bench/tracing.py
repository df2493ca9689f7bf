"""Reduction of a profiler trace of the window to per-layer numbers.

Read from the ``.xplane.pb`` that ``jax.profiler`` writes:

* the host plane ``/host:CPU``: the benchmark's own annotations (the
  window, ``bench_*`` spans around dispatch and waits), on the same clock
  as the device planes;
* each chip's plane ``/device:TPU:<id>``, line ``XLA Ops``: one event per
  HLO instruction run, named by the instruction's text
  (``%fusion.11 = f32[...] fusion(...)``). A ``while`` loop's event
  encloses its body's events; only the innermost (leaf) events count.

An instruction is put in a layer by the compiled program's HLO text,
which gives each instruction its ``op_name`` (the JAX name stack):

* ``grad``: op_name inside the benchmark loss's named scope;
* ``worker_kernel`` / ``server_kernel``: the Mosaic custom calls
  (``tpu_custom_call``) of ``admm_worker_select_update`` and of the
  server prox (``server_prox_update``, or ``prox_consensus`` on a mesh);
* ``collective``: all-reduce, all-gather, all-to-all, reduce-scatter and
  collective-permute instructions.

Times are seconds, averaged over the chips of the cell. ``busy`` is the
union of leaf intervals inside the window; ``collective_exposed`` is the
part of the collectives' intervals during which no other instruction
runs on that chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from problem import GRAD_SCOPE

WINDOW = "bench_window"
HOST_PREFIX = "bench_"
OPS_LINE = "XLA Ops"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"(?:calls|body|condition|branch_computations)=\{?%?"
                    r"([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|all-to-all|reduce-scatter"
                         r"|collective-permute)(-start|-done)?\(")
KERNELS = (("worker_kernel", ("admm_worker_select_update",)),
           ("server_kernel", ("server_prox_update", "prox_consensus")))
CATEGORIES = ("grad", "worker_kernel", "server_kernel", "collective")

Interval = Tuple[float, float]


def load(directory: str):
    """The ProfileData of the one trace under ``directory``."""
    from jax._src.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(paths)}")
    return ProfileData.from_file(paths[0])


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> op_name, from compiled HLO text.

    The compiler drops the op_name of the gradient's scatter-add in some
    programs (the SPMD epoch's), and of the sort it adds in front of a
    large scatter. The loss's scatter-add is the only scatter of an
    epoch, so an instruction that is or fuses a scatter, and a sort whose
    results feed one, take the loss's scope.
    """
    out, calls, users = {}, {}, {}
    comp, has_scatter, comp_calls = None, set(), {}
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        inst = m.group(1)
        s = _OP_NAME.search(line)
        out[inst] = s.group(1) if s else ""
        called = _CALLS.findall(line)
        calls[inst] = called
        comp_calls.setdefault(comp, []).extend(called)
        if " scatter(" in line:
            has_scatter.add(comp)
            calls[inst] = calls[inst] + [comp]
        rhs = line.split("=", 1)[1].split("metadata=")[0]
        for op in _OPERAND.findall(rhs):
            users.setdefault(op, []).append(inst)
    changed = True
    while changed:                         # computations calling scatters
        changed = False
        for c, cs in comp_calls.items():
            if c not in has_scatter and has_scatter.intersection(cs):
                has_scatter.add(c)
                changed = True
    scatter = {i for i, cs in calls.items() if has_scatter.intersection(cs)}

    def feeds_scatter(inst, seen=()):
        return any(u in scatter or (u.startswith("get-tuple-element")
                                    and u not in seen
                                    and feeds_scatter(u, seen + (u,)))
                   for u in users.get(inst, ()))
    for inst in out:
        if inst in scatter or (inst.startswith("sort")
                               and feeds_scatter(inst)):
            out[inst] = out[inst] or f"{GRAD_SCOPE}/scatter"
    return out


def instruction(event_name: str) -> str:
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def category(event_name: str, scopes: Dict[str, str]) -> Optional[str]:
    inst = instruction(event_name)
    if "tpu_custom_call" in event_name:
        for cat, names in KERNELS:
            if inst.startswith(names):
                return cat
    if _COLLECTIVE.search(event_name):
        return "collective"
    if GRAD_SCOPE in scopes.get(inst, ""):
        return "grad"
    return None


def leaves(events: List[Tuple[str, float, float]]):
    """Events that enclose no other event of their line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(events)
    stack: List[int] = []
    for k, (_, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(k)
    return [ev for ev, p in zip(events, parent) if not p]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def minus(a: List[Interval], b: List[Interval]) -> float:
    """Length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    total, k = 0.0, 0
    for s, e in a:
        cur = s
        while k < len(b) and b[k][1] <= cur:
            k += 1
        j = k
        while j < len(b) and b[j][0] < e:
            if b[j][0] > cur:
                total += b[j][0] - cur
            cur = max(cur, b[j][1])
            j += 1
        if cur < e:
            total += e - cur
    return total


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    host = pd.find_plane_with_name("/host:CPU")
    spans = []
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith(HOST_PREFIX):
                spans.append((ev.name, ev.start_ns, ev.start_ns
                              + ev.duration_ns))
    return spans


def _label(t: float, spans) -> str:
    """The innermost benchmark span that covers host time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside"


def reduce(pd, device_ids, hlo_text: str = "") -> dict:
    """Per-chip busy time, layer sums, collectives and the breakdown."""
    spans = _host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise RuntimeError(f"no {WINDOW!r} span in the trace")
    w0, w1 = windows[0]
    scopes = hlo_scopes(hlo_text)
    busy = 0.0
    sums = dict.fromkeys(CATEGORIES + ("collective_exposed",), 0.0)
    by_op: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev in device_ids:
        plane = pd.find_plane_with_name(f"/device:TPU:{dev}")
        if plane is None:
            raise RuntimeError(f"no plane for TPU {dev} in the trace")
        raw = [(ev.name, max(ev.start_ns, w0),
                min(ev.start_ns + ev.duration_ns, w1))
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        ops = leaves([r for r in raw if r[2] > r[1]])
        ivs = union([(s, e) for _, s, e in ops])
        busy += sum(e - s for s, e in ivs)
        coll, other = [], []
        for name, s, e in ops:
            cat = category(name, scopes)
            if cat is not None:
                sums[cat] += e - s
            (coll if cat == "collective" else other).append((s, e))
            inst = instruction(name)
            key = f"{inst} {cat}" if cat else inst
            by_op[key] = by_op.get(key, 0.0) + e - s
        sums["collective_exposed"] += minus(coll, other)
        prev = w0
        for s, e in ivs + [(w1, w1)]:
            if s > prev:
                gaps.append((_label(0.5 * (prev + s), spans), s - prev))
            prev = max(prev, e)
    n = len(device_ids)
    ns = 1e-9 / n
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy * ns, "chips": n,
            "op_s": {k: v * ns for k, v in sums.items()},
            "breakdown": {
                "device_ops": [[k, v * ns] for k, v in top_ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in top_gaps]}}
