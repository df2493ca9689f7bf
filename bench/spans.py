"""Reduction of the window's trace by the program's own names.

``tracing.py`` puts device instructions in the benchmark's layers; this
module splits what it leaves over (``epoch_other_ms``) and the host time
of the PS rounds by the names the program gives its work
(``repro.obs.names``):

* device: each leaf instruction inside the window that none of the
  gradient and the two kernels claims (``tracing.category``) goes to the
  innermost ``DEVICE_SCOPES`` name in its ``op_name`` (the compiled
  program's HLO text), or to ``unscoped``. The compiler leaves some
  instructions it makes without an ``op_name`` of the program's (none,
  or only an argument's name such as ``batch[0]``): the loops of
  dynamic-slice / dynamic-update-slice that carry out a large reshape
  between tiled layouts, async slices, copies of arguments. Such an
  instruction takes the ``op_name`` of the instruction that calls its
  computation, else of its nearest consumer that has one, else of its
  nearest such producer (``owners``). On a chip whose leaf instructions
  do not overlap, the stages and ``unscoped`` add up to
  ``epoch_other_ms`` exactly; a collective falls in its scope
  (``asybadmm.exchange``);
* host: on the thread that holds the window span, each ``HOST_SPANS``
  span's self time (its length minus the parts its child program spans
  cover) and count, clipped to the window; ``other`` is the window's
  time in no program span (the scheduler, the sink, the benchmark);
* compiles: the ``asybadmm.compile`` markers inside the window;
* ``idle_gaps``: the chips' longest idle gaps, labelled by the innermost
  benchmark or program span.

A program that names nothing (no ``DEVICE_SCOPES`` in ``repro.obs``)
reduces to None, and the readers of these metrics report nothing.

``bench/run.py`` does not call this module: its traced window hands the
readers ``tracing.reduce``'s output alone. The readers in
``bench/metrics/`` that read ``ctx["program"]`` (``gather_ms``,
``ps_syncs``, ...) take this module's ``reduce``; they report nothing,
and ``BENCHMARK.json`` does not list them, until ``traced_window`` passes
``spans.reduce(...)`` as ``ctx["program"]``. ``bench/tests/test_spans.py``
runs them on traces recorded on the chip.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import tracing
from problem import GRAD_SCOPE

try:
    from repro.obs.names import DEVICE_SCOPES, HOST_SPANS
except ImportError:                     # a program without its own names
    DEVICE_SCOPES = HOST_SPANS = frozenset()

COMPILE = "asybadmm.compile"
UNSCOPED = "unscoped"
CLAIMED = ("grad", "worker_kernel", "server_kernel")


def stage_of(op_name: str) -> str:
    """The innermost program scope named in ``op_name``, or
    ``unscoped``. The loss runs only inside ``asybadmm.grad``, so the
    loss's scope alone (which ``tracing.hlo_scopes`` gives the gradient's
    unnamed scatter) is that stage."""
    best, at = UNSCOPED, -1
    for name in DEVICE_SCOPES:
        k = op_name.rfind(name)
        if k > at:
            best, at = name, k
    if at < 0 and GRAD_SCOPE in op_name:
        return "asybadmm.grad"
    return best


def owners(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name, ``tracing.hlo_scopes`` with the
    compiler's unnamed instructions given their owner's op_name."""
    scopes = tracing.hlo_scopes(hlo_text)
    comp, where, caller, users, operands = None, {}, {}, {}, {}
    for line in hlo_text.splitlines():
        head = tracing._COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = tracing._INSTR.match(line)
        if not m:
            continue
        inst = m.group(1)
        where[inst] = comp
        for c in tracing._CALLS.findall(line):
            caller.setdefault(c, inst)
        rhs = line.split("=", 1)[1].split("metadata=")[0]
        operands[inst] = [o for o in tracing._OPERAND.findall(rhs)
                          if o != inst]
        for o in operands[inst]:
            users.setdefault(o, []).append(inst)

    def named(inst):
        return "/" in scopes.get(inst, "")     # not an argument's name

    def nearest(inst, edges):
        seen, frontier = {inst}, [inst]
        while frontier:
            nxt = []
            for i in frontier:
                for j in edges.get(i, ()):
                    if j in seen or j not in scopes:
                        continue
                    if named(j):
                        return scopes[j]
                    seen.add(j)
                    nxt.append(j)
            frontier = nxt
        return ""

    out = {i: s for i, s in scopes.items() if named(i)}

    def resolve(inst, depth=0):
        if inst in out or depth > 64:
            return out.get(inst, "")
        call = caller.get(where.get(inst))
        name = (resolve(call, depth + 1) if call else "") \
            or nearest(inst, users) or nearest(inst, operands)
        out[inst] = name
        return name
    for inst in scopes:
        resolve(inst)
    return out


def _leaf_ops(pd, dev, w0: int, w1: int) -> List[Tuple[str, int, int]]:
    """Chip ``dev``'s leaf instructions, clipped to the window."""
    plane = pd.find_plane_with_name(f"/device:TPU:{dev}")
    if plane is None:
        raise RuntimeError(f"no plane for TPU {dev} in the trace")
    raw = [(ev.name, max(ev.start_ns, w0),
            min(ev.start_ns + ev.duration_ns, w1))
           for line in plane.lines if line.name == tracing.OPS_LINE
           for ev in line.events]
    return tracing.leaves([r for r in raw if r[2] > r[1]])


def _device_stages(pd, device_ids, hlo_text: str, w0: int, w1: int
                   ) -> Dict[str, float]:
    scopes, owned = tracing.hlo_scopes(hlo_text), owners(hlo_text)
    sums = dict.fromkeys(sorted(DEVICE_SCOPES) + [UNSCOPED], 0.0)
    for dev in device_ids:
        for name, s, e in _leaf_ops(pd, dev, w0, w1):
            if tracing.category(name, scopes) in CLAIMED:
                continue
            sums[stage_of(owned.get(tracing.instruction(name), ""))] += e - s
    return {k: v * 1e-9 / len(device_ids) for k, v in sums.items()}


def _self_times(spans: List[Tuple[str, int, int]]) -> Dict[str, list]:
    """name -> [self ns, count] over properly nested spans of one
    thread."""
    out: Dict[str, list] = {}
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    stack: List[int] = []
    child = [0] * len(spans)
    for k, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(k)
    for (name, s, e), c in zip(spans, child):
        acc = out.setdefault(name, [0, 0])
        acc[0] += e - s - c
        acc[1] += 1
    return out


def reduce(pd, device_ids, hlo_text: str = "") -> Optional[dict]:
    """Seconds per device stage, host self time and count per host span,
    host time in no program span, and compile markers, in the window;
    None for a program that names nothing."""
    if not DEVICE_SCOPES:
        return None
    host = pd.find_plane_with_name("/host:CPU")
    window = [(line, ev) for line in host.lines for ev in line.events
              if ev.name == tracing.WINDOW]
    if not window:
        raise RuntimeError(f"no {tracing.WINDOW!r} span in the trace")
    main, ev = window[0]
    w0, w1 = ev.start_ns, ev.start_ns + ev.duration_ns
    named, compiles = [], 0
    for ev in main.events:
        s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
        if ev.name == COMPILE:
            compiles += w0 <= ev.start_ns <= w1
        elif ev.name in HOST_SPANS and e > s:
            named.append((ev.name, s, e))
    covered = sum(e - s for s, e in tracing.union(
        [(s, e) for _, s, e in named]))
    return {"window_s": (w1 - w0) * 1e-9,
            "stage_s": _device_stages(pd, device_ids, hlo_text, w0, w1),
            "host": {k: {"self_s": v[0] * 1e-9, "count": v[1]}
                     for k, v in sorted(_self_times(named).items())},
            "host_other_s": (w1 - w0 - covered) * 1e-9,
            "compiles": compiles}


def idle_gaps(pd, device_ids, top: int = 10) -> List[list]:
    """The ``top`` longest stretches of the window in which a chip runs
    no instruction, each as [label, seconds]: ``tracing.reduce``'s
    ``breakdown.idle_gaps``, labelled by the innermost benchmark
    (``bench_*``) *or program* (``HOST_SPANS``) span over the gap's
    midpoint. The compile markers have no length and label nothing."""
    labels = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for line in pd.find_plane_with_name("/host:CPU").lines
              for ev in line.events
              if ev.name.startswith(tracing.HOST_PREFIX)
              or (ev.name in HOST_SPANS and ev.name != COMPILE)]
    windows = [(s, e) for n, s, e in labels if n == tracing.WINDOW]
    if not windows:
        raise RuntimeError(f"no {tracing.WINDOW!r} span in the trace")
    w0, w1 = windows[0]
    gaps = []
    for dev in device_ids:
        prev = w0
        busy = tracing.union([(s, e) for _, s, e in _leaf_ops(pd, dev, w0,
                                                              w1)])
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                gaps.append([tracing._label(0.5 * (prev + s), labels),
                             (s - prev) * 1e-9])
            prev = max(prev, e)
    return sorted(gaps, key=lambda g: -g[1])[:top]


# ---------------------------------------------------------------------------
# what the readers in bench/metrics/ share
# ---------------------------------------------------------------------------

def stage_ms(ctx: dict, *stages: str):
    """Device milliseconds per epoch in ``stages``; None when the program
    names none of them or the trace has none of their time."""
    program = ctx.get("program")
    if program is None:
        return None
    seconds = sum(program["stage_s"].get(s, 0.0) for s in stages)
    if seconds <= 0:
        return None
    return 1000.0 * seconds / ctx["units"]


def host_spans(ctx: dict, prefix: str) -> Optional[list]:
    """The host span entries whose names start with ``prefix``; None
    when the trace has none."""
    program = ctx.get("program")
    if program is None:
        return None
    found = [v for k, v in program["host"].items() if k.startswith(prefix)]
    return found or None


def host_ms(ctx: dict, *prefixes: str):
    """Host self milliseconds per round in the spans named by
    ``prefixes``; None when the trace has none of them."""
    found = [v for p in prefixes for v in host_spans(ctx, p) or []]
    if not found:
        return None
    return 1000.0 * sum(v["self_s"] for v in found) / ctx["units"]
