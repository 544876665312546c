"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device numbers.

* The traced window runs from the start of the first ``bench.round`` host
  annotation to the end of the last one.
* ``busy_s``: the union of the device's operation intervals (the ``XLA Ops``
  line of each ``/device:`` plane) inside the window, averaged over the chips
  that ran anything; ``window_s``: the window's length.
* ``fused_device_s``: device time of the fused migrate program's module
  (``XLA Modules`` events named ``jit__fused_round...``), per traced
  ``bench.decide``.
* ``breakdown``: the ten device operations that took most time, and the ten
  longest idle gaps of the first chip, cut where the host's activity changes
  and named by it: a ``decide()`` stage (``bench.decide`` plus the round's
  own stage timings, in the order the scheduler runs them), the Simulator's
  work between decides (``sim``), or the harness (``bench``).
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

FUSED_MODULE = "jit__fused_round"
#: decide()'s stages in the order they run (RoundDecision.timings keys)
STAGES = ("schedule_s", "place_s", "pack_s", "migrate_s")

Interval = Tuple[float, float]


def load(path: str):
    """A trace from an ``.xplane.pb`` file, gzip-compressed when it ends in ``.gz``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted disjoint
    intervals."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``busy`` (sorted, disjoint) leaves idle."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _host_annotations(pd, name: str) -> List[Interval]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name == name]
    return sorted(out)


def host_segments(decides: Sequence[Interval], timings: Sequence[Dict[str, float]],
                  rounds: Sequence[Interval]) -> List[Tuple[float, float, str]]:
    """What the host was doing, as labelled intervals on the trace clock:
    each ``decide()`` split into its stages by the round's own stage timings
    (in the order the scheduler runs them), ``sim`` for the rest of each
    ``bench.round``.  Time outside every segment is the harness's."""
    segs: List[Tuple[float, float, str]] = []
    for k, (s, e) in enumerate(decides):
        edge = s
        for stage in STAGES:
            if k < len(timings):
                nxt = min(e, edge + timings[k].get(stage, 0.0) * 1e9)
                segs.append((edge, nxt, "decide/" + stage[:-2]))
                edge = nxt
        segs.append((edge, e, "decide"))
    for rs, re_ in rounds:
        t = rs
        for s, e, _ in sorted(x for x in segs if rs <= x[0] < re_):
            segs.append((t, s, "sim"))
            t = max(t, e)
        segs.append((t, re_, "sim"))
    return sorted(x for x in segs if x[1] > x[0])


def attribute(idle: Sequence[Interval], segs: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[str, float]]:
    """Each idle gap cut at the host segments' edges: ``(label, ns)`` pieces,
    ``bench`` for idle time outside every segment."""
    out = []
    for gs, ge in idle:
        covered = 0.0
        for s, e, name in segs:
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out.append((name, part))
                covered += part
        if ge - gs - covered > 0:
            out.append(("bench", ge - gs - covered))
    return out


def reduce(pd, timings: Sequence[Dict[str, float]]) -> Dict:
    """Device numbers of one traced window.  ``timings`` are the traced
    rounds' ``decide()`` stage timings, in order, for naming idle gaps."""
    rounds = _host_annotations(pd, "bench.round")
    decides = _host_annotations(pd, "bench.decide")
    if not rounds:
        raise ValueError("the trace holds no bench.round annotation")
    lo, hi = rounds[0][0], rounds[-1][1]
    decides = [d for d in decides if lo <= d[0] < hi]

    busy_per_chip, fused_ns, first_busy = [], 0.0, None
    op_ns: Dict[str, float] = defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = list(_events(plane, "XLA Ops"))
        if not ops:
            continue
        busy = union([(s, e) for _, s, e in ops], lo, hi)
        busy_per_chip.append(sum(e - s for s, e in busy))
        if first_busy is None:
            first_busy = busy
        for name, s, e in ops:
            op_ns[name] += max(0.0, min(e, hi) - max(s, lo))
        fused_ns += sum(
            max(0.0, min(e, hi) - max(s, lo))
            for name, s, e in _events(plane, "XLA Modules")
            if name.startswith(FUSED_MODULE)
        )
    if not busy_per_chip:
        raise ValueError("the trace holds no device operation")
    chips = len(busy_per_chip)
    segs = host_segments(decides, timings, rounds)
    idle = sorted(attribute(gaps(first_busy, lo, hi), segs), key=lambda p: -p[1])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_per_chip) / chips / 1e9,
        "chips": chips,
        "rounds": len(decides),
        "fused_device_s_per_round": fused_ns / chips / max(len(decides), 1) / 1e9,
        "breakdown": {
            "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
            "idle_gaps": [[name, ns / 1e9] for name, ns in idle[:10]],
        },
    }
