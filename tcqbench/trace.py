"""Reduction of a JAX profiler trace to device busy time, idle gaps and step
times, on the trace's own clock.

:func:`flatten` turns an ``.xplane.pb`` into plain event records; everything
after that works on those records, so the reduction is checked on small
recorded or hand-made fixtures.  A record is
``{"kind", "plane", "name", "start_ns", "end_ns"}`` with ``kind``:

* ``op``: an operation on a device (a TPU plane's ``XLA Ops`` line);
* ``module``: one execution of a compiled program on a device (the
  ``XLA Modules`` line), named after the jitted function;
* ``host``: a span the benchmark opened with ``TraceAnnotation``; its name
  starts with ``SPAN_PREFIX``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "tcqbench."
#: the programs that run one peel step: the fused kernel's jitted ``_step``
#: and the XLA composite ``_wave_step_impl``
STEP_PROGRAMS = r"jit__step\b|jit__wave_step_impl"
WINDOW_SPAN = SPAN_PREFIX + "window"
_LINE_KIND = {"XLA Ops": "op", "XLA Modules": "module"}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def flatten(path: str) -> List[dict]:
    """Device ops and programs from every TPU plane, and the benchmark's
    own host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            kind = _LINE_KIND.get(line.name) if device else None
            for ev in line.events:
                if kind is None and not (not device
                                         and ev.name.startswith(SPAN_PREFIX)):
                    continue
                out.append({"kind": kind or "host", "plane": plane.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "end_ns": float(ev.start_ns + ev.duration_ns)})
    return out


def _merge(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def window(events: Sequence[dict]) -> Optional[Tuple[float, float]]:
    """The traced window: the benchmark's ``window`` span."""
    spans = [e for e in events if e["kind"] == "host"
             and e["name"] == WINDOW_SPAN]
    if not spans:
        return None
    return spans[0]["start_ns"], spans[0]["end_ns"]


def busy(events: Sequence[dict], win: Tuple[float, float]
         ) -> Dict[str, List[Tuple[float, float]]]:
    """Per device plane, the merged intervals in which an op ran."""
    per: Dict[str, list] = defaultdict(list)
    for e in events:
        if e["kind"] == "op":
            per[e["plane"]].append((e["start_ns"], e["end_ns"]))
    return {p: _merge(_clip(iv, *win)) for p, iv in per.items()}


def busy_seconds(events: Sequence[dict], win) -> Optional[float]:
    """Seconds in which an op ran, averaged over the devices traced."""
    per = busy(events, win)
    if not per:
        return None
    return sum(sum(b - a for a, b in iv) for iv in per.values()) \
        / len(per) / 1e9


def idle_gaps(events: Sequence[dict], win) -> List[Tuple[str, float]]:
    """Every gap between device ops inside the window on the first device,
    named by the innermost benchmark span that holds its midpoint, longest
    first."""
    per = busy(events, win)
    if not per:
        return []
    iv = per[sorted(per)[0]]
    edges = [win[0]] + [x for ab in iv for x in ab] + [win[1]]
    spans = sorted((e["end_ns"] - e["start_ns"], e["start_ns"], e["end_ns"],
                    e["name"][len(SPAN_PREFIX):])
                   for e in events if e["kind"] == "host"
                   and e["name"] != WINDOW_SPAN)
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        name = next((n for _, s, t, n in spans if s <= mid <= t), "other")
        gaps.append((name, (b - a) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])


def op_family(name: str) -> str:
    """An op's instruction name without the ``%`` and the numeric suffix
    XLA appends (a TPU trace names an op by its whole HLO line)."""
    return re.sub(r"[.:]\d+$", "", name.split(" = ")[0].strip().lstrip("%"))


def top_ops(events: Sequence[dict], win, n: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds per op family inside the window, largest first."""
    tot: Dict[str, float] = defaultdict(float)
    for e in events:
        if e["kind"] == "op":
            for a, b in _clip([(e["start_ns"], e["end_ns"])], *win):
                tot[op_family(e["name"])] += (b - a) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def module_seconds(events: Sequence[dict], win, pattern: str
                   ) -> List[float]:
    """Device seconds of each execution of the programs whose name matches
    ``pattern``, inside the window."""
    rx = re.compile(pattern)
    return [(e["end_ns"] - e["start_ns"]) / 1e9 for e in events
            if e["kind"] == "module" and rx.search(e["name"])
            and win[0] <= e["start_ns"] <= win[1]]
