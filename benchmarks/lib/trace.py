"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

Two steps, so that the second can be tested without a chip:

* :func:`load` turns an ``.xplane.pb`` file into a plain dict,
  ``{"devices": {plane: {"ops": [[text, start_ns, dur_ns], ...],
  "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``.
  Device planes are those named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per executed HLO instruction (its text is the
  instruction's HLO) and ``XLA Modules`` one per executed program
  (``jit_<name>(<fingerprint>)``).  Host events are the benchmark's own
  ``TraceAnnotation`` spans, whose names start with ``bench.``.
* everything else is arithmetic on that dict.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
HOST_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_NUMERIC_SUFFIX = re.compile(r"(\.\d+)+$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


def load(xplane_path: str) -> dict:
    """Read an xplane file into the plain dict described above."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": [], "lines_seen": {}}
    for plane in data.planes:
        is_device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            out["lines_seen"].setdefault(plane.name, []).append(line.name)
            if is_device and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                dev = out["devices"].setdefault(
                    plane.name, {"ops": [], "modules": []})
                dev[key].extend([e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events)
            elif plane.name.startswith("/host:"):
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_PREFIX))
    for dev in out["devices"].values():
        dev["ops"].sort(key=lambda e: e[1])
        dev["modules"].sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


# -- arithmetic on the reduced trace -------------------------------------------

def union_seconds(events: Iterable[list]) -> float:
    """Length of the union of the events' intervals, in seconds."""
    total, cur_start, cur_end = 0, None, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, start + dur
        else:
            cur_end = max(cur_end, start + dur)
    if cur_end is not None:
        total += cur_end - cur_start
    return total * 1e-9


def window_of(trace: dict) -> Tuple[int, int]:
    """First start and last end over every device event, in ns: the traced
    window as the devices saw it."""
    starts, ends = [], []
    for dev in trace["devices"].values():
        for _, start, dur in dev["ops"]:
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_and_window(trace: dict) -> Tuple[float, float]:
    """(busy seconds averaged over the devices, window seconds).  Busy is
    the union of the intervals in which an operation ran on a device."""
    t0, t1 = window_of(trace)
    busy = [union_seconds(dev["ops"]) for dev in trace["devices"].values()
            if dev["ops"]]
    return sum(busy) / len(busy), (t1 - t0) * 1e-9


def idle_share_pct(trace: dict) -> float:
    busy, window = busy_and_window(trace)
    return 100.0 * (1.0 - busy / window)


def _runs_of(dev: dict, name: str) -> List[list]:
    """Executions of the program ``jit_<name>`` on one device.  The
    profiler names a module ``jit_<name>(<fingerprint>)``."""
    want = "jit_" + name
    return [e for e in dev["modules"]
            if e[0] == want or e[0].startswith(want + "(")]


def module_events(trace: dict, name: str) -> List[list]:
    """Executions of ``jit_<name>`` on the first device that ran it."""
    for dev in trace["devices"].values():
        hits = _runs_of(dev, name)
        if hits:
            return hits
    return []


def module_median_ms(trace: dict, name: str) -> Optional[float]:
    hits = module_events(trace, name)
    if not hits:
        return None
    return statistics.median(e[2] for e in hits) * 1e-6


def mosaic_ms_per_module(trace: dict, name: str) -> Optional[float]:
    """Summed duration of the Mosaic (Pallas) calls inside the executions of
    program ``name``, per execution, in ms.  A Mosaic call is an event whose
    own HLO text carries ``custom_call_target="tpu_custom_call"``; other
    custom calls (``ConcatBitcast``) are not kernels and are left out."""
    for dev in trace["devices"].values():
        mods = _runs_of(dev, name)
        if not mods:
            continue
        spans = [(s, s + d) for _, s, d in mods]
        total = sum(dur for text, start, dur in dev["ops"]
                    if MOSAIC_MARK in text
                    and any(a <= start < b for a, b in spans))
        return total * 1e-6 / len(mods)
    return None


def module_summary(trace: dict) -> Dict[str, list]:
    """{program name: [executions, median ms]} on the first busy device,
    fingerprints stripped: how a program's name is found by hand."""
    for dev in trace["devices"].values():
        if not dev["modules"]:
            continue
        by_name: Dict[str, List[int]] = {}
        for name, _, dur in dev["modules"]:
            by_name.setdefault(name.split("(", 1)[0], []).append(dur)
        return {k: [len(v), statistics.median(v) * 1e-6]
                for k, v in sorted(by_name.items())}
    return {}


def op_group(text: str) -> str:
    """A readable group for one HLO instruction: the name before `` = ``
    with its numeric suffix stripped (``%fusion.263 = ...`` -> ``fusion``)."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _NUMERIC_SUFFIX.sub("", head) or head


def top_device_ops(trace: dict, n: int = 10) -> List[list]:
    """The ``n`` groups of device operations with most total time, seconds
    summed over the first device that ran anything."""
    for dev in trace["devices"].values():
        if not dev["ops"]:
            continue
        totals: Dict[str, int] = {}
        for text, _, dur in dev["ops"]:
            g = op_group(text)
            totals[g] = totals.get(g, 0) + dur
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[g, ns * 1e-9] for g, ns in ranked]
    return []


def idle_gaps(trace: dict, n: int = 10) -> List[list]:
    """The longest idle gaps of the first busy device, each labelled with
    the benchmark's own host span that covers most of it (``unattributed``
    where none overlaps), summed per label, longest first."""
    for dev in trace["devices"].values():
        if not dev["ops"]:
            continue
        gaps, end = [], None
        for _, start, dur in dev["ops"]:
            if end is not None and start > end:
                gaps.append((end, start))
            end = start + dur if end is None else max(end, start + dur)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:200]
        totals: Dict[str, int] = {}
        for a, b in gaps:
            best, best_cover = "unattributed", 0
            for name, start, dur in trace["host"]:
                cover = min(b, start + dur) - max(a, start)
                if cover > best_cover:
                    best, best_cover = name, cover
            totals[best] = totals.get(best, 0) + (b - a)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in ranked]
    return []
