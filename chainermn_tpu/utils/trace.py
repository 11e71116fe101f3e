"""Device-trace analysis helpers — turn `jax.profiler` captures into
per-op time tables.

**Beyond-reference addition** (the reference had no profiling subsystem —
SURVEY.md §5.1; this is the TPU-side toolbox that replaced nvprof in its
workflow).  The round-2 performance investigation (docs/performance.md)
was driven entirely by these two primitives:

* :func:`device_op_times` — parse a trace directory into summed
  device-side op durations (host dispatch time excluded, which differs
  from wall clock by the share of the step the device sits idle);
* :func:`device_time` — time a callable by device timestamps instead of
  wall clock (profile-capture + parse in one call), immune to the
  async-dispatch illusion.

Usage::

    from chainermn_tpu.utils.trace import device_time, top_ops

    ms = device_time(step, (params, opt_state, batch), steps=10)
    table = top_ops("/tmp/trace_dir", n=20)   # [(name, ms, count), ...]
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

_CATEGORY_RE = re.compile(r"\.\d+$")


def _load_trace(trace_dir: str) -> Tuple[dict, Dict[int, str]]:
    paths = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.trace.json.gz"))
    if not paths:
        raise FileNotFoundError(
            f"no trace under {trace_dir!r} (expected "
            "plugins/profile/*/*.trace.json.gz — was the capture stopped?)")
    data = json.load(gzip.open(paths[-1]))
    pids = {}
    for e in data["traceEvents"]:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"]["name"]
    return data, pids


def _detect_device_track(pids: Dict[int, str]) -> str:
    """Pick the device track from the trace's process names.

    Prefers a TPU track (lowest-numbered), falls back to the first
    ``/device:`` track of any backend — so the same analysis code reads
    CPU-mesh and GPU captures without callers hard-coding
    ``/device:TPU:0`` (which silently sums zero events off-TPU).
    """
    tracks = sorted(v for v in pids.values() if v.startswith("/device:"))
    if not tracks:
        raise ValueError(
            "no /device: track in trace (process names: "
            f"{sorted(set(pids.values()))}) — not a device capture?")
    for t in tracks:
        if t.startswith("/device:TPU:"):
            return t
    return tracks[0]


def device_op_times(trace_dir: str,
                    device: Optional[str] = None) -> Dict[str, Tuple[float, int]]:
    """Sum device-side op durations from a profiler capture.

    Returns ``{op_name: (total_ms, count)}`` for complete events on the
    given device track, excluding the per-program wrapper events
    (``jit_*`` and bare step numbers) so the values are real op time.
    ``device=None`` auto-detects the track (TPU preferred, else the
    first ``/device:`` process in the capture).
    """
    data, pids = _load_trace(trace_dir)
    if device is None:
        device = _detect_device_track(pids)
    acc: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    for e in data["traceEvents"]:
        if (e.get("ph") == "X" and "dur" in e
                and pids.get(e["pid"]) == device):
            name = e["name"]
            if name.startswith("jit_") or re.fullmatch(r"\d+", name):
                continue
            a = acc[name]
            a[0] += e["dur"] / 1e3
            a[1] += 1
    return {k: (v[0], v[1]) for k, v in acc.items()}


def top_ops(trace_dir: str, n: int = 20, by_category: bool = False,
            device: Optional[str] = None) -> List[Tuple[str, float, int]]:
    """Top-``n`` ops (or name-categories, with trailing ``.N`` stripped)
    by total device time: ``[(name, total_ms, count), ...]`` descending."""
    times = device_op_times(trace_dir, device=device)
    if by_category:
        cat: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
        for name, (ms, c) in times.items():
            a = cat[_CATEGORY_RE.sub("", name)]
            a[0] += ms
            a[1] += c
        times = {k: (v[0], v[1]) for k, v in cat.items()}
    rows = [(k, ms, c) for k, (ms, c) in times.items()]
    rows.sort(key=lambda r: -r[1])
    return rows[:n]


def device_time(fn: Callable, args: tuple, steps: int = 5, warmup: int = 2,
                trace_dir: Optional[str] = None,
                device: Optional[str] = None) -> float:
    """Per-call device-side milliseconds of ``fn(*args)``.

    Captures a profiler trace around ``steps`` calls and sums the device
    track — the number a wall clock cannot give: dispatch is
    asynchronous, and a wall time also counts every gap in which the
    device waits for the host (see docs/performance.md).

    The final output is fenced with a device→host VALUE read, so every
    timed call has actually executed.  ``trace_dir=None`` uses (and
    removes) a temporary directory; pass a path to keep the capture.
    """
    import jax

    def fence(out):
        leaf = jax.tree.leaves(out)[0]
        jax.block_until_ready(leaf)
        np.asarray(jax.device_get(leaf)).ravel()[:1]

    for _ in range(warmup):
        out = fn(*args)
    fence(out)
    tmp = trace_dir or tempfile.mkdtemp(prefix="chainermn_tpu_trace_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(steps):
            out = fn(*args)
        fence(out)
        jax.profiler.stop_trace()
        total = sum(ms for ms, _ in device_op_times(tmp, device=device).values())
    finally:
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    return total / steps


__all__ = ["device_op_times", "device_time", "top_ops"]
