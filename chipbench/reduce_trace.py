"""From a profiler trace to the numbers the per-layer metrics read.  The
benchmark keeps its own reduction (the program's ``utils/trace.py`` sums
durations, so overlapping operations count twice, and cannot give an idle or
an exposed-collective share).

``read_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` and nothing else.  What it keeps is an EVENTS
document, which is also what the fixtures under ``fixtures/`` hold:

    {"devices": {"<plane name>": [[name, start_ns, duration_ns], ...]},
     "host_spans": [[name, start_ns, duration_ns], ...]}

(a device event's name shortened by ``short_name``)

``devices`` holds the operation line of every device plane (on a TPU the
line "XLA Ops" of the planes "/device:TPU:<n>"); ``host_spans`` holds the
host's annotated phases (``jax.profiler.TraceAnnotation``), on the same
clock.  Every function below works on such a document, in nanoseconds.

Operations nest (a loop or a call contains its body), so time by name is
SELF time: an event's duration less that of the events it contains.  Busy
time is the union of the intervals of the LEAF events (those that contain
no other).  What an exchange between chips costs is read by scope
(``scopes.spans_where`` / ``exposed``: an asynchronous pair spans from its
start's beginning to its done's end, and its EXPOSED part is what no
operation outside the scope covers) and, by instruction name, as the time
the device only waits (``layer_metrics/collective_wait_ms.py``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os

HOST_SPANS = ("dispatch", "read_loss")
OP_LINE = "XLA Ops"


# ---- reading -------------------------------------------------------------

def short_name(name):
    """A device event is named by its whole HLO instruction, some hundreds
    of characters (``%fusion.9 = f32[1,8191,49152]{2,1,0:T(8,128)}
    fusion(...), kind=...``).  Keep the instruction's own name, the shape it
    produces and whether it is a Pallas kernel: ``fusion.9 f32[1,8191,49152]``
    or ``block_0.3 (bf16[16,8192,128] tpu_custom_call``."""
    head, found, rest = name.partition(" = ")
    if not found:
        return name[:120]
    shape = rest.split("{", 1)[0].strip()[:40]
    kernel = (" tpu_custom_call"
              if 'custom_call_target="tpu_custom_call"' in rest else "")
    return f"{head.lstrip('%')} {shape}{kernel}"


def read_xplane(path, host_spans=HOST_SPANS):
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    devices, spans = {}, []
    planes = list(profile.planes)
    on_device = [p for p in planes if p.name.startswith("/device:")
                 and any(line.name == OP_LINE for line in p.lines)]
    for plane in on_device:
        ops = []
        for line in plane.lines:
            if line.name == OP_LINE:
                ops.extend([short_name(e.name), float(e.start_ns),
                            float(e.duration_ns)] for e in line.events)
        devices[plane.name] = ops
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name in host_spans:
                    spans.append([event.name, float(event.start_ns),
                                  float(event.duration_ns)])
                elif not on_device and event.duration_ns > 0 and any(
                        key == "hlo_op" for key, _ in event.stats):
                    # a CPU rehearsal: XLA's CPU client runs the operations
                    # on host threads and tags them with their HLO name
                    devices.setdefault("/host:CPU", []).append(
                        [short_name(event.name), float(event.start_ns),
                         float(event.duration_ns)])
    return {"devices": devices, "host_spans": spans}


def reduce_directory(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_xplane(paths[-1])


def dump_events(events, path):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as handle:
        json.dump(events, handle)


def load_events(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as handle:
        return json.load(handle)


# ---- intervals -------------------------------------------------------------

def union(intervals):
    """Merged, sorted, non-overlapping ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, cover):
    """The parts of merged ``intervals`` that merged ``cover`` leaves open."""
    out, j = [], 0
    for start, end in intervals:
        at = start
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def _spans(ops):
    return [[start, start + duration] for _, start, duration in ops]


def device_names(events):
    return sorted(events["devices"])


def first_device(events):
    return events["devices"][device_names(events)[0]]


def window(ops):
    """From the first operation's start to the last one's end."""
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def leaf_ops(ops):
    """The events that contain no other event (a loop or a call spans its
    body, gaps included, and would hide them)."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    leaves = []
    for index, (name, start, duration) in enumerate(ordered):
        end, contains = start + duration, False
        for other in ordered[index + 1:]:
            if other[1] >= end:
                break
            if other[1] + other[2] <= end:
                contains = True
                break
        if not contains:
            leaves.append([name, start, duration])
    return leaves


def busy_ns(ops):
    return length(union(_spans(leaf_ops(ops))))


def window_s(events):
    start, end = window(first_device(events))
    return (end - start) / 1e9


def mean_busy_s(events):
    names = device_names(events)
    return sum(busy_ns(events["devices"][n]) for n in names) / len(names) / 1e9


def idle_share(ops):
    start, end = window(ops)
    return 1.0 - busy_ns(ops) / (end - start)


def self_times(ops):
    """``{name: self nanoseconds}``: each event's duration less the events
    nested in it (an event that only overlaps another is not nested)."""
    totals, open_events = {}, []      # open: [end, name, self], by start

    def close(until):
        for event in [e for e in open_events if e[0] <= until]:
            open_events.remove(event)
            totals[event[1]] = totals.get(event[1], 0.0) + max(event[2], 0.0)

    for name, start, duration in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        parents = [e for e in open_events if e[0] >= start + duration]
        if parents:
            parents[-1][2] -= duration
        open_events.append([start + duration, name, duration])
    close(float("inf"))
    return totals


def time_of(ops, matches):
    """Summed self time of the operations whose name ``matches``."""
    return sum(ns for name, ns in self_times(ops).items() if matches(name))


# ---- gaps ------------------------------------------------------------------

def idle_gaps(events, count=5):
    """The longest idle gaps of the first device, each named by the host
    span that covers most of it (or ``no_host_span``)."""
    ops = first_device(events)
    start, end = window(ops)
    gaps = subtract([[start, end]], union(_spans(leaf_ops(ops))))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for gap_start, gap_end in gaps[:count]:
        best, best_cover = "no_host_span", 0.0
        for name, span_start, duration in events["host_spans"]:
            cover = (min(gap_end, span_start + duration)
                     - max(gap_start, span_start))
            if cover > best_cover:
                best, best_cover = name, cover
        named.append([best, (gap_end - gap_start) / 1e9])
    return named


def breakdown(events, count=10):
    totals = self_times(first_device(events))
    top = sorted(totals.items(), key=lambda item: -item[1])[:count]
    return {"device_ops": [[name, ns / 1e9] for name, ns in top],
            "idle_gaps": idle_gaps(events, 5)}


if __name__ == "__main__":
    import sys

    document = read_xplane(sys.argv[1])
    print(json.dumps({name: len(ops)
                      for name, ops in document["devices"].items()}))
    print(json.dumps(breakdown(document), indent=1))
