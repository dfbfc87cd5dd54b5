"""From a profiler trace (``*.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` gives planes, their lines, and events with a
start and a duration in nanoseconds. A TPU chip is a plane named
``/device:TPU:<n>``; on it the line ``XLA Ops`` holds every operation that
ran on the core and ``XLA Modules`` every whole program. Host threads are
lines of the plane ``/host:CPU``, and the benchmark's own
``TraceAnnotation`` spans are events there, on the same clock.

Nothing here names a model, a module or a metric: readers pass the module
names they look for.
"""
import bisect
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE, MODULES_LINE = 'XLA Ops', 'XLA Modules'
HOST_PLANE = '/host:CPU'
COLLECTIVE = re.compile(r'all-reduce|all-gather|reduce-scatter|all-to-all|'
                        r'collective-permute', re.I)


def union(intervals):
    """Merged, sorted intervals of a list of (start, end)."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover):
    """The part of merged ``intervals`` that merged ``cover`` leaves bare."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, at = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < b:
            out.append([at, b])
    return out


def gaps(busy, lo, hi):
    """Idle stretches of [lo, hi] that the merged ``busy`` leaves."""
    return subtract([[lo, hi]], busy)


SHORT_GAP_NS = 5000      # under this a gap is the device's own, between ops


def attribute(gap_list, spans):
    """Idle nanoseconds by the host span open in them: a gap goes to the
    innermost (latest-started) span that covers its middle, else to
    'no span'; gaps too short for the host to be their cause are lumped
    as 'between device ops'."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    by = {}
    for a, b in gap_list:
        name = 'between device ops'
        if b - a >= SHORT_GAP_NS:
            mid, name = (a + b) / 2, 'no span'
            i = bisect.bisect_right(starts, mid)
            for s, e, n in reversed(spans[max(0, i - 64):i]):
                if e >= mid:
                    name = n
                    break
        by[name] = by.get(name, 0.0) + (b - a)
    return sorted(by.items(), key=lambda kv: -kv[1])


def short_op(text):
    """``%fusion.409 = (bf16[96,128,3072]{...}, ...) fusion(...), kind=kLoop``
    -> ``%fusion.409 bf16[96,128,3072] fusion``: the HLO op's name, its
    (first) result shape and its opcode."""
    m = re.match(r'(%?[\w.\-]+) = \(?(\w+\[[\d,]*\])?[^ ]* ?.*? ([\w\-]+)\(',
                 text)
    if not m:
        return text[:96]
    return ' '.join(p for p in m.groups() if p)


HOST_INTERNAL = re.compile(r'::|=>')      # the runtime's own C++ spans


def read_planes(path):
    """{plane name: {line name: [(start_ns, end_ns, event name)]}}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return planes


def reduce_planes(planes, n_devices, span_names=None):
    """The reduction proper, on :func:`read_planes`' structure.

    Returns ``busy_s`` and ``window_s`` (averaged over the devices used),
    ``idle_share``, ``modules`` {name with its fingerprint: [seconds, calls]}
    averaged over devices, ``collective_s`` and ``collective_exposed_s``, ``device_ops``
    [[name, seconds]] and ``idle_gaps`` [[host span, seconds]], longest
    first. The window is from the first to the last device operation over
    all devices: the stretch in which the trace shows the device at work.
    """
    devs = sorted((int(DEVICE_PLANE.match(n).group(1)), n)
                  for n in planes if DEVICE_PLANE.match(n))[:n_devices]
    if not devs:
        raise RuntimeError('no device plane in the trace: %s'
                           % sorted(planes))
    ops = {n: planes[n].get(OPS_LINE, []) for _, n in devs}
    if not any(ops.values()):
        raise RuntimeError('no operation ran on the device in the trace')
    lo = min(e[0] for evs in ops.values() for e in evs)
    hi = max(e[1] for evs in ops.values() for e in evs)
    spans = []
    for line in planes.get(HOST_PLANE, {}).values():
        spans += [e for e in line if not HOST_INTERNAL.search(e[2])
                  and (span_names is None or e[2] in span_names)]
    busy_s, coll_s, exposed_s = [], [], []
    op_time, module_time, idle = {}, {}, []
    for _, name in devs:
        evs = ops[name]
        busy = union((a, b) for a, b, _ in evs)
        busy_s.append(total(busy))
        coll = union((a, b) for a, b, n in evs if COLLECTIVE.search(n))
        compute = union((a, b) for a, b, n in evs
                        if not COLLECTIVE.search(n))
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, compute)))
        for a, b, n in evs:
            n = short_op(n)
            op_time[n] = op_time.get(n, 0.0) + (b - a)
        mods = sorted(planes[name].get(MODULES_LINE, []))
        if len(mods) >= 4:
            mods = mods[1:-1]       # the trace's edges may cut these two
        for a, b, n in mods:
            m = module_time.setdefault(n, [0.0, 0])
            m[0] += b - a
            m[1] += 1
        idle += gaps(busy, lo, hi)
    k, ns = len(devs), 1e-9
    window = (hi - lo) * ns
    busy = sum(busy_s) / k * ns
    return {
        'busy_s': busy, 'window_s': window,
        'idle_share': 1.0 - busy / window,
        'modules': {n: [t / k * ns, c / k]
                    for n, (t, c) in module_time.items()},
        'collective_s': sum(coll_s) / k * ns,
        'collective_exposed_s': sum(exposed_s) / k * ns,
        'device_ops': [[n, t / k * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        'idle_gaps': [[n, t / k * ns]
                      for n, t in attribute(idle, spans)[:10]],
    }


def reduce(path, n_devices, span_names=None):
    return reduce_planes(read_planes(path), n_devices, span_names)
