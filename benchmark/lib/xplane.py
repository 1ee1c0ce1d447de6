"""Reduction of a jax.profiler trace (.xplane.pb) to device metrics.

What is read, as seen in traces this benchmark took on a TPU v5e (a small
recorded one is in tests/benchmark/data/):

- planes named `/device:TPU:<n>` are chips. Their line `XLA Ops` carries one
  event per executed HLO instruction, named by the instruction's text, with
  start and duration on the device's clock; `Async XLA Ops` carries one event
  per asynchronous pair (copy-start/-done, all-reduce-start/-done) lasting
  from the start to the done. Nothing else on a device plane is used.
- an op's event METADATA carries the compiler's `hlo_category` ("convolution
  fusion", "loop fusion", "all-reduce", ...): that, not the op's name, says
  whether a fusion holds a convolution or a dot. jax.profiler.ProfileData
  shows an event's own stats but not its metadata's, so the file is decoded
  here, from the protobuf wire format of tsl/profiler/protobuf/xplane.proto
  (only the fields named in _decode below).
- the host plane (`/host:CPU`) carries this benchmark's own
  jax.profiler.TraceAnnotation marks, named `bench_mark:<label>:<ns>` with the
  host's perf_counter_ns in the name, so that the two clocks can be laid side
  by side: offset = mark's time on the trace - <ns>. The device's clock runs
  about a millisecond off the host's in these traces; gaps are attributed at
  that resolution.

Busy time is the union of the op intervals on a chip, so nested or
overlapping events are not counted twice. The traced slice runs from the mark
`begin` to the mark `end`; op intervals are cut to it.

Interval arithmetic is kept apart (union / subtract / gaps), on plain
(lo, hi) pairs, so that it is tested on hand-made intervals.
"""
from __future__ import annotations

import re
import struct
import sys

MARK = 'bench_mark:'
OP_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'
DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
COLLECTIVE = re.compile(
    r'^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast)')
# hlo_category values whose time is spent feeding the matrix unit
MXU_CATEGORIES = ('convolution', 'convolution fusion', 'dot', 'dot fusion')


# -- intervals ---------------------------------------------------------------

def union(intervals):
    """Sorted, merged copy of (lo, hi) intervals; empty ones are dropped."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(merged):
    return sum(hi - lo for lo, hi in merged)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a, b):
    """Parts of the merged intervals `a` that no interval of merged `b`
    covers."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of [lo, hi]: what the merged `busy` leaves."""
    return subtract([(lo, hi)], busy)


# -- the file ----------------------------------------------------------------

def _fields(buf, pos, end):
    """(field number, wire type, value) of one protobuf message. A varint's
    value is its integer, a length-delimited field's is its (start, end) in
    `buf`, a fixed64's its 8 bytes."""
    while pos < end:
        key = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, value
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, (pos, pos + size)
            pos += size
        elif wire == 1:
            yield number, wire, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield number, wire, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f'wire type {wire} at byte {pos}')


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode('utf-8', 'replace')


def _signed(value):
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names):
    """(name, value) of one XStat; a ref_value is the name it points to."""
    name = value = None
    for number, wire, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack('<d', v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf, span):
    key = value = None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _decode(path, want_line=lambda plane, line: True):
    """[{'name', 'lines': {line name: [(event name, start_ps, end_ps,
    metadata stats)]}}] of the XSpace at `path`. Events of lines that
    `want_line` refuses are skipped unread."""
    with open(path, 'rb') as f:
        buf = f.read()
    planes = []
    for number, _, span in _fields(buf, 0, len(buf)):
        if number != 1:                                   # XSpace.planes
            continue
        name, lines, event_meta, stat_names = '', [], {}, {}
        for n, _, v in _fields(buf, *span):
            if n == 2:                                    # XPlane.name
                name = _text(buf, v)
            elif n == 3:                                  # XPlane.lines
                lines.append(v)
            elif n == 4:                                  # .event_metadata
                event_meta.update([_map_entry(buf, v)])
            elif n == 5:                                  # .stat_metadata
                key, meta = _map_entry(buf, v)
                for m, _, w in _fields(buf, *meta):
                    if m == 2:                            # XStatMetadata.name
                        stat_names[key] = _text(buf, w)
        metadata = {}

        def describe(meta_id):
            if meta_id not in metadata:
                label, stats = str(meta_id), {}
                for m, _, w in _fields(buf, *event_meta.get(meta_id, (0, 0))):
                    if m == 2:                        # XEventMetadata.name
                        label = _text(buf, w)
                    elif m == 5:                      # XEventMetadata.stats
                        key, value = _stat(buf, w, stat_names)
                        stats[key] = value
                metadata[meta_id] = (label, stats)
            return metadata[meta_id]

        out = {}
        for line in lines:
            line_name, stamp_ns, events = '', 0, []
            for n, _, v in _fields(buf, *line):
                if n == 2:                                # XLine.name
                    line_name = _text(buf, v)
                elif n == 3:                              # XLine.timestamp_ns
                    stamp_ns = _signed(v)
                elif n == 4:                              # XLine.events
                    events.append(v)
            if not want_line(name, line_name):
                continue
            rows = []
            for event in events:
                meta_id = offset = duration = 0
                for n, _, v in _fields(buf, *event):
                    if n == 1:                            # XEvent.metadata_id
                        meta_id = v
                    elif n == 2:                          # XEvent.offset_ps
                        offset = _signed(v)
                    elif n == 3:                          # XEvent.duration_ps
                        duration = _signed(v)
                label, stats = describe(meta_id)
                start = stamp_ns * 1000 + offset
                rows.append((label, start, start + duration, stats))
            out.setdefault(line_name, []).extend(rows)
        planes.append({'name': name, 'lines': out})
    return planes


# -- the reduction -----------------------------------------------------------

def mark_name(label, perf_ns):
    return f'{MARK}{label}:{perf_ns}'


def is_collective(name, category=None):
    """An op is a collective by its hlo_category, or by the opcode that
    follows its result type in the instruction's text."""
    if category is not None and COLLECTIVE.match(category):
        return True
    opcode = re.search(r'\s([a-z][a-z\-]*)\(', name)
    return bool(opcode and COLLECTIVE.match(opcode.group(1)))


def signature(name, category):
    """What an op is without which instance it is: its category (or opcode)
    and its result type without layouts, e.g. 'convolution fusion
    (bf16[128,128,3072], bf16[128,128,3072])'. Instances of one signature
    (one per layer, say) are summed in the breakdown."""
    head = name.split(' = ', 1)[-1]
    opcode = re.search(r'\s([a-z][a-z\-]*)\(', ' ' + head)
    result = head[:opcode.start()] if opcode else head
    result = re.sub(r'\{[^{}]*\}', '', result).strip()
    kind = category or (opcode.group(1) if opcode else 'op')
    return f'{kind} {result}'[:160]


def reduce(path, top=10):
    """The trace at `path` as a dict of plain numbers (seconds):

      slice_s          begin mark to end mark (or first to last device op)
      offset_ns        trace clock minus perf_counter_ns, or None unmarked
      chips            per device plane: busy_s; ops [[signature, s, calls,
                       an instance's name]] by time; categories
                       {hlo_category: s}; collective_s (async pairs from
                       start to done, and synchronous ones);
                       collective_exposed_s (the part with no compute op
                       running); gaps [(lo_ns, hi_ns)], longest first
      busy_s           mean over chips; idle_share = 1 - busy_s / slice_s

    None when the trace holds no device plane (a CPU rehearsal)."""
    planes = _decode(path, lambda plane, line: plane.startswith('/host:')
                     or (DEVICE_PLANE.match(plane)
                         and line in (OP_LINE, ASYNC_LINE)))
    marks = {}
    for plane in planes:
        if plane['name'].startswith('/host:'):
            for events in plane['lines'].values():
                for name, start, _, _ in events:
                    if name.startswith(MARK):
                        label, perf_ns = name[len(MARK):].rsplit(':', 1)
                        marks[label] = (start, int(perf_ns) * 1000)
    devices = sorted((int(DEVICE_PLANE.match(p['name']).group(1)), p)
                     for p in planes if DEVICE_PLANE.match(p['name']))
    devices = [(i, p) for i, p in devices if p['lines'].get(OP_LINE)]
    if not devices:
        return None
    if 'begin' in marks and 'end' in marks:
        lo, hi = marks['begin'][0], marks['end'][0]
    else:
        stamps = [t for _, p in devices for e in p['lines'][OP_LINE]
                  for t in e[1:3]]
        lo, hi = min(stamps), max(stamps)
    offsets = sorted(t - p for t, p in marks.values())
    chips = []
    for index, plane in devices:
        ops, cats, compute, coll = {}, {}, [], []
        for name, a, b, stats in plane['lines'][OP_LINE]:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            category = stats.get('hlo_category')
            entry = ops.setdefault(signature(name, category), [0, 0, name])
            entry[0] += b - a
            entry[1] += 1
            if category is not None:
                cats[category] = cats.get(category, 0) + (b - a)
            (coll if is_collective(name, category) else compute).append(
                (a, b))
        for name, a, b, stats in plane['lines'].get(ASYNC_LINE, ()):
            if is_collective(name, stats.get('hlo_category')):
                coll += clip([(a, b)], lo, hi)
        compute, coll = union(compute), union(coll)
        busy = union(compute + coll)
        idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
        chips.append({
            'plane': plane['name'], 'index': index,
            'busy_s': total(busy) * 1e-12,
            'ops': [[sig, ps * 1e-12, calls, name.split(' = ')[0]]
                    for sig, (ps, calls, name) in
                    sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]],
            'op_count': sum(e[1] for e in ops.values()),
            'categories': {c: ps * 1e-12 for c, ps in
                           sorted(cats.items(), key=lambda kv: -kv[1])},
            'collective_s': total(coll) * 1e-12,
            'collective_exposed_s': total(subtract(coll, compute)) * 1e-12,
            'gaps': [(a / 1000, b / 1000) for a, b in idle],
        })
    slice_s = (hi - lo) * 1e-12
    busy_s = sum(c['busy_s'] for c in chips) / len(chips)
    return {'slice_s': slice_s,
            'offset_ns': offsets[len(offsets) // 2] / 1000 if offsets
            else None,
            'chips': chips, 'busy_s': busy_s,
            'idle_share': 1.0 - busy_s / slice_s if slice_s > 0 else None}


def attribute_gaps(idle, spans, offset_ns, names, top=10):
    """[[name, seconds]]: each idle gap (trace clock, ns) goes to the host
    span that covers its midpoint, trying `names` in order; gaps under no
    such span go to 'no span'. `spans` are (name, start_perf_ns, end_perf_ns)
    on the host's perf_counter clock, moved onto the trace's by
    `offset_ns`."""
    by_name = {n: sorted((a + offset_ns, b + offset_ns)
                         for name, a, b in spans if name == n)
               for n in names}
    out = {}
    for lo, hi in idle:
        mid = (lo + hi) / 2
        owner = 'no span'
        for n in names:
            if any(a <= mid < b for a, b in by_name[n]):
                owner = n
                break
        out[owner] = out.get(owner, 0.0) + (hi - lo) * 1e-9
    return [[n, s] for n, s in
            sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def describe(path, out=sys.stdout, events_per_line=6):
    """Planes, lines, a few events and their metadata stats: what to look at
    by hand before trusting reduce() on a new kind of trace."""
    for plane in _decode(path):
        print(f"PLANE {plane['name']!r}", file=out)
        for line, events in plane['lines'].items():
            print(f'  LINE {line!r} events={len(events)}', file=out)
            for name, a, b, stats in events[:events_per_line]:
                print(f'    {name[:90]!r} start_ps={a} dur_ps={b - a} '
                      f'{ {k: str(v)[:50] for k, v in stats.items()} }',
                      file=out)


if __name__ == '__main__':
    describe(sys.argv[1])
