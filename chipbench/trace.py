"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX
(``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand, PR 24): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per launch of an executable, named ``jit_<fn>(<hash>)``) and ``XLA
Ops`` (one event per HLO op, named by its HLO text, serial on the one
TensorCore); one plane ``/host:CPU`` whose ``python`` lines hold the
``TraceAnnotation`` spans.  All on one clock, in nanoseconds."""

import collections
import re

WINDOW_SPAN = 'chipbench:traced_window'
SPAN_PREFIX = 'chipbench:'
COLLECTIVES = ('all-reduce', 'reduce-scatter', 'all-gather',
               'all-to-all', 'collective-permute')


def load(path):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def op_label(hlo):
    """``'%fusion.3 = bf16[8,4]{1,0} fusion(...), kind=...'`` ->
    ``('fusion', 'fusion bf16[8,4]')``: the op's kind and a stable
    label (kind, the fusion's own name without its number, result
    type without layouts).  A Pallas kernel is a ``custom-call`` to
    ``tpu_custom_call`` and is labelled ``pallas``."""
    name, _, rest = hlo.partition(' = ')
    if not rest:
        return hlo, hlo
    if rest.startswith('('):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == '(') - (ch == ')')
            if depth == 0:
                break
        rtype, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        rtype, _, tail = rest.partition(' ')
    kind = tail.split('(', 1)[0].strip()
    rtype = re.sub(r'\{[^}]*\}', '', rtype)
    if len(rtype) > 96:
        rtype = rtype[:96] + '...'
    stem = re.sub(r'[.\d]+$', '', name.lstrip('%'))
    parts = [kind]
    if kind == 'custom-call' and 'tpu_custom_call' in hlo:
        parts = ['pallas', kind]
    elif stem and stem != kind:
        parts.append(stem)
    return kind, ' '.join(parts + [rtype])


def _union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


class Summary:
    """A reduced trace.  Seconds are per chip (averaged over the device
    planes) unless a name says otherwise."""

    def __init__(self):
        self.n_devices = 0
        self.window_s = 0.0
        self.busy_s = 0.0
        self.op_seconds = collections.Counter()      # label -> s
        self.pallas_s = 0.0
        self.collective_s = 0.0   # serial on the core, so all exposed
        self.modules = {}         # name -> [launches, seconds]
        self.idle_gaps = collections.Counter()       # host span -> s

    @property
    def idle_share(self):
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def module(self, pattern):
        """``(launches, seconds)`` summed over the executables whose
        name contains ``pattern``, over all chips."""
        hits = [v for k, v in self.modules.items() if pattern in k]
        return sum(v[0] for v in hits), sum(v[1] for v in hits)

    def breakdown(self):
        return {'device_ops': [[k, v] for k, v in
                               self.op_seconds.most_common(10)],
                'idle_gaps': [[k, v] for k, v in
                              self.idle_gaps.most_common(10)]}


def _host_spans(profile, window_span):
    spans, window = [], None
    for plane in profile.planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == window_span:
                    window = (a, b)
                else:
                    spans.append((a, b, ev.name))
    return spans, window


def _attribute(gap, spans):
    """The host span that covers most of an idle gap."""
    best, cover = '_no_span_', 0.0
    for a, b, name in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > cover:
            best, cover = name, c
    return best


def reduce(profile, window_span=WINDOW_SPAN):
    """``Summary`` of a trace, or ``None`` of one in which no
    operation ran on a device.  The window is the ``window_span``
    annotation where the trace has one, else the extent of the device
    events."""
    out = Summary()
    spans, window = _host_spans(profile, window_span)
    devices = []
    for plane in profile.planes:
        if not plane.name.startswith('/device:TPU:'):
            continue
        lines = {line.name: line for line in plane.lines}
        if 'XLA Ops' not in lines:
            continue
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in lines['XLA Ops'].events]
        mods = ([(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                 for ev in lines['XLA Modules'].events]
                if 'XLA Modules' in lines else [])
        if ops:
            devices.append((ops, mods))
    if not devices:
        return None   # no chip in the trace (a CPU rehearsal)
    if window is None:
        window = (min(o[0] for ops, _ in devices for o in ops),
                  max(o[1] for ops, _ in devices for o in ops))
    lo, hi = window
    out.n_devices = n = len(devices)
    out.window_s = (hi - lo) * 1e-9
    labels = {}
    for index, (ops, mods) in enumerate(devices):
        busy = _union(_clip([(a, b) for a, b, _ in ops], lo, hi))
        out.busy_s += sum(b - a for a, b in busy) * 1e-9 / n
        for a, b, hlo in ops:
            seconds = (min(b, hi) - max(a, lo)) * 1e-9
            if seconds <= 0:
                continue
            if hlo not in labels:
                labels[hlo] = op_label(hlo)
            kind, label = labels[hlo]
            out.op_seconds[label] += seconds / n
            if label.startswith('pallas '):
                out.pallas_s += seconds / n
            if kind.startswith(COLLECTIVES):
                out.collective_s += seconds / n
        for a, b, name in mods:
            if b <= lo or a >= hi:
                continue
            entry = out.modules.setdefault(name.split('(')[0], [0, 0.0])
            entry[0] += 1
            entry[1] += (b - a) * 1e-9
        if index == 0:
            at = lo
            for a, b in busy + [[hi, hi]]:
                if a > at:
                    out.idle_gaps[_attribute((at, a), spans)] += \
                        (a - at) * 1e-9
                at = max(at, b)
    if out.busy_s > out.window_s * (1 + 1e-9):
        raise AssertionError('device busy %.6f s exceeds the traced '
                             'window %.6f s' % (out.busy_s, out.window_s))
    return out
