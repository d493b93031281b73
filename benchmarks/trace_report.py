#!/usr/bin/env python
"""Turn a banked ``jax.profiler`` trace into a step-time breakdown.

VERDICT r4 next #2 asks for "a trace-backed analysis of the specific
bottleneck" behind the ResNet-50 step time.  ``strategy_trace.py``
captures the traces; this tool converts them into evidence a reader
can act on without TensorBoard: per-category self-time totals (convs
vs elementwise/BN vs copies/transposes vs collectives), the top ops
by self time with their achieved GFLOP/s and memory bandwidth, and
DMA-stall percentages -- i.e. *where the 12.4 ms goes*.

The reference has no profiling subsystem at all (SURVEY §5); this is
parity-plus tooling on the TPU side of the ledger.

Implementation: the trace dirs hold ``*.xplane.pb`` XSpace protos;
``xprof.convert.raw_to_tool_data`` (the TensorBoard profile plugin's
own converter, available in this image) renders the ``hlo_stats``
DataTable, which this script aggregates.  Degrades gracefully when a
trace has no device plane (e.g. a CPU capture): the report then says
so instead of fabricating zeros.

Usage::

    python benchmarks/trace_report.py DIR [DIR...]   # explicit dirs
    python benchmarks/trace_report.py --latest       # newest trace per
                                                     # strategy under
                                                     # results/traces/

Writes ``benchmarks/results/trace_report.json`` (one object per trace
dir) and prints a readable summary; exits 0 with a "no traces" note
when nothing is found (so CI wiring is safe before the first trace
lands).
"""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
RES = os.path.join(HERE, 'results')
TOP_N = 12

# hlo_stats "HLO op category" -> coarse bucket.  Anything unmatched
# falls into 'other' and is reported verbatim in top_ops, so a novel
# category is visible rather than silently mis-bucketed.
BUCKETS = (
    ('convolution', 'conv/matmul'),
    ('dot', 'conv/matmul'),
    ('all-reduce', 'collective'),
    ('all-gather', 'collective'),
    ('reduce-scatter', 'collective'),
    ('collective', 'collective'),
    ('copy', 'copy/transpose'),
    ('transpose', 'copy/transpose'),
    ('reshape', 'copy/transpose'),
    ('fusion', 'fusion/elementwise'),
    ('loop', 'fusion/elementwise'),
    ('elementwise', 'fusion/elementwise'),
    ('reduce', 'reduction'),
    ('rng', 'rng'),
    ('infeed', 'host-io'),
    ('outfeed', 'host-io'),
)


def bucket_of(category):
    cat = (category or '').lower()
    for needle, bucket in BUCKETS:
        if needle in cat:
            return bucket
    return 'other'


def cell_float(v):
    """Tolerant float from an xprof DataTable cell (ADVICE r5 #3).
    DataTables emit plain numbers but ALSO formatted strings --
    thousands separators ('1,234'), percent suffixes ('56.2%') --
    depending on converter version; a strict float() crashed the
    standalone CLI after analysis already succeeded.  Returns None
    for anything unparseable (callers fall back to the raw value)."""
    if v is None:
        return None
    if isinstance(v, (int, float)):
        return float(v)
    try:
        return float(str(v).replace(',', '').replace('%', '').strip())
    except ValueError:
        return None


def datatable_rows(table):
    """Yield dicts from a Google-DataTable-shaped ``hlo_stats`` JSON."""
    cols = [c.get('id') for c in table.get('cols', [])]
    for row in table.get('rows', []):
        cells = row.get('c', [])
        yield {cols[i]: (cells[i] or {}).get('v')
               for i in range(min(len(cols), len(cells)))}


def _tool_json(paths, tool):
    """One xprof tool's output for a list of xplane paths, via
    whichever converter generation this image ships:

    - ``xprof.convert.raw_to_tool_data`` (standalone xprof package);
    - else the TF pybind entry point directly.  tensorboard-plugin-
      profile 2.17's python wrapper binds
      ``_pywrap_profiler.xspace_to_tools_data``, which TF >= 2.18
      moved to ``_pywrap_profiler_plugin`` -- the wrapper import dies
      with AttributeError and its tool table predates ``hlo_stats``
      anyway, which is why this script "never produced a real
      breakdown" (VERDICT r5) on those images.  The pybind call
      itself works and serves hlo_stats/framework_op_stats DataTable
      JSON; overview_page comes back as a proto and goes through the
      plugin's own gviz converter.
    """
    try:
        from xprof.convert import raw_to_tool_data as r
        data, _ = r.xspace_to_tool_data(paths, tool, {})
        return data
    except ImportError:
        pass
    from tensorflow.python.profiler.internal import (  # noqa: E501  pylint: disable=g-direct-tensorflow-import
        _pywrap_profiler_plugin as plugin)
    raw, ok = plugin.xspace_to_tools_data(list(paths), tool)
    if not ok:
        raise RuntimeError('converter rejected tool %r: %r'
                           % (tool, raw[:200]))
    if tool == 'overview_page':
        from tensorboard_plugin_profile.convert import (
            overview_page_proto_to_gviz)
        return overview_page_proto_to_gviz.to_json(raw)
    return raw


def _tool_tables(paths, tool):
    """hlo_stats returns one DataTable; framework_op_stats returns a
    list of them (device table, host table).  Normalize to a list."""
    data = _tool_json(paths, tool)
    obj = json.loads(data) if isinstance(data, (str, bytes)) else data
    return obj if isinstance(obj, list) else [obj]


def _collect_ops(paths, tool):
    """(buckets, ops) aggregated from one xprof tool's tables."""
    buckets, ops = {}, []
    for table in _tool_tables(paths, tool):
        for row in datatable_rows(table):
            self_us = cell_float(row.get('total_self_time')) or 0.0
            if self_us <= 0:
                continue
            cat = row.get('category') or row.get('type') or '?'
            b = buckets.setdefault(bucket_of(cat),
                                   {'self_time_us': 0.0, 'ops': 0})
            b['self_time_us'] += self_us
            b['ops'] += 1
            ops.append({
                'op': (row.get('hlo_op_name')
                       or row.get('operation') or '?'),
                'category': cat,
                'occurrences': row.get('occurrences'),
                'self_time_us': round(self_us, 1),
                'gflops_per_sec': row.get('model_flop_rate'),
                'memory_bw_gibs': row.get('measured_memory_bw'),
                'dma_stall_pct': row.get('dma_stall_percent'),
            })
    return buckets, ops


def _xplane_pb2():
    """The XSpace proto module, wherever this image ships it."""
    try:
        from xprof.protobuf import xplane_pb2
        return xplane_pb2
    except ImportError:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
        return xplane_pb2


def _collect_intervals(paths):
    """``{plane_name: [(start_us, end_us, bucket), ...]}`` from the
    raw XSpace protos -- the timestamped view the overlap computation
    needs (op-stats tables carry self-times only, no concurrency
    information).  Spans from every line of a plane are pooled: a
    collective on one line overlaps compute on another line of the
    same plane (async collective streams / other cores)."""
    pb = _xplane_pb2()
    out = {}
    for path in paths:
        space = pb.XSpace()
        with open(path, 'rb') as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            meta = plane.event_metadata
            ivs = out.setdefault(plane.name, [])
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith('$'):
                        continue  # python tracing scaffolding
                    start = ev.offset_ps / 1e6
                    ivs.append((start, start + ev.duration_ps / 1e6,
                                bucket_of(name)))
    return out


#: buckets whose spans count as compute a collective can hide behind
OVERLAP_COMPUTE = ('conv/matmul', 'fusion/elementwise', 'reduction')


def overlap_stats_from_paths(paths):
    """Trace-wide overlap statistics: per plane, the ``collective``-
    bucket intervals vs the union of compute-bucket intervals, summed
    across planes.  Uses the SAME interval arithmetic and definition
    as the runtime telemetry layer
    (:mod:`chainermn_tpu.telemetry.report`): ``overlap_fraction =
    1 - exposed/total``, None when the trace has no collective spans
    (absence of evidence is reported as absence)."""
    from chainermn_tpu.telemetry.report import overlap_from_intervals

    total = exposed = 0.0
    seen = False
    for ivs in _collect_intervals(paths).values():
        coll = [(a, b) for a, b, bk in ivs if bk == 'collective']
        if not coll:
            continue
        comp = [(a, b) for a, b, bk in ivs
                if bk in OVERLAP_COMPUTE]
        st = overlap_from_intervals(coll, comp)
        total += st['total_collective_s']   # _us actually; see below
        exposed += st['exposed_collective_s']
        seen = True
    # intervals above are in MICROSECONDS, so the "seconds" fields of
    # overlap_from_intervals come back in us; normalize to ms here
    return {
        'total_collective_ms': round(total / 1e3, 3),
        'exposed_collective_ms': round(exposed / 1e3, 3),
        'overlap_fraction': (
            None if not seen or total <= 0.0
            else round(max(0.0, min(1.0, 1.0 - exposed / total)), 4)),
    }


def _collect_host_events(paths, min_self_us=1.0):
    """(buckets, ops) from the raw XSpace host planes.

    The CPU backend emits no framework/HLO op-stats rows at all (the
    converter returns an IDLE-only table), but the ``/host:CPU``
    plane DOES carry per-executable and per-HLO-op spans
    (``TfrtCpuExecutable::Execute``, ``dot.3``, ``fusion.12``...).
    Walking the proto directly turns a CPU capture into a real
    breakdown -- the plumbing check that proves the whole
    capture->convert->aggregate path off-chip, which is exactly what
    the r3-r5 windows lacked.  Self time = span duration minus the
    duration of spans nested inside it on the same thread line.
    """
    pb = _xplane_pb2()
    agg = {}
    for path in paths:
        space = pb.XSpace()
        with open(path, 'rb') as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            meta = plane.event_metadata
            for line in plane.lines:
                spans = sorted(
                    ((ev.offset_ps, ev.offset_ps + ev.duration_ps,
                      meta[ev.metadata_id].name)
                     for ev in line.events
                     # '$'-prefixed spans are the python tracing
                     # scaffolding (profiler.py frames), not workload
                     if not meta[ev.metadata_id].name.startswith('$')),
                    key=lambda s: (s[0], -s[1]))
                stack = []  # (end_ps, self_ps accumulator index)
                selfs = []
                for start, end, name in spans:
                    while stack and stack[-1][0] <= start:
                        stack.pop()
                    if stack:  # nested: parent loses this span's time
                        selfs[stack[-1][1]][1] -= (end - start)
                    selfs.append([name, end - start])
                    stack.append((end, len(selfs) - 1))
                for name, self_ps in selfs:
                    a = agg.setdefault(name, [0, 0.0])
                    a[0] += 1
                    a[1] += max(self_ps, 0) / 1e6  # ps -> us
    buckets, ops = {}, []
    for name, (count, self_us) in agg.items():
        if self_us < min_self_us:
            continue
        cat = bucket_of(name)
        b = buckets.setdefault(cat, {'self_time_us': 0.0, 'ops': 0})
        b['self_time_us'] += self_us
        b['ops'] += 1
        ops.append({'op': name, 'category': cat, 'occurrences': count,
                    'self_time_us': round(self_us, 1)})
    return buckets, ops


# overview_page property keys worth surfacing (TPU traces populate
# these; host-only traces report zeros, which analyze_trace's caller
# sees only alongside real op rows anyway)
UTIL_KEYS = (
    'device_duty_cycle_percent',
    'mxu_utilization_percent',
    'hbm_utilization_percent',
    'flop_rate_utilization_relative_to_roofline',
    'device_idle_time_percent',
)


def device_utilization(paths):
    """Device-level utilization summary from the overview_page tool
    (best-effort; {} when unavailable)."""
    try:
        out = {}
        for table in _tool_tables(paths, 'overview_page'):
            props = table.get('p') or {}
            for key in UTIL_KEYS:
                if key in props and key not in out:
                    out[key] = props[key]
        return out
    except Exception:
        return {}


def overlap_by_axis_from_telemetry(outdir):
    """Per-mesh-axis overlap split from a telemetry session dir
    (``events-rank*.jsonl``).  Device xplane profiles carry no mesh
    axis names -- the HLO op name of a lowered all-reduce says
    nothing about WHICH named axis it spans -- so the dp-vs-tp split
    of the overlap column comes from the axis-tagged telemetry spans
    (:func:`chainermn_tpu.telemetry.report.overlap_stats`), captured
    alongside the profile (``CHAINERMN_TPU_TELEMETRY=<dir>``)."""
    from chainermn_tpu.telemetry import report as treport

    _metas, spans, _events, _bad = treport.load_rank_logs(outdir)
    st = treport.overlap_stats(spans)
    return {
        key: {
            'spans': agg['spans'],
            'total_collective_ms': round(
                agg['total_collective_s'] * 1e3, 3),
            'exposed_collective_ms': round(
                agg['exposed_collective_s'] * 1e3, 3),
            'overlap_fraction': agg['overlap_fraction'],
        }
        for key, agg in (st.get('per_axis') or {}).items()}


def analyze_trace(trace_dir, telemetry_dir=None):
    """One report object for one trace dir (or an explanatory stub).

    ``telemetry_dir`` (or a ``telemetry/`` subdir of the trace dir
    holding ``events-rank*.jsonl``) adds the per-axis dp-vs-tp split
    to the overlap object -- see
    :func:`overlap_by_axis_from_telemetry`."""
    paths = sorted(glob.glob(
        os.path.join(trace_dir, '**', '*.xplane.pb'), recursive=True))
    out = {'trace_dir': os.path.relpath(trace_dir, HERE)}
    if not paths:
        out['error'] = 'no .xplane.pb under trace dir'
        return out
    # a trace dir accumulates one timestamped profiler session per
    # capture (plugins/profile/<ts>/); summing them would double-count
    # self-times across rounds, so analyze ONLY the newest session
    sessions = {}
    for p in paths:
        sessions.setdefault(os.path.dirname(p), []).append(p)
    newest = max(sessions)  # session dir names are UTC timestamps
    paths = sessions[newest]
    out['session'] = os.path.relpath(newest, trace_dir)
    if len(sessions) > 1:
        out['older_sessions_ignored'] = len(sessions) - 1
    try:
        buckets, ops = _collect_ops(paths, 'hlo_stats')
        out['source'] = 'hlo_stats'
        if not ops:
            # a CPU/host-only trace has no HLO device plane; the
            # framework-op view still shows where host time went,
            # and exercises this parser off-chip
            buckets, ops = _collect_ops(paths, 'framework_op_stats')
            out['source'] = 'framework_op_stats (no device-op rows; ' \
                'host-only trace)'
        if not ops:
            # the CPU backend emits op-stats rows for NEITHER tool
            # (IDLE-only tables); the raw host plane still carries
            # per-executable / per-HLO-op spans -- aggregate those
            buckets, ops = _collect_host_events(paths)
            out['source'] = 'xplane_host_events (op-stats tools ' \
                'empty; aggregated raw host-plane spans)'
    except Exception as e:  # converter is external; never crash the CI
        out['error'] = 'xprof conversion failed: %r' % e
        return out
    if not ops:
        out['error'] = ('trace has no device-op, framework-op or '
                        'host-plane rows')
        return out
    # overlap column (ISSUE 6 / ROADMAP item 5): collective span time
    # hidden behind compute vs exposed, from the raw xplane intervals
    # (best-effort: op-stats-only traces carry no timestamps)
    try:
        out['overlap'] = overlap_stats_from_paths(paths)
    except Exception as e:
        out['overlap'] = {'total_collective_ms': None,
                          'exposed_collective_ms': None,
                          'overlap_fraction': None,
                          'error': repr(e)}
    # dp-vs-tp axis split of the overlap column, from the axis-tagged
    # telemetry capture when one rode along (never fabricated from
    # the axis-blind device profile)
    tdir = telemetry_dir or os.path.join(trace_dir, 'telemetry')
    if glob.glob(os.path.join(tdir, 'events-rank*.jsonl')):
        try:
            out['overlap']['by_axis'] = \
                overlap_by_axis_from_telemetry(tdir)
            out['overlap']['by_axis_source'] = tdir
        except Exception as e:
            out['overlap']['by_axis_error'] = repr(e)
    util = device_utilization(paths)
    if util:
        out['device_utilization'] = util
    total = sum(b['self_time_us'] for b in buckets.values())
    out['total_self_time_us'] = round(total, 1)
    out['buckets'] = {
        k: {'self_time_us': round(v['self_time_us'], 1),
            'pct': round(100.0 * v['self_time_us'] / total, 1),
            'ops': v['ops']}
        for k, v in sorted(buckets.items(),
                           key=lambda kv: -kv[1]['self_time_us'])}
    ops.sort(key=lambda o: -o['self_time_us'])
    out['top_ops'] = ops[:TOP_N]
    return out


def latest_trace_dirs():
    """All (platform, strategy) trace dirs under results/traces.
    Each dir holds exactly one strategy's captures; session selection
    (newest capture within a dir) happens in analyze_trace."""
    return sorted(p for p in
                  glob.glob(os.path.join(RES, 'traces', '*', '*'))
                  if os.path.isdir(p))


def render(report):
    lines = ['## %s' % report['trace_dir']]
    if report.get('error'):
        lines.append('  (no analysis: %s)' % report['error'])
        return '\n'.join(lines)
    lines.append('  total device self time: %.1f us'
                 % report['total_self_time_us'])
    ov = report.get('overlap') or {}
    if ov.get('overlap_fraction') is not None:
        lines.append(
            '  overlap fraction: %.3f  (collective %.3f ms, '
            '%.3f ms exposed)'
            % (ov['overlap_fraction'], ov['total_collective_ms'],
               ov['exposed_collective_ms']))
    elif ov:
        lines.append('  overlap: no collective spans in trace%s'
                     % (' (%s)' % ov['error'] if ov.get('error')
                        else ''))
    for key, agg in sorted((ov.get('by_axis') or {}).items()):
        frac = agg.get('overlap_fraction')
        lines.append(
            '    axis %-12s %4d spans  %8.3f ms collective  '
            '%8.3f ms exposed  overlap %s'
            % (key, agg['spans'], agg['total_collective_ms'],
               agg['exposed_collective_ms'],
               '-' if frac is None else '%.3f' % frac))
    for key, val in (report.get('device_utilization') or {}).items():
        lines.append('  %s: %s' % (key, val))
    for name, b in report['buckets'].items():
        lines.append('  %-20s %8.1f us  %5.1f%%  (%d ops)'
                     % (name, b['self_time_us'], b['pct'], b['ops']))
    lines.append('  top ops by self time:')
    for o in report['top_ops']:
        extras = []
        # tolerant per-op formatting (ADVICE r5 #3): a cell the
        # converter rendered as a formatted string must not crash the
        # report -- parse through cell_float, fall back to the raw
        # value verbatim
        for field, fmt in (('gflops_per_sec', '%.0f GF/s'),
                           ('memory_bw_gibs', '%.0f GiB/s'),
                           ('dma_stall_pct', '%.0f%% DMA stall')):
            raw = o.get(field)
            if not raw:
                continue
            try:
                f = cell_float(raw)
                extras.append(fmt % f if f is not None
                              else '%s=%r' % (field, raw))
            except (TypeError, ValueError):
                extras.append('%s=%r' % (field, raw))
        lines.append('    %8.1f us  %-28s %-16s %s'
                     % (o['self_time_us'], o['op'][:28], o['category'],
                        ', '.join(extras)))
    return '\n'.join(lines)


def main(argv):
    telemetry_dir = None
    if '--telemetry' in argv:
        i = argv.index('--telemetry')
        telemetry_dir = argv[i + 1] if i + 1 < len(argv) else None
        argv = argv[:i] + argv[i + 2:]
    dirs = [a for a in argv if not a.startswith('--')]
    if '--latest' in argv or not dirs:
        dirs = dirs or latest_trace_dirs()
    out_path = os.path.join(RES, 'trace_report.json')
    if not dirs:
        # ADVICE r5 #4: a previously committed breakdown must not
        # outlive the captures it described (strategy_trace rmtree's
        # failed capture dirs) -- rewrite the artifact with an
        # explanatory stub so it always reflects the LATEST capture
        # state instead of contradicting a jsonl row's trace_error
        # SAME row shape as the banked-artifact path (one JSONL row,
        # 'trace_dir' key always present, errors under 'error'): JSON
        # consumers iterate rows and read row['trace_dir'] / .get(
        # 'error') uniformly -- the old stub omitted trace_dir and
        # diverged from the per-dir schema
        stub = {
            'trace_dir': None,
            'error': 'no trace dirs found',
            'detail': ('no capture dirs under %s at report time; any '
                       'previous per-op breakdown is superseded (its '
                       'captures were removed)'
                       % os.path.relpath(os.path.join(RES, 'traces'),
                                         HERE)),
        }
        os.makedirs(RES, exist_ok=True)
        with open(out_path, 'w') as f:
            f.write(json.dumps(stub) + '\n')
        print('no trace dirs found under %s'
              % os.path.join(RES, 'traces'))
        print('wrote stub %s' % os.path.relpath(out_path,
                                                os.getcwd()))
        return 0
    reports = [analyze_trace(d, telemetry_dir=telemetry_dir)
               for d in dirs]
    with open(out_path, 'w') as f:
        for rep in reports:
            f.write(json.dumps(rep) + '\n')
    for rep in reports:
        print(render(rep))
    print('wrote %s' % os.path.relpath(out_path, os.getcwd()))
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
