"""What every run does: find the cell's files by the names in
``BENCHMARK.json``, check the device, place the compile cache, hand the
cell to its driver, reduce the trace, read the per-layer metrics and
build the result line."""

import contextlib
import glob
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: the benchmark's own directory, not the ``.jax_compile_cache`` the
#: program and the repo's CPU tests fill: entries a test run leaves there
#: have no access-time files, and every write on the chip then fails
CACHE_DIR = os.path.join(ROOT, '.chipbench_cache')
TRACE_DIR = os.path.join(ROOT, '.chipbench_trace')
#: the profiler runs over the LAST seconds of a ``--trace 1`` window
TRACE_SECONDS = 8.0


class Spec:
    """One cell and the files its names lead to."""

    def __init__(self, workload, root=ROOT):
        self.root = root
        with open(os.path.join(root, 'BENCHMARK.json')) as f:
            self.benchmark = json.load(f)
        cells = {c['name']: c for c in self.benchmark['workloads']}
        if workload not in cells:
            raise SystemExit('no workload %r in BENCHMARK.json (have %s)'
                             % (workload, ', '.join(sorted(cells))))
        self.cell = cells[workload]
        self.name = workload
        config = next(c for c in self.benchmark['configs']
                      if c['name'] == self.cell['config'])
        self.cfg = self._json(config['file'])
        self.mix = self._json('chipbench/traffic/%s.json'
                              % self.cell['traffic'])
        self.limits = self._json('chipbench/limits/%s.json' % workload)
        self.end_to_end = [m for m in self.benchmark['end_to_end']
                           if workload in m.get('workloads', [workload])]
        self.per_layer = [
            dict(m, **self._json('chipbench/layer_metrics/%s.json'
                                 % m['name']))
            for m in self.benchmark['per_layer']
            if workload in m.get('workloads', [workload])]

    def _json(self, relative):
        with open(os.path.join(self.root, relative)) as f:
            return json.load(f)

    def reader(self, name):
        """``read(run, **args)`` of ``chipbench/readers/<name>.py``,
        found by file so that a later PR adds a reader by adding a
        file."""
        path = os.path.join(self.root, 'chipbench', 'readers',
                            name + '.py')
        spec = importlib.util.spec_from_file_location(
            'chipbench_reader_' + name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


class Run:
    """One run's state: what the driver fills and the readers read."""

    def __init__(self, spec, seed, seconds, trace, t_process, devices,
                 control=False):
        self.spec, self.seed, self.seconds = spec, int(seed), seconds
        self.trace_requested = bool(trace)
        self.t_process = t_process
        self.devices = devices
        self.spans = []       # (name, t0, t1) on time.perf_counter
        self.counters = {}
        self.e2e = {}
        self.checks = []      # (name, value, limit)
        self.window = None
        self.attempted = self.failed = 0
        self.memory_peak_bytes = None
        self.trace = None     # chipbench.trace.Summary of a traced run
        self.control = control  # control.py: read the fp8 control too
        self.control_readings = []
        self._tracing = False
        self._window_span = None
        self._trace_dir = os.path.join(TRACE_DIR, spec.name)

    def say(self, msg):
        print('[chipbench %7.1fs] %s'
              % (time.perf_counter() - self.t_process, msg), flush=True)

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def setup_done(self):
        self.e2e['setup_s'] = time.perf_counter() - self.t_process
        self.say('set-up done: %.1f s' % self.e2e['setup_s'])

    def maybe_start_trace(self, elapsed):
        if (self.trace_requested and not self._tracing
                and elapsed >= self.seconds - TRACE_SECONDS):
            import jax
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=options)
            self._tracing = True
            from chipbench import trace
            self._window_span = jax.profiler.TraceAnnotation(
                trace.WINDOW_SPAN)
            self._window_span.__enter__()

    def stop_trace(self):
        if not self._tracing:
            return
        import jax
        self._window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False
        from chipbench import trace
        files = glob.glob(os.path.join(self._trace_dir, '**',
                                       '*.xplane.pb'), recursive=True)
        self.trace = trace.reduce(trace.load(files[0]))
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        if self.trace is None and self.devices[0].platform == 'tpu':
            raise SystemExit('chipbench: the traced window holds no '
                             'device operation')

    def read_memory_peak(self):
        """Peak bytes on the fullest chip.  Read while the program's
        state is live: the allocator's ``peak_bytes_in_use`` leaves out
        what executables reserve for their temporaries
        (``peak_bytes_reserved``, 11 of the LM step's 15.9 GB), so the
        peak is the larger of it and live bytes + that reservation."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            if 'peak_bytes_in_use' in stats:
                peaks.append(max(
                    stats['peak_bytes_in_use'],
                    stats.get('bytes_in_use', 0)
                    + stats.get('peak_bytes_reserved', 0)))
        self.say('memory_stats of device 0: %r'
                 % (self.devices[0].memory_stats(),))
        self.memory_peak_bytes = max(peaks) if peaks else None

    def check(self, name, value):
        """One number compared, beside its limit; a number the cell's
        limits file does not name is printed and decides nothing."""
        value = float(value)
        if name not in self.spec.limits:
            self.say('reading %s: %.6g (no limit in this cell)'
                     % (name, value))
            return
        limit = self.spec.limits[name]
        ok = math.isfinite(value) and value <= limit
        self.checks.append((name, value, limit))
        self.say('check %s: %.6g (limit %.6g) %s'
                 % (name, value, limit, 'ok' if ok else 'NOT CORRECT'))

    def control_reading(self, name, value):
        self.control_readings.append((name, float(value)))
        self.say('control %s: %.6g' % (name, float(value)))

    @property
    def correct(self):
        return bool(self.checks) and all(
            math.isfinite(v) and v <= limit for _, v, limit in self.checks)

    def span_seconds(self, name):
        """Seconds of the window covered by spans called ``name``."""
        t0, t1 = self.window
        return sum(min(b, t1) - max(a, t0) for n, a, b in self.spans
                   if n == name and b > t0 and a < t1)


def place_compile_cache():
    """JAX's persistent cache at ONE fixed path inside the checkout
    (the path is part of the key).  The program's own helper takes the
    directory it is given through ``JAX_COMPILATION_CACHE_DIR``."""
    os.environ['JAX_COMPILATION_CACHE_DIR'] = CACHE_DIR
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS',
                          '0')
    os.environ.setdefault('JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES',
                          '-1')
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')


def find_devices(chips, platform):
    """The first ``chips`` devices, or an exit: a measurement path that
    finds no accelerator, or fewer chips than the cell asks for, fails
    and prints no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < chips:
        raise SystemExit(
            'chipbench: need %d %s chip(s), JAX sees %r'
            % (chips, platform, devices))
    return devices[:chips]


def device_line(run):
    first = run.devices[0]
    out = {'platform': first.platform, 'kind': first.device_kind,
           'count': len(run.devices),
           'memory_peak_bytes': run.memory_peak_bytes}
    if run.trace is not None:
        out['busy_s'] = run.trace.busy_s
        out['window_s'] = run.trace.window_s
    return out


def run_cell(spec, seed, seconds, trace, t_process, platform='tpu',
             control=False):
    """One run of one cell; the result as a dict (``main`` prints it).
    ``platform='cpu'`` exists for the tests' rehearsals at tiny sizes:
    ``run.py`` never passes it, and what such a run returns is not a
    device metric."""
    devices = find_devices(spec.cell['chips'], platform)
    run = Run(spec, seed, seconds, trace, t_process, devices, control)
    run.say('cell %s on %d x %s; compile cache %s holds %d entries'
            % (spec.name, len(devices), devices[0].device_kind, CACHE_DIR,
               len(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR)
               else 0))
    driver = importlib.import_module('chipbench.drivers.'
                                     + spec.mix['kind'])
    driver.run(run)

    if trace:
        metrics = {}
        for m in spec.per_layer:
            value = spec.reader(m['reader'])(run, **m.get('args', {}))
            if value is not None:
                metrics[m['name']] = {'value': float(value),
                                      'unit': m['unit']}
    else:
        metrics = {m['name']: {'value': float(run.e2e[m['name']]),
                               'unit': m['unit']}
                   for m in spec.end_to_end}
    result = {'correct': run.correct, 'attempted': int(run.attempted),
              'failed': int(run.failed), 'metrics': metrics,
              'device': device_line(run),
              'checks': [[n, v, limit] for n, v, limit in run.checks]}
    if control:
        result['control'] = [[n, v] for n, v in run.control_readings]
    if run.trace is not None:
        result['breakdown'] = run.trace.breakdown()
    return result


def main(argv, t_process):
    import argparse
    parser = argparse.ArgumentParser(
        description='one run of one cell of BENCHMARK.json')
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    spec = Spec(args.workload)
    place_compile_cache()
    result = run_cell(spec, args.seed, args.seconds, args.trace,
                      t_process)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
