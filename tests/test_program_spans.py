"""The program's own spans in the profiler's trace (ISSUE 25): an open
JAX profiler session is an enabled telemetry session, every
layer-boundary span is a ``cmn:<name>`` annotation on the clock the
device events are on and a recorder record that names its parent and
its thread; executables and kernels carry stable names; and the
benchmark's two new readers read what the program keeps."""

import glob
import importlib.util
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu import serving, telemetry, training
from chainermn_tpu.models import MLP, Classifier, TransformerLM
from chainermn_tpu.telemetry import recorder as rec_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    yield
    telemetry.disable()


def _updater(device_prefetch):
    comm = chainermn_tpu.create_communicator('xla', mesh_shape=(2, 4))
    model = MLP(n_units=16, n_out=10)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 784), jnp.float32))
    opt = chainermn_tpu.create_multi_node_optimizer(optax.sgd(0.1), comm)
    rs = np.random.RandomState(0)
    data = [(rs.randn(784).astype(np.float32), np.int32(i % 10))
            for i in range(64)]
    if device_prefetch:
        iterator = training.iterators.MultiprocessIterator(
            data, 16, shuffle=False)
    else:
        iterator = training.SerialIterator(data, 16, shuffle=False)
    return training.StandardUpdater(
        iterator, opt, Classifier(model.apply), params, comm,
        has_aux=True, device_prefetch=device_prefetch)


def _lm():
    model = TransformerLM(vocab_size=32, d_model=32, n_heads=4,
                          n_layers=1, d_ff=32, max_len=64)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))['params']


def _engine(page_size=8):
    eng = serving.GenerationEngine(*_lm(), n_slots=2,
                                   max_prompt_len=8, paged=True,
                                   page_size=page_size)
    eng.warmup()
    return eng, serving.GenerationQueue(max_prompt_len=8,
                                        page_size=page_size)


class _Traced:
    """What one profiled block left: the ``cmn:`` events of the
    ``.xplane.pb`` by host line, and the recorder's records on
    ``time.perf_counter``, with the host-clock window around both."""

    def __init__(self, tmp_path, body):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        self.t0 = time.perf_counter()
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=options)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        self.t1 = time.perf_counter()
        path, = glob.glob(os.path.join(str(tmp_path), '**',
                                       '*.xplane.pb'), recursive=True)
        self.lines = {}     # line id -> [(name, start, end, stats)]
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith('/host:'):
                continue
            for index, line in enumerate(plane.lines):
                events = [(ev.name[len(rec_mod.TRACE_PREFIX):],
                           ev.start_ns, ev.start_ns + ev.duration_ns,
                           {k: v for k, v in ev.stats})
                          for ev in line.events
                          if ev.name.startswith(rec_mod.TRACE_PREFIX)]
                if events:
                    self.lines[index] = events
        rec = telemetry.active()
        self.records = [
            dict(r, p0=rec.to_perf_counter(r['t0']),
                 p1=rec.to_perf_counter(r['t1']))
            for r in rec.events if r['type'] == 'span']

    def line_of(self, name):
        """The one host line whose events include ``name``."""
        line, = [k for k, events in self.lines.items()
                 if any(n == name for n, _, _, _ in events)]
        return line

    def trace_events(self, name):
        return [e for events in self.lines.values() for e in events
                if e[0] == name]

    def named(self, name):
        return [r for r in self.records if r['name'] == name]

    def check_children_inside_parents(self):
        by_id = {r['id']: r for r in self.records if 'id' in r}
        linked = 0
        for r in by_id.values():
            parent = by_id.get(r['parent'])
            if parent is None:
                continue
            linked += 1
            assert parent['thread'] == r['thread']
            assert parent['p0'] <= r['p0'] and r['p1'] <= parent['p1']
        assert linked
        for events in self.lines.values():
            for name, a, b, _ in events:
                for other, c, d, _ in events:
                    # one thread's spans nest or are disjoint
                    assert b <= c or d <= a or (a <= c and d <= b) \
                        or (c <= a and b <= d), (name, other)

    def check_records_inside_the_window(self):
        assert self.records
        for r in self.records:
            assert self.t0 <= r['p0'] <= r['p1'] <= self.t1, r['name']


@pytest.mark.parametrize('device_prefetch', [0, 2])
def test_profiler_session_switches_the_trainers_spans_on(
        tmp_path, device_prefetch):
    """(a) No telemetry enabled: the profiler's trace holds the
    trainer's ``cmn:`` spans, the producers' on threads of their own,
    children inside their parents, and the recorder's records lie in
    the host-clock window around the session."""
    upd = _updater(device_prefetch)
    try:
        upd.update()
        upd.update()
        assert telemetry.active() is None
        traced = _Traced(tmp_path, lambda: [upd.update()
                                            for _ in range(4)])
    finally:
        finalize = getattr(upd.iterator, 'finalize', None)
        if finalize is not None:
            finalize()
    assert telemetry.active().follows_profiler
    assert telemetry.live() is None     # the session is closed
    expected = ['train_update', 'input_wait', 'host_batch_prep', 'h2d',
                'jitted_step', 'metrics_sync']
    if device_prefetch:
        expected.append('batch_fetch')
    for name in expected:
        assert traced.trace_events(name), name
        assert traced.named(name), name
    main = traced.line_of('train_update')
    for name in ('input_wait', 'jitted_step', 'metrics_sync'):
        assert traced.line_of(name) == main
    update_ids = {r['id'] for r in traced.named('train_update')}
    assert len(update_ids) == 4
    for name in ('input_wait', 'jitted_step', 'metrics_sync'):
        assert {r['parent'] for r in traced.named(name)} == update_ids
    if device_prefetch:
        # both producers are threads of their own, with no parent
        fetch = traced.line_of('batch_fetch')
        place = traced.line_of('host_batch_prep')
        assert len({main, fetch, place}) == 3
        assert traced.line_of('h2d') == place
        for name in ('batch_fetch', 'host_batch_prep', 'h2d'):
            assert {r['parent'] for r in traced.named(name)} == {None}
    else:
        assert traced.line_of('host_batch_prep') == main
        assert {r['parent'] for r in traced.named('host_batch_prep')} \
            == update_ids
    # the trainer's spans carry the iteration, in both sinks
    iterations = [r['iteration'] for r in traced.named('train_update')]
    assert iterations == [2, 3, 4, 5]
    assert [s['iteration'] for _, _, _, s in
            traced.trace_events('train_update')] == iterations
    traced.check_children_inside_parents()
    traced.check_records_inside_the_window()


def test_profiler_session_switches_the_schedulers_spans_on(tmp_path):
    """(a) The paged engine: one ``serve_tick`` per tick, parent of
    admission, prefill, decode preparation, decode and emission; the
    request stages stay recorder-only."""
    eng, queue = _engine()
    assert telemetry.active() is None

    def ticks():
        for prompt, n_out in (([1, 2, 3], 6), ([4, 5], 4), ([6], 5)):
            queue.submit(prompt, n_out)
        while eng.step(queue):
            pass

    traced = _Traced(tmp_path, ticks)
    children = ('serve_expire', 'serve_admit', 'serve_prefill_prep',
                'serve_prefill', 'serve_decode_prep', 'serve_decode',
                'serve_emit')
    tick_ids = {r['id']: r for r in traced.named('serve_tick')}
    assert len(tick_ids) >= 6
    assert len(traced.trace_events('serve_tick')) == len(tick_ids)
    main = traced.line_of('serve_tick')
    for name in children:
        assert traced.trace_events(name), name
        assert traced.line_of(name) == main
        for r in traced.named(name):
            assert r['parent'] in tick_ids
            assert r['step'] == tick_ids[r['parent']]['step']
    assert len(traced.named('serve_prefill')) == 3
    assert sum(r['prefills'] for r in tick_ids.values()) == 3
    assert [r['step'] for r in traced.named('serve_tick')] == \
        sorted(r['step'] for r in tick_ids.values())
    # the dispatch and the wait, by name, under their call's span
    for call in ('serve_decode', 'serve_prefill'):
        ids = {r['id'] for r in traced.named(call)}
        for part in ('_dispatch', '_wait'):
            assert traced.named(call + part)
            assert {r['parent'] for r in traced.named(call + part)} \
                <= ids
            assert traced.line_of(call + part) == main
    assert {r.get('reason') for r in traced.named('serve_decode')} \
        >= {None, 'prime'}
    assert {r['ran_ahead'] for r in traced.named('serve_decode')
            if 'ran_ahead' in r} == {0, 1}
    # an interval the engine only knows the ends of: a record, no
    # annotation, no place in a thread's stack
    idle = traced.named('device_idle')
    assert idle and not traced.trace_events('device_idle')
    assert all('id' not in r and 'parent' not in r for r in idle)
    # per-request stages: records, no annotation
    stages = {'queue_wait', 'admit_wait', 'bucket_pack', 'prefill',
              'decode'}
    assert stages <= {r['name'] for r in traced.records}
    for name in stages:
        assert not traced.trace_events(name)
        assert all('request_id' in r for r in traced.named(name))
    traced.check_children_inside_parents()
    traced.check_records_inside_the_window()


@pytest.mark.parametrize('page_size', [8, 16])
def test_decode_span_says_how_the_paged_kernels_grid_engaged(page_size):
    """``serve_decode`` carries the pages the paged decode kernel's
    copies fetched and the grid steps it took, over rows and layers,
    from the lengths and the rule alone (``decode_pages_per_grid_step``
    reads their ratio), and what of a stored row of the pool it reads
    is K/V (``kv_lane_fill``).  Pages of 16 bfloat16 positions make a
    head-major pool, pages of 8 (off the dtype's sublane tile) a
    page-major one: the counters follow the branch that reads it."""
    from chainermn_tpu import ops
    eng, queue = _engine(page_size)
    recorder = telemetry.enable()
    request = queue.submit([1, 2, 3, 4, 5], 12)
    while not request.done():
        eng.step(queue)
    spans = [r for r in recorder.events if r.get('type') == 'span'
             and r['name'] == 'serve_decode']
    # a span that dispatches a call says whether it ran ahead of its
    # predecessor's read; the span that READS a call's vector, a tick
    # later, says what the call was: one of each a launched call
    assert [r['ran_ahead'] for r in spans
            if 'ran_ahead' in r] == [0] + [1] * 10
    decode = [r for r in spans if 'bucket' in r]
    assert len(decode) == 11
    head_major = page_size == 16
    assert ('head_major' in eng._cache_struct) == head_major
    leaf = eng._cache_struct['k'][0]
    assert leaf.shape[1:] == ((4, 16, 128) if head_major
                              else (8, 4, 128))
    for i, r in enumerate(decode):
        # one live row of 6 + i positions, the bucket's other rows on
        # the scratch page; one layer
        lengths = [6 + i] + [1] * (r['bucket'] - 1)
        assert r['kv_positions'] == 6 + i
        assert (r['kv_pages_read'], r['kv_grid_steps']) == \
            ops.decode_paged_grid(lengths, leaf.shape[1:], leaf.dtype,
                                  eng.pages_per_seq,
                                  head_major=head_major)
        # this model's head of 8 values alone in a 128-lane row (4
        # heads of 8 do not fill one), in either layout
        assert (r['kv_live_lanes'], r['kv_lanes']) == (8, 128)
        assert r['kv_pages_read'] == \
            -(-(6 + i) // page_size) + r['bucket'] - 1
        # every row's live pages fit one step of either rule's
        assert r['kv_grid_steps'] == r['bucket']


def test_with_neither_profiler_nor_recorder_nothing_is_recorded():
    """(b) The off path: ``NULL_SPAN``, no recorder installed, no
    record made -- through a trainer step and a scheduler tick."""
    assert telemetry.live() is None
    assert telemetry.span('train_update') is rec_mod.NULL_SPAN
    upd = _updater(0)
    upd.update()
    eng, queue = _engine()
    queue.submit([1, 2, 3], 3)
    while eng.step(queue):
        pass
    assert telemetry.active() is None and telemetry.live() is None


def test_a_lone_engine_reads_the_environment_variable(monkeypatch):
    """``CHAINERMN_TPU_TELEMETRY`` used to be read by updaters and
    communicators only: a serving process with neither ignored it."""
    monkeypatch.setenv(telemetry.ENV_VAR, '1')
    eng, queue = _engine()
    rec = telemetry.active()
    assert rec is not None and rec.outdir is None
    assert not rec.follows_profiler
    queue.submit([1, 2, 3], 2)
    while eng.step(queue):
        pass
    assert {'serve_tick', 'serve_decode', 'queue_wait'} <= {
        r['name'] for r in rec.events}


def test_one_predicate_after_the_profile_ends(tmp_path, monkeypatch):
    """``live()`` is the one predicate for "telemetry is on":
    ``enabled()`` and the guards at the communicator, the optimizer
    wrapper and the chaos sites agree with it once the profiler
    session that installed the recorder has closed; and such a
    recorder does not stand in for ``CHAINERMN_TPU_TELEMETRY``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert telemetry.enabled()
        with telemetry.span('profiled'):
            pass
    finally:
        jax.profiler.stop_trace()
    rec = telemetry.active()
    assert rec.follows_profiler and telemetry.live() is None
    assert not telemetry.enabled() and telemetry.registry() is None
    n = len(rec.events)
    upd = _updater(0)       # a communicator, a wrapper, an updater
    upd.update()
    upd.comm.broadcast_data({'w': jnp.ones(3)})
    telemetry.event('late')
    assert len(rec.events) == n
    # the variable is read all the same, and makes the recorder last
    assert telemetry.maybe_enable_from_env() is rec
    monkeypatch.setenv(telemetry.ENV_VAR, '1')
    telemetry._env_checked = False
    assert telemetry.maybe_enable_from_env() is rec
    assert not rec.follows_profiler and telemetry.live() is rec
    upd.update()
    assert 'train_update' in {r['name'] for r in rec.events[n:]}


def test_two_threads_install_one_recorder(tmp_path):
    """The rule under contention: many threads find the profiler open
    at once and install exactly one recorder, losing no record."""
    import sys
    import threading
    n_threads, n_spans = 16, 50
    start = threading.Barrier(n_threads)
    seen = []

    def worker():
        start.wait(timeout=30)
        for _ in range(n_spans):
            with telemetry.span('contended'):
                pass
        seen.append(telemetry.active())

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        jax.profiler.stop_trace()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(seen) == n_threads and len(set(map(id, seen))) == 1
    records = [r for r in seen[0].events if r['name'] == 'contended']
    assert len(records) == n_threads * n_spans
    assert len({r['id'] for r in records}) == len(records)


def test_trainer_step_carries_its_name():
    """(c) The lowered trainer step is ``jit_train_step``."""
    upd = _updater(0)
    arrays = upd.shard_batch([next(upd.iterator)[0]] * 16)
    text = upd._step.lower(*upd._step_args(arrays)).as_text()
    assert 'module @jit_train_step ' in text


#: the engine's executables by family: the names other code keys on
_EXECUTABLE_NAMES = {
    'prefill': 'jit_serve_prefill',
    'decode': 'jit_serve_decode',
    'copy': 'jit_serve_page_copy',
    'draft_prefill': 'jit_serve_draft_prefill',
    'draft_decode': 'jit_serve_draft_decode',
    'verify': 'jit_serve_verify',
    'draft_copy': 'jit_serve_draft_page_copy',
}
_ENGINE_MODES = {
    'slab': {},
    'paged': {'paged': True},
    'paged_chunked': {'paged': True, 'prefill_chunk': 4},
    'slab_draft': {'draft': True},
    'paged_draft': {'paged': True, 'draft': True},
}


@pytest.mark.parametrize('mode', sorted(_ENGINE_MODES))
def test_executables_carry_stable_names(mode):
    """(c) In every mode of the engine ``warmup()`` builds one
    executable per bucket of each family the mode has and no other,
    each traced once and named for its family -- exactly the decode
    ones hold ``decode`` -- and a second ``warmup()`` traces and
    compiles nothing."""
    options = dict(_ENGINE_MODES[mode])
    draft = options.pop('draft', False)
    paged = options.get('paged', False)
    model, params = _lm()
    if draft:       # any model of the same vocabulary can propose
        options.update(draft_model=model, draft_params=params)
    eng = serving.GenerationEngine(model, params, n_slots=2,
                                   max_prompt_len=8, page_size=8,
                                   **options)
    warm = eng.warmup()

    def lower_name(exe):
        # an AOT executable's HLO module carries the jitted name
        return exe.as_text().split('HloModule ', 1)[1].split(
            ',', 1)[0].strip()

    n_prefill = 1 if 'prefill_chunk' in options else len(
        eng.prefill_edges)
    n_decode = len(eng.decode_edges)
    tables = {'prefill': (eng._prefill, n_prefill),
              'decode': (eng._decode, n_decode),
              'copy': ({0: eng._copy} if eng._copy else {},
                       int(paged)),
              'draft_prefill': (eng._draft_prefill, n_prefill * draft),
              'draft_decode': (eng._draft_decode, n_decode * draft),
              'verify': (eng._verify, n_decode * draft),
              'draft_copy': ({0: eng._draft_copy} if eng._draft_copy
                             else {}, int(paged and draft))}
    names = set()
    for family, (table, n_buckets) in tables.items():
        assert len(table) == n_buckets, (family, sorted(table))
        for bucket, (exe, aot) in table.items():
            assert aot
            name = lower_name(exe)
            assert name == _EXECUTABLE_NAMES[family], (family, bucket)
            assert ('decode' in name) == ('decode' in family), name
            names.add(name)
    assert names == {_EXECUTABLE_NAMES[family]
                     for family, (_, n) in tables.items() if n}
    assert set(warm) == {family for family, (_, n) in tables.items()
                         if n and 'copy' not in family}

    def counts():
        return {'compile_count': eng.compile_count,
                'prefill': eng.prefill_trace_count,
                'decode': eng.decode_trace_count,
                'copy': eng.copy_trace_count,
                'draft': eng.draft_trace_count,
                'verify': eng.verify_trace_count}

    assert counts() == {
        'compile_count': sum(n for _, n in tables.values()),
        'prefill': n_prefill, 'decode': n_decode,
        'copy': tables['copy'][1] + tables['draft_copy'][1],
        'draft': tables['draft_prefill'][1] + tables['draft_decode'][1],
        'verify': tables['verify'][1]}
    before, compiles = counts(), len(telemetry.compile_log)
    assert eng.warmup() == warm
    assert counts() == before
    assert len(telemetry.compile_log) == compiles


def test_compile_log_is_always_on_and_bounded():
    """The one always-on counter: building an updater registers the
    listener once; a compile appends ``(perf_counter, event, s)``."""
    _updater(0)
    telemetry.install_compile_log()     # idempotent
    x = jnp.ones(7)
    n0 = len(telemetry.compile_log)
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + n0)(x).block_until_ready()
    t1 = time.perf_counter()
    compiles = list(telemetry.compile_log)[n0:]
    assert len(compiles) == 1
    t, event, seconds = compiles[0]
    assert event == 'backend_compile'
    assert t0 <= t <= t1 and 0 < seconds < t1 - t0
    assert telemetry.compile_log.maxlen == 4096
    assert telemetry.active() is None


# ---------------------------------------------------------------------
# (d) the benchmark's two new readers, on hand-made runs

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        'reader_' + name,
        os.path.join(ROOT, 'chipbench', 'readers', name + '.py'))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _run(window):
    said = []
    return types.SimpleNamespace(window=window, say=said.append,
                                 said=said)


def _ms(value):
    # a record's wall-clock times (1.8e9 s) resolve to ~0.2 us
    return pytest.approx(value, abs=0.01)


def _record(rec, name, t0, t1, **attrs):
    """A span record at perf_counter times ``t0``..``t1``."""
    base = rec.now() - time.perf_counter()
    rec._append(dict({'type': 'span', 'name': name, 'kind': 'x',
                      't0': base + t0, 't1': base + t1}, **attrs))


def test_program_span_reader_on_a_hand_made_run():
    read = _reader('program_span')
    window = (100.0, 110.0)
    assert read(_run(window), stat='mean', span='jitted_step') is None
    rec = telemetry.enable()
    assert read(_run(window), stat='mean', span='jitted_step') is None
    # before the window, and straddling its end: left out
    _record(rec, 'jitted_step', 99.0, 99.5, id=1, parent=None)
    _record(rec, 'jitted_step', 109.9, 110.1, id=2, parent=None)
    for i, ms in enumerate((2.0, 4.0, 6.0, 8.0)):
        _record(rec, 'jitted_step', 101.0 + i, 101.0 + i + ms / 1e3,
                id=10 + i, parent=None)
    # producer time per batch, in whatever thread
    for i in range(2):
        _record(rec, 'batch_fetch', 102.0 + i, 102.010 + i, id=20 + i,
                parent=None, thread=7)
        _record(rec, 'host_batch_prep', 103.0 + i, 103.030 + i,
                id=30 + i, parent=None, thread=8)
        _record(rec, 'h2d', 103.5 + i, 103.505 + i, id=40 + i,
                parent=None, thread=8)
    # two ticks with children, one decode outside any tick
    for i, tick in enumerate((50, 51)):
        t = 105.0 + i
        _record(rec, 'serve_tick', t, t + 0.100, id=tick, parent=None)
        _record(rec, 'serve_admit', t, t + 0.001, id=60 + i,
                parent=tick)
        _record(rec, 'serve_decode', t + 0.010, t + 0.080, id=70 + i,
                parent=tick)
    _record(rec, 'serve_prefill', 105.081, 105.095, id=80, parent=50)
    _record(rec, 'serve_decode', 107.0, 107.070, id=81, parent=None)

    run = _run(window)      # one run: the records are laid out once
    assert read(run, stat='mean', span='jitted_step') == _ms(5.0)
    assert read(run, stat='p75', span='jitted_step') == _ms(6.5)
    assert read(run, stat='mean', span='absent') is None
    assert 'program spans: 18 records' in run.said[0]
    assert len(run.said) == 1
    assert read(run, stat='sum_per', per='host_batch_prep',
                spans=['batch_fetch', 'host_batch_prep', 'h2d']) == \
        _ms(45.0)
    assert read(run, stat='sum_per', per='absent',
                spans=['h2d']) is None
    # a tick's host-only part: the tick less two of its children
    host = read(run, stat='self_mean', span='serve_tick',
                less=['serve_prefill', 'serve_decode'])
    assert host == _ms((16.0 + 30.0) / 2)
    assert 'serve_admit 1.000' in run.said[-1]
    assert 'self 22.000' in run.said[-1]
    with pytest.raises(KeyError):
        read(run, stat='median', span='serve_tick')


def _serving_records(rec):
    """Two ticks of a scheduler that writes ISSUE 37's spans, by hand:
    tick 50 admits two requests and primes the pipeline, tick 51 runs
    ahead; every child named, 3 ms and 1 ms of the ticks uncovered."""
    t = 105.0
    _record(rec, 'serve_tick', t, t + 0.050, id=50, parent=None,
            admitted=2)
    _record(rec, 'serve_expire', t, t + 0.001, id=51, parent=50)
    _record(rec, 'serve_admit', t + 0.001, t + 0.002, id=52, parent=50)
    at = t + 0.002
    for i, rid in enumerate(('r1', 'r2')):
        _record(rec, 'admit_wait', t + 0.002, at, request_id=rid,
                behind=i)
        _record(rec, 'serve_prefill_prep', at, at + 0.002, id=53 + 10 * i,
                parent=50)
        _record(rec, 'device_idle', at - 0.001, at + 0.002,
                cause='admission', after='serve_admit' if i == 0
                else 'serve_prefill_wait', exact=i)
        _record(rec, 'serve_prefill', at + 0.002, at + 0.012,
                id=54 + 10 * i, parent=50)
        _record(rec, 'serve_prefill_dispatch', at + 0.002, at + 0.003,
                id=55 + 10 * i, parent=54 + 10 * i)
        _record(rec, 'serve_prefill_wait', at + 0.003, at + 0.012,
                id=56 + 10 * i, parent=54 + 10 * i)
        _record(rec, 'serve_emit', at + 0.012, at + 0.013,
                id=57 + 10 * i, parent=50, first=1)
        at += 0.013
    _record(rec, 'serve_decode_prep', at, at + 0.004, id=80, parent=50)
    _record(rec, 'device_idle', at - 0.001, at + 0.004,
            cause='admission', after='serve_prefill_wait', exact=1)
    _record(rec, 'serve_decode', at + 0.004, at + 0.019, id=81,
            parent=50, ran_ahead=0, reason='prime')
    _record(rec, 'serve_decode_dispatch', at + 0.004, at + 0.005,
            id=82, parent=81)
    t = 106.0
    _record(rec, 'serve_tick', t, t + 0.020, id=90, parent=None)
    _record(rec, 'serve_expire', t, t + 0.001, id=91, parent=90)
    _record(rec, 'serve_admit', t + 0.001, t + 0.002, id=92, parent=90)
    _record(rec, 'serve_decode_prep', t + 0.002, t + 0.006, id=93,
            parent=90)
    _record(rec, 'device_idle', t + 0.004, t + 0.006, cause='steady',
            after='serve_decode_prep', exact=0)
    _record(rec, 'serve_decode', t + 0.006, t + 0.016, id=94,
            parent=90, ran_ahead=1)
    _record(rec, 'serve_decode_dispatch', t + 0.006, t + 0.009, id=95,
            parent=94)
    _record(rec, 'serve_decode_wait', t + 0.009, t + 0.016, id=96,
            parent=94)
    _record(rec, 'serve_emit', t + 0.016, t + 0.019, id=97, parent=90)
    # a settle: a wait and no dispatch; then a priming call's idle
    _record(rec, 'serve_decode', 107.0, 107.003, id=98, parent=None,
            reason='end')
    _record(rec, 'serve_decode_wait', 107.0, 107.003, id=99, parent=98)
    _record(rec, 'device_idle', 107.003, 107.009, cause='end',
            after='serve_decode_wait', exact=1)
    _record(rec, 'device_idle', 108.0, 108.001, cause='other',
            after='client', exact=0)


def test_program_span_share_reader_on_a_hand_made_run():
    """The one new reader (ISSUE 37): 100 x the summed durations of
    the records called ``span`` whose attributes match ``where``, over
    the traced window's seconds; 0.0 where the program writes the
    spans and nothing matches; ``None`` for an older program."""
    read = _reader('program_span_share')
    args = dict(span='device_idle', since='serve_decode_dispatch')
    window = (100.0, 110.0)
    assert read(_run(window), **args) is None      # no recorder
    rec = telemetry.enable()
    _record(rec, 'serve_tick', 101.0, 101.1, id=1, parent=None)
    _record(rec, 'serve_decode', 101.0, 101.05, id=2, parent=1)
    # a program older than the dispatch span: never a number
    assert read(_run(window), **args) is None
    _serving_records(rec)
    _record(rec, 'device_idle', 109.5, 110.5, cause='steady',
            after='client', exact=0)   # straddles
    run = _run(window)
    run.trace = types.SimpleNamespace(window_s=8.0)
    whole = read(run, split=['cause', 'after'], **args)
    # 3 + 3 + 5 (admission) + 2 (steady) + 6 (end) + 1 (other) ms
    assert whole == pytest.approx(100 * 0.020 / 8.0, abs=1e-4)
    by_cause, by_after = run.said[-2:]
    assert 'points by cause: admission 0.14, end 0.08' in by_cause
    assert 'other 0.01' in by_cause
    assert 'points by after: serve_prefill_wait 0.10' in by_after
    parts = {cause: read(run, where={'cause': cause}, **args)
             for cause in ('admission', 'end', 'steady', 'other')}
    assert parts['admission'] == pytest.approx(100 * 0.011 / 8.0,
                                               abs=1e-4)
    assert sum(parts.values()) == pytest.approx(whole, abs=1e-6)
    assert read(run, where={'cause': 'absent'}, **args) == 0.0
    assert read(run, span='absent', since='serve_decode_dispatch') \
        == 0.0
    # no trace: over the records' own extent (101.0 .. 108.001)
    untraced = _run(window)
    assert read(untraced, **args) == pytest.approx(
        100 * 0.020 / 7.001, abs=1e-3)


ISSUE_37_METRICS = {
    # 50 ms less 47 and 20 ms less 19 uncovered
    'tick_uncovered_ms': 2.0,
    'decode_wait_ms': 5.0,                  # 7 and 3
    'decode_dispatch_ms': 2.0,              # 1 and 3
    'device_starved_share': 100 * 0.020 / 8.0,
    'device_starved_share.admission': 100 * 0.011 / 8.0,
    'device_starved_share.steady': 100 * 0.002 / 8.0,
    'admit_wait_p75_ms': 9.75,              # 0 and 13
    'admit_wait_p90_ms': 11.7,
    'admits_per_admit_tick': 2.0,           # the one tick that has it
}


@pytest.mark.parametrize('name', sorted(ISSUE_37_METRICS))
def test_issue_37_metric_files_on_a_hand_made_run(name):
    """Each of the nine metric files, with the reader and arguments it
    commits: its value on the hand-made run, and nothing (never a
    number from elsewhere) from a program that lacks its spans."""
    import json
    with open(os.path.join(ROOT, 'chipbench', 'layer_metrics',
                           name + '.json')) as f:
        metric = json.load(f)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        entry, = [m for m in json.load(f)['per_layer']
                  if m['name'] == name]
    assert entry['unit'] == metric['unit']
    assert entry['layer'] == metric['layer']
    assert entry['moves'] == metric['moves']
    read = _reader(metric['reader'])
    window = (100.0, 110.0)
    rec = telemetry.enable()
    # the parent of ISSUE 37: a tick, its old children, the old stages
    _record(rec, 'serve_tick', 101.0, 101.1, id=1, parent=None)
    _record(rec, 'serve_decode', 101.0, 101.05, id=2, parent=1)
    _record(rec, 'queue_wait', 101.0, 101.001, request_id='r0')
    older = _run(window)
    older.trace = types.SimpleNamespace(window_s=8.0)
    value = read(older, **metric['args'])
    if name == 'tick_uncovered_ms':
        # its span is as old as the tick: what the old tick left
        # uncovered, which is what the metric is the proof against
        assert value == _ms(50.0)
    else:
        assert value is None
    telemetry.disable()
    rec = telemetry.enable()
    _serving_records(rec)
    run = _run(window)
    run.trace = types.SimpleNamespace(window_s=8.0)
    run.spec = types.SimpleNamespace(cfg={})
    assert read(run, **metric['args']) == pytest.approx(
        ISSUE_37_METRICS[name], abs=0.01)


def test_trainer_span_metrics_on_a_hand_made_run():
    """``input_wait_ms`` and ``train_update_self_ms``, with the
    arguments their committed files give the reader."""
    import json
    read = _reader('program_span')
    args = {}
    for name in ('input_wait_ms', 'train_update_self_ms'):
        with open(os.path.join(ROOT, 'chipbench', 'layer_metrics',
                               name + '.json')) as f:
            args[name] = json.load(f)['args']
    rec = telemetry.enable()
    for i, (wait, sync) in enumerate(((0.100, 0.150), (0.120, 0.130))):
        t, up = 101.0 + i, 10 + i
        _record(rec, 'train_update', t, t + 0.300, id=up, parent=None)
        _record(rec, 'input_wait', t, t + wait, id=20 + i, parent=up)
        _record(rec, 'host_batch_prep', t + wait, t + wait + 0.010,
                id=30 + i, parent=up)
        _record(rec, 'h2d', t + wait + 0.010, t + wait + 0.015,
                id=40 + i, parent=up)
        _record(rec, 'jitted_step', t + wait + 0.015,
                t + wait + 0.020, id=50 + i, parent=up)
        _record(rec, 'metrics_sync', t + 0.300 - sync, t + 0.300,
                id=60 + i, parent=up)
    # a producer thread's spans have no parent: not the update's
    _record(rec, 'host_batch_prep', 101.0, 101.2, id=70, parent=None,
            thread=9)
    run = _run((100.0, 110.0))
    assert read(run, **args['input_wait_ms']) == _ms(110.0)
    assert read(run, **args['train_update_self_ms']) == _ms(30.0)
    assert 'self 30.000' in run.said[-1]


def test_compile_log_reader_on_a_hand_made_run(monkeypatch):
    read = _reader('compile_log')
    log = [(99.0, 'backend_compile', 1.0),
           (101.0, 'backend_compile', 2.5),
           (111.0, 'backend_compile', 0.3)]
    monkeypatch.setattr(telemetry, 'compile_log', log)
    run = _run((100.0, 110.0))
    assert read(run) == 1
    assert 'compiled in the window: 2.500 s' in run.said[0]
    assert read(_run((120.0, 130.0))) == 0
    assert read(_run(None)) is None
    monkeypatch.delattr(telemetry, 'compile_log')
    assert read(run) is None    # a program that keeps no such log
