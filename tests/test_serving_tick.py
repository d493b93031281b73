"""The generation engine's tick accounting, telemetry and request
tracing (out of ``tests/test_serving.py``, a file of its own so that it
is a unit of ``--dist loadfile``).
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu import serving
from chainermn_tpu.serving import (InferenceEngine, OverloadError,
                                   RequestQueue)
from conftest import mlp_setup as _mlp_setup, tiny_lm as _tiny_lm


class TestTickAccounting:
    """ISSUE 37: the serving tick accounts for itself.  The children
    of ``serve_tick`` tile it, a decode call's dispatch and the wait
    for its vector are spans of their own, every launch says whether
    it found the device starved (``device_idle``), a call that did not
    go out ahead says why, and a first token says what it waited
    behind (``admit_wait``).  All of it only with a recorder live."""

    PS = 8
    CHILDREN = {'serve_expire', 'serve_admit', 'serve_prefill_prep',
                'serve_prefill', 'serve_emit', 'serve_decode_prep',
                'serve_decode'}
    WORK = (([1, 2, 3], 6), ([4, 5], 4), ([6], 5), ([7, 8, 9, 10], 7),
            ([11], 3), ([12, 13], 9))

    @pytest.fixture(autouse=True)
    def _telemetry_off(self):
        from chainermn_tpu import telemetry
        telemetry.disable()
        yield
        telemetry.disable()

    def _engine(self, mode):
        kw = dict(n_slots=4, max_prompt_len=8, max_len=32)
        if mode != 'slots':
            kw.update(paged=True, page_size=self.PS)
        if mode == 'spec':
            draft, dparams = _tiny_lm(n_layers=1)
            kw.update(draft_model=draft, draft_params=dparams)
        eng = serving.GenerationEngine(*_tiny_lm(n_layers=2), **kw)
        eng.warmup()
        return eng, serving.GenerationQueue(
            max_prompt_len=8,
            page_size=self.PS if eng.paged else None)

    def _serve(self, eng, q, work=WORK, late=2):
        """``work`` through the engine: all but the last ``late``
        requests submitted before the first tick (so several are
        admitted in ONE tick), the rest a few ticks in."""
        work = list(work)
        reqs = [q.submit(p, n) for p, n in work[:len(work) - late]]
        for tick in range(400):
            if tick in (3, 5) and len(reqs) < len(work):
                reqs.append(q.submit(*work[len(reqs)]))
            eng.step(q)
            if len(reqs) == len(work) and all(r.done() for r in reqs):
                break
        return [[int(t) for t in r.result(timeout=0)] for r in reqs]

    def _recorded(self, mode, **kw):
        from chainermn_tpu import telemetry
        eng, q = self._engine(mode)
        rec = telemetry.enable()
        out = self._serve(eng, q, **kw)
        eng.step(q)     # one idle tick more
        spans = [r for r in rec.events if r.get('type') == 'span']
        return eng, out, spans

    @staticmethod
    def _named(spans, name):
        return [r for r in spans if r['name'] == name]

    @pytest.mark.parametrize('mode', ['paged', 'spec'])
    def test_the_ticks_children_bear_the_names_and_do_not_overlap(
            self, mode):
        _, _, spans = self._recorded(mode)
        names = set(self.CHILDREN)
        if mode == 'spec':
            names |= {'serve_draft', 'serve_verify'}
        ticks = {r['id']: [] for r in self._named(spans, 'serve_tick')}
        assert len(ticks) > 8
        for r in spans:
            if r.get('parent') in ticks:
                assert r['name'] in names, r['name']
                ticks[r['parent']].append(r)
        by_id = {r['id']: r for r in spans if 'id' in r}
        seen = set()
        for tick, children in ticks.items():
            children.sort(key=lambda r: r['t0'])
            assert children[0]['name'] == 'serve_expire'
            assert children[1]['name'] == 'serve_admit'
            for a, b in zip(children, children[1:]):
                assert a['t1'] <= b['t0'], (a['name'], b['name'])
            assert by_id[tick]['t0'] <= children[0]['t0']
            assert children[-1]['t1'] <= by_id[tick]['t1']
            seen |= {r['name'] for r in children}
        if mode == 'spec':      # its decode tick is draft and verify
            names -= {'serve_decode_prep', 'serve_decode'}
        assert seen == names

    def test_first_token_emit_is_told_apart_by_its_attribute(self):
        eng, _, spans = self._recorded('paged')
        emits = self._named(spans, 'serve_emit')
        first = [r for r in emits if r.get('first') == 1]
        assert len(first) == eng.prefills == len(self.WORK)
        assert len(emits) - len(first) == eng.decode_steps
        assert all('active_slots' not in r for r in emits)
        # a sequence's three spans, in order, in one tick
        for r in first:
            prep, = [p for p in self._named(spans, 'serve_prefill_prep')
                     if p['parent'] == r['parent']
                     and p['slot'] == r['slot']]
            call, = [p for p in self._named(spans, 'serve_prefill')
                     if p['parent'] == r['parent']
                     and p['slot'] == r['slot']]
            assert prep['t1'] <= call['t0'] <= call['t1'] <= r['t0']

    @pytest.mark.parametrize('mode', ['paged', 'slots'])
    def test_the_wait_is_split_from_the_dispatch(self, mode):
        _, _, spans = self._recorded(mode)
        kids = {}
        for r in spans:
            if r['name'].startswith(('serve_decode_', 'serve_prefill_')) \
                    and r['name'] != 'serve_prefill_prep':
                kids.setdefault(r['parent'], []).append(r['name'])
        decodes = self._named(spans, 'serve_decode')
        assert {r.get('reason') for r in decodes} >= {None, 'prime',
                                                      'end'}
        for r in decodes:
            mine = sorted(kids.get(r['id'], []))
            if r.get('ran_ahead') == 1:
                assert mine == ['serve_decode_dispatch',
                                'serve_decode_wait']
            elif r['reason'] == 'prime':   # a dispatch and no wait
                assert mine == ['serve_decode_dispatch']
            else:                          # a settle: the reverse
                assert mine == ['serve_decode_wait']
        for r in self._named(spans, 'serve_prefill'):
            assert kids[r['id']] == ['serve_prefill_dispatch',
                                     'serve_prefill_wait']

    def test_prep_spans_say_what_they_allocated_and_evicted(self):
        """ISSUE 40: ``serve_prefill_prep`` / ``serve_decode_prep``
        carry ``pages`` and ``evicted`` exactly when pages were
        allocated / index references dropped under them, and the
        ``prefix_evictions`` gauge is their sum."""
        from chainermn_tpu import telemetry
        eng = serving.GenerationEngine(
            *_tiny_lm(n_layers=2), n_slots=2, max_prompt_len=16,
            max_len=32, paged=True, page_size=self.PS)
        eng.warmup()
        q = serving.GenerationQueue(max_prompt_len=16,
                                    page_size=self.PS)
        rec = telemetry.enable()
        # the engine's own calls, stamped on the recorder's clock
        allocated, evicted = [], []
        alloc, evict = eng._alloc_page, eng._prefix_index.evict

        def stamped_alloc():
            page = alloc()
            assert page is not None
            allocated.append(rec.now())
            return page

        def stamped_evict(n_needed=1):
            dropped = evict(n_needed)
            evicted.extend([rec.now()] * dropped)
            return dropped

        eng._alloc_page = stamped_alloc
        eng._prefix_index.evict = stamped_evict
        rng = np.random.RandomState(3)
        work = [(rng.randint(1, 32, size=n).tolist(), out)
                for n, out in ((9, 10), (16, 12), (3, 4), (12, 14),
                               (8, 9), (15, 3), (10, 12), (16, 16))]
        self._serve(eng, q, work=work)
        eng.step(q)     # one idle tick more: the gauges' last word
        spans = [r for r in rec.events if r.get('type') == 'span']
        preps = (self._named(spans, 'serve_prefill_prep')
                 + self._named(spans, 'serve_decode_prep'))
        for r in preps:
            under = [sum(r['t0'] <= t <= r['t1'] for t in stamps)
                     for stamps in (allocated, evicted)]
            assert [r.get('pages', 0), r.get('evicted', 0)] == under
            assert r.get('pages') != 0 and r.get('evicted') != 0
        # nothing allocates or evicts outside the two spans here (no
        # shared prefix: no copy-on-write page at admission)
        assert sum(r.get('pages', 0) for r in preps) \
            == len(allocated) == eng.pages_allocated
        assert sum(r.get('evicted', 0) for r in preps) \
            == len(evicted) == eng.stats()['prefix_evictions'] \
            == rec.registry.gauge('prefix_evictions').value
        first = self._named(spans, 'serve_prefill_prep')
        assert [r['pages'] for r in first] \
            == [-(-len(prompt) // self.PS) for prompt, _ in work]
        for name in ('serve_prefill_prep', 'serve_decode_prep'):
            mine = self._named(spans, name)
            assert any('evicted' in r for r in mine), name
            assert any('evicted' not in r for r in mine), name
        assert any('pages' not in r
                   for r in self._named(spans, 'serve_decode_prep'))
        # an engine without an index: pages, and never ``evicted``
        telemetry.disable()
        eng = serving.GenerationEngine(
            *_tiny_lm(n_layers=2), n_slots=2, max_prompt_len=16,
            max_len=32, paged=True, page_size=self.PS,
            prefix_sharing=False)
        eng.warmup()
        rec = telemetry.enable()
        self._serve(eng, q, work=work)
        spans = [r for r in rec.events if r.get('type') == 'span']
        assert sum(r.get('pages', 0) for r in spans) \
            == eng.pages_allocated > 0
        assert not any('evicted' in r for r in spans)
        assert 'prefix_evictions' not in rec.registry.snapshot()

    @pytest.mark.parametrize('mode', ['paged', 'slots', 'spec'])
    def test_a_call_that_did_not_go_out_ahead_says_why(self, mode):
        from chainermn_tpu.serving.generate import SETTLE_REASONS
        eng, _, spans = self._recorded(mode)
        decodes = self._named(spans, 'serve_decode')
        for r in decodes:
            assert ('reason' in r) == (r.get('ran_ahead') != 1)
            assert r.get('reason', 'end') in SETTLE_REASONS
        settles = eng.stats()['settles']
        assert tuple(settles) == SETTLE_REASONS
        # six always-on counts: the priming calls are the calls that
        # did not go out ahead, the rest settles without a dispatch
        assert settles['prime'] == \
            eng.decode_calls - eng.decode_calls_ahead
        for reason in SETTLE_REASONS:
            assert settles[reason] == sum(
                1 for r in decodes if r.get('reason') == reason)
        assert sum(settles.values()) == len(
            [r for r in decodes if 'reason' in r])
        if mode == 'spec':
            assert not decodes and eng.verify_steps > 0
        else:
            assert settles['prime'] > 0 and settles['end'] > 0

    def test_a_change_of_bucket_and_a_swap_are_reasons_too(self):
        from chainermn_tpu import telemetry
        eng, q = self._engine('paged')
        rec = telemetry.enable()
        # two rows of unequal length: when the short one ends by an
        # EOS the host could not foresee, the next call's bucket is
        # another while a call is in flight
        self._serve(eng, q, work=(([1, 2, 3], 12), ([4, 5], 3),
                                  ([6], 2)), late=0)
        long_req = q.submit([1, 2], 6)
        for _ in range(3):
            eng.step(q)
        assert eng._inflight is not None
        for slot in list(eng._slots.values()):     # the rows go
            eng._release_pages(slot.pages, slot.ring, slot.state_row)
        eng._free += list(eng._slots)
        eng._slots.clear()
        eng.step(q)                                 # a drained table
        long_req.set_result([])
        eng.swap_params(eng.params, validate=False)
        reasons = [r['reason'] for r in rec.events
                   if r.get('name') == 'serve_decode' and 'reason' in r]
        assert 'drained' in reasons
        assert eng.stats()['settles']['drained'] == 1
        assert eng.stats()['settles']['swap'] == 0   # nothing in flight

    @pytest.mark.parametrize('mode', ['paged', 'slots'])
    def test_a_first_token_says_what_it_waited_behind(self, mode):
        eng, _, spans = self._recorded(mode)
        stages = ('queue_wait', 'admit_wait', 'bucket_pack', 'prefill')
        by_request = {}
        for r in spans:
            if r['name'] in stages:
                by_request.setdefault(r['request_id'], {})[
                    r['name']] = r
        assert len(by_request) == len(self.WORK)
        for found in by_request.values():
            chain = [found[name] for name in stages]
            for a, b in zip(chain, chain[1:]):
                assert a['t1'] == b['t0']      # they telescope
            total = sum(r['t1'] - r['t0'] for r in chain)
            assert total == pytest.approx(
                chain[-1]['t1'] - chain[0]['t0'], abs=1e-7)
        # four were waiting at the first tick: admitted together,
        # served one after the other
        behind = sorted(r['behind']
                        for r in self._named(spans, 'admit_wait'))
        assert behind == [0, 0, 0, 1, 2, 3]
        waits = {r['behind']: r for r in self._named(spans, 'admit_wait')
                 if r['t0'] <= min(x['t0'] for x in
                                   self._named(spans, 'admit_wait'))
                 + 1e-3 or r['behind']}
        calls = sorted(self._named(spans, 'serve_prefill'),
                       key=lambda r: r['t0'])
        for k in (1, 2, 3):
            # the k-th waited at least the k prefill calls before it
            assert waits[k]['t1'] >= calls[k - 1]['t1']
            assert waits[k]['t1'] - waits[k]['t0'] >= sum(
                c['t1'] - c['t0'] for c in calls[:k])

    def test_admitting_ticks_say_how_many_they_admitted(self):
        eng, _, spans = self._recorded('paged')
        ticks = self._named(spans, 'serve_tick')
        admitted = [r['admitted'] for r in ticks if 'admitted' in r]
        assert admitted == [4, 1, 1]
        assert sum(admitted) == eng.stats()['admissions'] == 6
        assert all(r['admitted'] >= 1 for r in ticks
                   if 'admitted' in r)
        assert len(ticks) > len(admitted)

    @pytest.mark.parametrize('mode', ['paged', 'slots', 'spec'])
    def test_every_launch_says_whether_it_found_the_device_idle(
            self, mode):
        _, _, spans = self._recorded(mode)
        idle = self._named(spans, 'device_idle')
        assert idle
        launches = {r['t0']: r for r in spans
                    if r['name'].endswith('_dispatch')
                    or r['name'] in ('serve_draft', 'serve_verify')}
        blocked = [r for r in spans if r['name'] in (
            'serve_decode_wait', 'serve_prefill_wait')
            and r['t1'] - r['t0'] > 50e-6]
        for r in idle:
            assert r['kind'] == 'serve' and 'id' not in r
            assert r['t0'] <= r['t1']
            assert r['cause'] in ('admission', 'end', 'steady', 'other')
            assert r['exact'] in (0, 1)
            # it ends where a launch begins; a prefill is an admission
            launch = launches[r['t1']]
            if launch['name'] == 'serve_prefill_dispatch':
                assert r['cause'] == 'admission'
            for w in blocked:       # never inside a wait that blocked
                assert r['t1'] <= w['t0'] or w['t1'] <= r['t0']
            if r['exact']:
                assert r['after'].endswith('_wait')
                assert any(w['t1'] == r['t0'] for w in blocked)
        assert len({r['t1'] for r in idle}) == len(idle)
        if mode == 'spec':
            assert {r['cause'] for r in idle} == {'other', 'admission'}
        else:
            # a prefill, and the priming call after one
            assert {(r['cause'], launches[r['t1']]['name'])
                    for r in idle} >= {
                ('admission', 'serve_prefill_dispatch'),
                ('admission', 'serve_decode_dispatch')}
            # the CPU's calls end at once: every one is seen idle
            assert all(r['after'] != 'client' or r['cause'] != 'other'
                       for r in idle)

    def test_an_admission_beside_a_call_in_flight_is_admissions(self):
        """The first decode call after a prefill is booked
        ``admission`` whatever stands between them: it went out ahead
        of a call in flight (not ``steady``), behind a settle for a
        change of bucket, or a tick later behind a settle for a
        foreseen end (not ``end``)."""
        from chainermn_tpu import telemetry
        eng, q = self._engine('paged')
        rec = telemetry.enable()
        reqs = [q.submit([1, 2, 3], 16), q.submit([4, 5], 16)]
        late = {4: ([6], 5),        # two rows -> three: another bucket
                8: ([7, 8], 9),     # as the third request ends
                10: ([9], 3)}       # a call in flight, the same bucket
        for tick in range(400):
            if tick in late:
                assert eng._inflight is not None
                reqs.append(q.submit(*late[tick]))
            eng.step(q)
            if len(reqs) == 5 and all(r.done() for r in reqs):
                break
        spans = [r for r in rec.events if r.get('type') == 'span']
        by_id = {r['id']: r for r in spans if 'id' in r}
        decodes = self._named(spans, 'serve_decode')
        launches = self._named(spans, 'serve_decode_dispatch')
        idle = {r['t1']: r for r in self._named(spans, 'device_idle')}
        shapes = set()
        for call in self._named(spans, 'serve_prefill')[2:]:
            first = min((r for r in launches if r['t0'] > call['t1']),
                        key=lambda r: r['t0'])
            between = [r['reason'] for r in decodes
                       if call['t1'] < r['t0'] and r['t1'] < first['t0']]
            shapes.add((by_id[first['parent']]['ran_ahead'],
                        tuple(between)))
            # the CPU's calls end at once: the prefill's read-back saw
            # the device idle, so this launch has its record
            assert idle[first['t0']]['cause'] == 'admission'
        assert shapes == {(0, ('bucket',)), (0, ('end',)), (1, ())}
        # (``steady`` where a call ahead found the CPU done already)
        assert {'admission', 'end'} <= {
            r['cause'] for r in idle.values()} <= {
            'admission', 'end', 'steady'}
        # ... and a launch without a prefill before it is not
        for r in launches:
            prior = [c for c in self._named(spans, 'serve_prefill')
                     if c['t1'] < r['t0']]
            since = [d for d in launches
                     if prior and prior[-1]['t1'] < d['t0'] < r['t0']]
            if since and r['t0'] in idle:
                assert idle[r['t0']]['cause'] != 'admission'

    def test_the_tick_gauges_are_looked_up_once(self, monkeypatch):
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry import recorder as rec_mod
        looked_up = []
        real = rec_mod.Registry.gauge

        def gauge(self, name, help=''):
            looked_up.append(name)
            return real(self, name, help)

        monkeypatch.setattr(rec_mod.Registry, 'gauge', gauge)
        eng, q = self._engine('paged')
        rec = telemetry.enable()
        self._serve(eng, q)
        assert sorted(looked_up) == [
            'active_slots', 'prefix_evictions', 'serve_decode_backlog',
            'serve_kv_pages_free', 'serve_kv_pages_in_use',
            'serve_prefill_backlog', 'serve_queue_depth']
        snap = rec.registry.snapshot()
        assert snap['serve_queue_depth']['value'] == 0.0
        assert snap['active_slots']['value'] == 1.0
        assert snap['serve_kv_pages_in_use']['value'] is not None

    @pytest.mark.parametrize('mode', ['paged', 'slots', 'spec'])
    def test_with_telemetry_off_nothing_of_it_runs(self, mode,
                                                   monkeypatch):
        """The same tokens, and neither ``is_ready()`` nor a record:
        the account exists only where a recorder is live."""
        from chainermn_tpu import telemetry
        _, traced, _ = self._recorded(mode)
        telemetry.disable()
        eng, q = self._engine(mode)

        def boom(self):
            raise AssertionError('is_ready() with telemetry off')

        monkeypatch.setattr(type(jnp.zeros(1)), 'is_ready', boom)
        assert self._serve(eng, q) == traced
        assert telemetry.active() is None
        assert eng._last_call is None and eng._idle_since is None
        assert eng._gauges is None
        # ... and the always-on counts count all the same
        assert eng.stats()['admissions'] == len(self.WORK)
        if mode != 'spec':
            assert eng.stats()['settles']['prime'] > 0

    def test_the_report_knows_the_ticks_anatomy(self, tmp_path):
        """``telemetry report``: the tick's phases in order, the calls'
        dispatch and wait, the reasons, the idle account by cause and
        by phase; ``--request`` decomposes through ``admit_wait``."""
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry import report
        eng, q = self._engine('paged')
        rec = telemetry.enable(str(tmp_path))
        self._serve(eng, q)
        rec.flush()
        built = report.build_report(str(tmp_path))
        ticks = built['serve_ticks']
        assert ticks['ticks'] > 8
        assert set(ticks['phases']) == self.CHILDREN | {
            'serve_emit (first)', 'serve_prefill_dispatch',
            'serve_prefill_wait', 'serve_decode_dispatch',
            'serve_decode_wait'}
        assert ticks['phases']['serve_emit (first)']['count'] == 6
        assert 0 <= ticks['uncovered_mean_ms'] < ticks['tick_mean_ms']
        assert ticks['decode_reasons']['prime'] == \
            eng.stats()['settles']['prime']
        assert ticks['admits_per_admit_tick'] == 2.0     # 4, 1, 1
        idle = ticks['device_idle']
        assert idle['records'] > 0
        assert sum(idle['by_cause_ms'].values()) == pytest.approx(
            idle['total_ms'], abs=0.01)
        assert 'admission' in idle['by_cause_ms']
        text = report.render_text(built)
        assert 'scheduler ticks:' in text
        assert 'serve_prefill_prep' in text and 'by after:' in text
        worst = built['requests']['worst']
        assert 'admit_wait' in worst['stage_ms']
        assert 'admit_wait' in text
        assert report.REQUEST_STAGES.index('admit_wait') == 1
        trace = report.request_traces(rec.events)[worst['request_id']]
        assert [s['name'] for s in trace['stages']][:4] == [
            'queue_wait', 'admit_wait', 'bucket_pack', 'prefill']
        assert 'admit_wait' in report.render_request_text(trace)
        assert report.serve_tick_summary([]) is None

    def test_a_recorder_that_goes_away_leaves_no_stale_probe(self):
        """Calls launched while no recorder is live go unseen, so the
        engine forgets the last one it saw: a recorder that comes back
        does not take a finished, long-gone call for an idle device."""
        from chainermn_tpu import telemetry
        eng, q = self._engine('paged')
        telemetry.enable()
        self._serve(eng, q, work=self.WORK[:2], late=0)
        assert eng._last_call is not None
        telemetry.disable()
        self._serve(eng, q, work=self.WORK[2:4], late=0)
        assert eng._last_call is None and eng._idle_since is None
        rec = telemetry.enable()
        req = q.submit([1, 2, 3], 4)
        eng.step(q)
        first, = [r for r in rec.events
                  if r.get('name') == 'serve_prefill_dispatch']
        assert not [r for r in rec.events
                    if r.get('name') == 'device_idle'
                    and r['t1'] <= first['t0']]
        while not req.done():
            eng.step(q)


class TestGenerateTelemetry:
    def _generate_capture(self, tmp_path):
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4)
        eng.warmup()
        q = serving.GenerationQueue(max_prompt_len=4)
        cap = str(tmp_path / 'cap')
        serving.open_loop_generate(
            eng, q, rate=400.0, n_requests=6, seed=5,
            prompt_len_range=(1, 4), max_new_tokens=3,
            capture_dir=cap)
        return cap

    def test_serve_summary_generate_block(self, tmp_path):
        from chainermn_tpu.telemetry import diagnosis
        cap = self._generate_capture(tmp_path)
        diag = diagnosis.quick_verdict(cap)
        assert diag is not None
        gen = diag['serve']['generate']
        assert gen['tokens'] == 18           # 6 requests x 3 tokens
        assert gen['ttft_ms']['p50'] is not None
        assert gen['intertoken_ms']['p50'] is not None
        assert gen['tokens_per_s'] is not None
        assert gen['decode_steps'] > 0
        assert gen['active_slots'] is not None  # the per-step gauge
        assert any('decode capture' in s
                   for s in diag['verdict']['summary'])

    def test_metrics_only_decode_window_not_empty(self, tmp_path):
        """The regression pin: a decode capture holding ONLY metrics
        still parses as a serving capture with a generate block."""
        from chainermn_tpu.telemetry import diagnosis
        cap = self._generate_capture(tmp_path)
        only = tmp_path / 'metrics_only'
        only.mkdir()
        data = json.load(open(os.path.join(cap, 'metrics-rank0.json')))
        with open(only / 'metrics-rank0.json', 'w') as f:
            json.dump(data, f)
        diag = diagnosis.quick_verdict(str(only))
        assert diag is not None
        assert diag['serve']['generate']['tokens'] == 18

    def test_serve_decode_spans_feed_anomaly_scan(self):
        from chainermn_tpu.telemetry import diagnosis
        spans = [
            {'type': 'span', 'name': 'serve_decode', 'kind': 'serve',
             't0': i * 0.01, 't1': i * 0.01 + (0.5 if i == 7
                                               else 0.002),
             'iteration': i, 'rank': 0}
            for i in range(12)]
        rows = diagnosis.step_anomalies(spans)
        assert rows and rows[0]['phase'] == 'serve_decode'
        assert rows[0]['iteration'] == 7

    def test_serve_phases_vocabulary_extended(self):
        from chainermn_tpu.telemetry.report import SERVE_PHASES
        assert 'serve_prefill' in SERVE_PHASES
        assert 'serve_decode' in SERVE_PHASES


# ---------------------------------------------------------------------
# per-request distributed tracing (ISSUE 12 tentpole)

class TestRequestTracing:
    def test_generate_stage_budgets_sum_to_e2e(self, tmp_path):
        """THE ISSUE 12 acceptance pin: from a recorded generate
        capture, the report decomposes the worst request's latency
        into queue/pack/prefill/decode stage budgets that sum to its
        end-to-end latency (+-1 ms), with every stage present."""
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry import report as trep
        cap = str(tmp_path / 'cap')
        rec = telemetry.enable(cap)
        try:
            model, params = _tiny_lm()
            eng = serving.GenerationEngine(model, params, n_slots=2,
                                           max_prompt_len=4)
            eng.warmup()
            q = serving.GenerationQueue(max_prompt_len=4)
            a = q.submit([1, 2], 6)
            b = q.submit([3], 3)
            for _ in range(24):
                if a.done() and b.done():
                    break
                eng.step(q)
            assert len(a.result()) == 6 and len(b.result()) == 3
            rec.flush()
        finally:
            telemetry.disable()
        rep = trep.build_report(cap)
        reqs = rep['requests']
        assert reqs['count'] == 2 and reqs['completed'] == 2
        worst = reqs['worst']
        assert {'queue_wait', 'bucket_pack', 'prefill',
                'decode'} <= set(worst['stage_ms'])
        assert abs(worst['stage_sum_ms'] - worst['e2e_ms']) <= 1.0
        # every traced request tiles, not just the worst
        traces = trep.request_traces(
            trep.load_rank_logs(cap)[1] + trep.load_rank_logs(cap)[2])
        for tr in traces.values():
            assert abs(sum(tr['stage_ms'].values())
                       - tr['e2e_ms']) <= 1.0
            assert tr['outcome'] == 'complete'
        # the CLI reconstructs a single request's timeline
        from chainermn_tpu.telemetry.__main__ import main
        assert main(['report', '--request', worst['request_id'],
                     cap]) == 0
        assert main(['report', '--request', 'rNOPE', cap]) == 1

    def test_request_ids_unique_and_monotonic(self):
        q = serving.GenerationQueue(max_prompt_len=4)
        ids = [q.submit([1], 2).request_id for _ in range(4)]
        nums = [int(i[1:]) for i in ids]
        assert len(set(ids)) == 4
        assert nums == sorted(nums)
        # the batch queue draws from the same process-wide counter
        rq = serving.RequestQueue(max_batch=4)
        r = rq.submit(np.zeros((1, 3), np.float32))
        assert int(r.request_id[1:]) > nums[-1]

    def test_shed_events_carry_forensics(self):
        """Satellite pin: queue_full, queued-deadline and
        mid-generation sheds each emit a `shed` event with
        request_id, reason and queue depth, and bump the per-reason
        counter serve_summary breaks down."""
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry.report import serve_summary
        rec = telemetry.enable()
        try:
            clock = [0.0]
            q = serving.GenerationQueue(max_prompt_len=4, max_queue=1,
                                        clock=lambda: clock[0])
            q.submit([1], 2, deadline=0.5)
            with pytest.raises(OverloadError):
                q.submit([2], 2)          # queue_full
            clock[0] = 1.0
            assert q.pop(4) == []         # deadline shed at pop
            sheds = [e for e in rec.events
                     if e.get('kind') == 'request'
                     and e.get('name') == 'shed']
            assert len(sheds) == 2
            by_reason = {e['reason']: e for e in sheds}
            assert by_reason['queue_full']['queue_depth'] == 1
            assert by_reason['queue_full']['request_id']
            assert by_reason['deadline']['waited_ms'] >= 500.0
            snap = {'rank': 0, 'metrics': rec.registry.snapshot()}
            serve = serve_summary(snap['metrics'])
            assert serve['shed_reasons'] == {'queue_full': 1.0,
                                             'deadline': 1.0}
            assert serve['shed'] == 2.0
        finally:
            telemetry.disable()

    def test_mid_generation_shed_names_request(self):
        from chainermn_tpu import telemetry
        rec = telemetry.enable()
        try:
            model, params = _tiny_lm()
            eng = serving.GenerationEngine(model, params, n_slots=1,
                                           max_prompt_len=4)
            eng.warmup()
            clock = [0.0]
            q = serving.GenerationQueue(max_prompt_len=4,
                                        clock=lambda: clock[0])
            doomed = q.submit([1], 100, deadline=5.0)
            eng.step(q, clock=lambda: clock[0])
            clock[0] = 10.0
            eng.step(q, clock=lambda: clock[0])
            assert doomed.done()
            sheds = [e for e in rec.events
                     if e.get('kind') == 'request'
                     and e.get('name') == 'shed']
            assert sheds and sheds[-1]['request_id'] \
                == doomed.request_id
            assert sheds[-1]['reason'] == 'deadline'
            assert sheds[-1]['tokens'] >= 1
        finally:
            telemetry.disable()

    def test_flight_dump_includes_request_table(self, tmp_path):
        """Satellite pin: a flight dump mid-generation names the
        in-flight requests (id, slot, stage, tokens emitted)."""
        from chainermn_tpu import telemetry
        cap = str(tmp_path / 'flight')
        rec = telemetry.enable(cap)
        try:
            model, params = _tiny_lm()
            eng = serving.GenerationEngine(model, params, n_slots=2,
                                           max_prompt_len=4)
            eng.warmup()
            q = serving.GenerationQueue(max_prompt_len=4)
            req = q.submit([1, 2], 50)
            eng.step(q)               # mid-generation
            assert not req.done()
            path = rec.dump_flight('test_crash')
            record = json.load(open(path))
            table = record['serve_requests']
            assert table['active'][0]['request_id'] == req.request_id
            assert table['active'][0]['stage'] == 'decode'
            assert table['active'][0]['tokens'] >= 1
            assert table['step_index'] >= 1
        finally:
            telemetry.disable()

    def test_queue_depth_sampled_each_tick(self):
        """Satellite pin: serve_queue_depth + the prefill/decode
        backlog split are gauged at every scheduler tick, and the
        serve_decode span carries queue_depth/n_slots attrs."""
        from chainermn_tpu import telemetry
        rec = telemetry.enable()
        try:
            model, params = _tiny_lm()
            eng = serving.GenerationEngine(model, params, n_slots=1,
                                           max_prompt_len=4)
            eng.warmup()
            q = serving.GenerationQueue(max_prompt_len=4)
            q.submit([1], 3)
            q.submit([2], 3)          # waits: only one slot
            eng.step(q)
            snap = rec.registry.snapshot()
            # sampled at tick START (pressure onset): both requests
            # were waiting when the first tick began
            assert snap['serve_queue_depth']['value'] == 2.0
            eng.step(q)
            snap = rec.registry.snapshot()
            assert snap['serve_queue_depth']['value'] == 1.0
            assert snap['serve_prefill_backlog']['value'] == 1.0
            assert snap['serve_decode_backlog']['value'] is not None
            decode_spans = [e for e in rec.events
                            if e.get('name') == 'serve_decode']
            assert decode_spans
            assert decode_spans[-1]['n_slots'] == 1
            assert 'queue_depth' in decode_spans[-1]
        finally:
            telemetry.disable()

    def test_batch_path_stages_tile_e2e(self):
        """The forward-only engine's requests trace too:
        queue_wait -> bucket_pack -> execute -> complete."""
        from chainermn_tpu import telemetry
        from chainermn_tpu.telemetry.report import request_traces
        rec = telemetry.enable()
        try:
            model, params, apply_fn, example = _mlp_setup()
            eng = InferenceEngine(apply_fn, params, example,
                                  max_batch=4)
            eng.warmup()
            q = RequestQueue(max_batch=4, max_wait=0.001)
            r1 = q.submit(np.zeros((2, 48), np.float32))
            r2 = q.submit(np.zeros((1, 48), np.float32))
            for pb in q.take(timeout=1.0):
                eng.serve_packed(pb)
            assert r1.done() and r2.done()
            traces = request_traces(list(rec.events))
            assert len(traces) == 2
            for tr in traces.values():
                assert {'queue_wait', 'bucket_pack',
                        'execute'} <= set(tr['stage_ms'])
                assert tr['outcome'] == 'complete'
                assert abs(sum(tr['stage_ms'].values())
                           - tr['e2e_ms']) <= 1.0
        finally:
            telemetry.disable()

    def test_open_loop_reports_worst_request_and_slo(self):
        from chainermn_tpu.telemetry.slo import SLOMonitor, \
            default_slos
        model, params = _tiny_lm()
        eng = serving.GenerationEngine(model, params, n_slots=2,
                                       max_prompt_len=4)
        eng.warmup()
        q = serving.GenerationQueue(max_prompt_len=4)
        mon = SLOMonitor(slos=default_slos(ttft_s=30.0,
                                           intertoken_s=30.0))
        rep = serving.open_loop_generate(
            eng, q, rate=300.0, n_requests=6, seed=6,
            prompt_len_range=(1, 4), max_new_tokens=3,
            slo_monitor=mon)
        assert rep['served'] == 6
        worst = rep['worst_request']
        assert worst['completed'] == 6
        assert abs(worst['worst']['stage_sum_ms']
                   - worst['worst']['e2e_ms']) <= 1.0
        assert rep['slo']['verdict']['overall'] in ('ok', 'warn',
                                                    'breach')
        assert mon.n_ingested > 0
