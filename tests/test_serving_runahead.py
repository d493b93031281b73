"""The generation engine's decode runs ahead of the host (out of
``tests/test_serving.py``, a file of its own so that it is a unit of
``--dist loadfile``).
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from chainermn_tpu import serving
from chainermn_tpu.serving import OverloadError
from chainermn_tpu.utils import chaos
from conftest import tiny_lm as _tiny_lm


# ---------------------------------------------------------------------
# the decode tick runs one call ahead of the host's reads (ISSUE 36)

class TestDecodeRunsAhead:
    """Call t+1 is dispatched with call t's tokens still on the
    device: the served tokens stay the oracle loop's token for token,
    a row that goes while its call is in flight gets no token and
    loses none it was owed, and a caller may assume after ``step()``
    what it always could."""

    PS = 8

    def _models(self):
        return _tiny_lm(n_layers=2)

    def _oracle(self, model, params, prompt, n_new, eos=None):
        toks = [int(t) for t in prompt]
        out = []
        for _ in range(n_new):
            logits = model.apply({'params': params},
                                 jnp.asarray([toks], jnp.int32))
            tok = int(jnp.argmax(logits[0, -1]))
            out.append(tok)
            toks.append(tok)
            if tok == eos:
                break
        return out

    def _engine(self, model, params, paged, **kw):
        base = dict(n_slots=4, max_prompt_len=8, max_len=32)
        if paged:
            base.update(paged=True, page_size=self.PS,
                        prefix_sharing=False)
        base.update(kw)
        eng = serving.GenerationEngine(model, params, **base)
        eng.warmup()
        return eng

    def _queue(self, eng, **kw):
        return serving.GenerationQueue(
            max_prompt_len=eng.max_prompt_len,
            page_size=eng.page_size if eng.paged else None, **kw)

    @staticmethod
    def _count_drops(eng):
        """Tokens of a read call that no request got: rows whose slot
        went between the call's dispatch and its read."""
        dropped = []
        emit = eng._emit

        def counting(pend, toks, t0, clock):
            dropped.extend(
                sid for sid, slot in zip(pend.rows, pend.slots)
                if slot is not None and eng._slots.get(sid) is not slot)
            return emit(pend, toks, t0, clock)

        eng._emit = counting
        return dropped

    @staticmethod
    def _streamed(events):
        """An ``on_token`` that records ``(token, request was done)``
        and the cell the request goes into once it is submitted."""
        cell = {}

        def on_token(_rid, tokens):
            for tok in tokens:
                events.append((tok, cell['request'].done()))

        return on_token, cell

    def _all_back(self, eng):
        assert eng._inflight is None and not eng._slots
        assert sorted(eng._free) == list(range(eng.n_slots))
        if eng.paged:
            assert eng.pool.in_use() == 0

    @pytest.mark.parametrize('paged', [False, True],
                             ids=['slots', 'paged'])
    @pytest.mark.parametrize('eos', ['none', 'mid', 'late'])
    def test_tokens_are_the_oracle_loops(self, paged, eos):
        """Staggered lengths over 4 slots (decode edges 1 / 2 / 4):
        requests arrive while a call is in flight, so occupancy
        crosses an edge under it (a settle), rows shift as slots end
        and fill (``src`` is a gather), positions cross page
        boundaries at 8 and 16, and every request's tokens are the
        oracle loop's.  ``mid``: a token of the streams is the EOS, so
        rows end where the host could not foresee it; ``late``: one
        row in a steady batch hits it, with the next call already out
        -- that call's token for the row is dropped, exactly one."""
        model, params = self._models()
        rng = np.random.RandomState(3)
        shapes = [(3, 14), (6, 9), (2, 20), (7, 5), (5, 12), (4, 16)]
        prompts = [rng.randint(1, 32, size=n).tolist()
                   for n, _ in shapes]
        n_new = [m for _, m in shapes]
        arrive = {0: [0, 1], 3: [2], 6: [3, 4], 12: [5]}
        eos_id = None
        if eos == 'mid':
            # the sixth token of the first stream: it ends that row
            # there, and any other row where it comes first
            eos_id = self._oracle(model, params, prompts[0], 14)[5]
        elif eos == 'late':
            arrive = {0: [2]}
            free = self._oracle(model, params, prompts[2], 20)
            eos_id = free[5]
            assert eos_id not in free[:5]
        want = [self._oracle(model, params, p, m, eos_id)
                for p, m in zip(prompts, n_new)]
        eng = self._engine(model, params, paged, eos_id=eos_id)
        dropped = self._count_drops(eng)
        compiled = eng.compile_count
        q = self._queue(eng, max_queue=16)
        reqs = {}
        for step in range(200):
            for j in arrive.get(step, ()):
                reqs[j] = q.submit(prompts[j], n_new[j])
            eng.step(q)
            assert eng.decode_calls - eng.decode_steps == (
                eng._inflight is not None)
            if step > max(arrive) and all(
                    r.done() for r in reqs.values()):
                break
        for j, req in reqs.items():
            assert [int(t) for t in req.result(timeout=0)] == want[j]
        eng.step(q)      # a call left in flight by an EOS is read off
        self._all_back(eng)
        assert eng.compile_count == compiled
        st = eng.stats()
        assert st['decode_runahead_share'] > 0
        assert st['tokens_generated'] == sum(
            len(want[j]) for j in reqs)
        if eos == 'none':
            assert not dropped
        if eos == 'late':
            # five decode tokens were served and a sixth dropped
            assert len(dropped) == 1
            assert len(want[2]) == 6 and st['decode_steps'] == 6

    @pytest.mark.parametrize('how', ['deadline', 'dry_pool',
                                     'serve_cancel'])
    def test_a_row_that_goes_in_flight_gets_no_token_and_loses_none(
            self, how):
        """A deadline expiry, a dry pool's shed and the
        ``serve_cancel`` chaos site each take a slot whose row is in
        the call in flight: the dead request is streamed nothing
        after its error (its token of that call is dropped), what it
        got is a prefix of the oracle's, and the survivor's tokens are
        the oracle's, none lost."""
        model, params = self._models()
        prompts = ([11, 25, 26], [4, 25, 9])
        n_new = (24, 20)
        want = [self._oracle(model, params, p, m)
                for p, m in zip(prompts, n_new)]
        kw = {}
        if how == 'dry_pool':
            # scratch + 6 pages of 4: both rows hold three when the
            # first needs a fourth; alone, the survivor's 6 fit
            kw = dict(n_slots=2, page_size=4, n_pages=7, max_len=24)
        eng = self._engine(model, params, how == 'dry_pool', **kw)
        dropped = self._count_drops(eng)
        now = [0.0]

        def clock():
            return now[0]

        q = self._queue(eng, clock=clock)
        events = ([], [])
        reqs = []
        for i in range(2):
            on_token, cell = self._streamed(events[i])
            cell['request'] = q.submit(
                prompts[i], n_new[i], on_token=on_token,
                deadline=5.0 if (how, i) == ('deadline', 0) else None)
            reqs.append(cell['request'])
        victim, survivor = reqs
        if how == 'serve_cancel':
            chaos.install(chaos.FaultInjector('serve_cancel=@3'))
        in_flight_when_taken = None
        try:
            for step in range(80):
                if how == 'deadline' and step == 3:
                    now[0] = 10.0
                flying = eng._inflight
                eng.step(q, clock=clock)
                if victim.done() and in_flight_when_taken is None:
                    in_flight_when_taken = flying is not None and any(
                        slot is not None and slot.request is victim
                        for slot in flying.slots)
                if survivor.done():
                    break
        finally:
            chaos.uninstall()
        # the step that took the victim found its row in a call
        assert in_flight_when_taken
        with pytest.raises(OverloadError) as ei:
            victim.result(timeout=0)
        assert ei.value.reason == ('kv_pages' if how == 'dry_pool'
                                   else 'deadline')
        got = [tok for tok, _ in events[0]]
        assert 1 <= len(got) < n_new[0] and got == want[0][:len(got)]
        assert not any(done for _, done in events[0] + events[1])
        assert [tok for tok, _ in events[1]] == want[1]
        assert [int(t) for t in survivor.result(timeout=0)] == want[1]
        assert len(dropped) == 1         # the victim's, and no other
        self._all_back(eng)

    @pytest.mark.parametrize('case', [
        'one_call_in_flight', 'run_drains', 'replica_drains',
        'swap_after_drain', 'steady_batch_runs_ahead',
        'speculative_never_does'])
    def test_what_a_caller_may_assume(self, case):
        """After ``step()`` at most ONE call is in flight and a row is
        in ``_slots`` until its last token is emitted, so ``run()``
        and a fleet replica drain to empty and ``swap_params`` right
        after a drain settles what an EOS left on the device; a steady
        batch runs ahead, a speculative engine never does."""
        model, params = self._models()
        prompt, n_new = [20, 11], 20
        free = self._oracle(model, params, prompt, n_new)
        eos_id = free[5]              # first met as the sixth token
        want = free[:6]
        if case == 'speculative_never_does':
            draft, dparams = _tiny_lm(n_layers=1)
            eng = self._engine(model, params, False,
                               draft_model=draft, draft_params=dparams)
            q = self._queue(eng)
            req = q.submit(prompt, n_new)
            while not req.done():
                eng.step(q)
                assert eng._inflight is None
            assert [int(t) for t in req.result(timeout=0)] == free
            assert eng.stats()['decode_runahead_share'] == 0
            return
        eng = self._engine(model, params, True, eos_id=eos_id)
        if case in ('one_call_in_flight', 'steady_batch_runs_ahead'):
            q = self._queue(eng)
            reqs = [q.submit(prompt, n_new),
                    q.submit([4, 25, 9], 12), q.submit([15, 25], 7)]
            while not all(r.done() for r in reqs):
                eng.step(q)
                live = {s.request for s in eng._slots.values()}
                # a request leaves the slots only once it has its end
                assert all(r.done() or r in live for r in reqs)
                assert eng.decode_calls - eng.decode_steps == (
                    eng._inflight is not None)
            # ticks around an end (three requests) overlap nothing;
            # every other call went out ahead of its predecessor's read
            share = eng.stats()['decode_runahead_share']
            assert 0.5 < share < 1
            assert share == eng.decode_calls_ahead / eng.decode_calls
        elif case == 'run_drains':
            import threading
            q = self._queue(eng)
            req = q.submit(prompt, n_new)
            stop = threading.Event()
            stop.set()
            eng.run(q, stop=stop, idle_sleep=0.0)
            assert [int(t) for t in req.result(timeout=0)] == want
            assert eng.decode_calls == eng.decode_steps == 6
        elif case == 'replica_drains':
            from chainermn_tpu.serving import fleet
            replica = fleet.LocalReplica('r0', eng).start()
            try:
                req = replica.submit(prompt, n_new)
                assert [int(t) for t in req.result(timeout=60)] == want
                assert replica.drain(timeout=60)
                # the loop reads off what the EOS left in flight
                for _ in range(400):
                    if eng._inflight is None:
                        break
                    time.sleep(0.005)
            finally:
                replica.close()
        else:
            q = self._queue(eng)
            req = q.submit(prompt, n_new)
            while not req.done():
                eng.step(q)
            # the EOS was found with the next call already out
            assert eng._inflight is not None and not eng._slots
            traces = eng.decode_trace_count
            assert eng.swap_params(params, version=5) == 5
            assert eng.decode_trace_count == traces
            again = q.submit(prompt, n_new)
            while not again.done():
                eng.step(q)
            assert [int(t) for t in again.result(timeout=0)] == want
            eng.step(q)
        self._all_back(eng)
