"""Speculative decoding through the generation engine (out of
``tests/test_serving.py``, a file of its own so that it is a unit of
``--dist loadfile``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chainermn_tpu import serving
from conftest import tiny_lm as _tiny_lm


class TestSpeculativeDecoding:
    """ISSUE 19: draft-propose / single-pass target-verify.  THE pin
    is exact token-for-token equivalence with the non-speculative
    oracle engine in every cache mode -- speculation is a schedule,
    never an approximation -- plus the amortization accounting
    (verify executions per token < 1 under a perfect draft) and the
    no-recompile trace-flatness across slot refills."""

    PS = 8

    def _models(self):
        target, tparams = _tiny_lm(n_layers=2)
        draft, dparams = _tiny_lm(n_layers=1)
        return target, tparams, draft, dparams

    def _engine(self, model, params, paged=False, spec=None,
                chunk=None, **kw):
        base = dict(n_slots=2, max_prompt_len=16, max_len=32)
        base.update(kw)
        if paged:
            base.update(paged=True, page_size=self.PS)
            if chunk:
                base.update(prefill_chunk=chunk)
        if spec is not None:
            dmodel, dparams = spec
            base.update(draft_model=dmodel, draft_params=dparams)
        return serving.GenerationEngine(model, params, **base)

    def _queue(self, eng, **kw):
        return serving.GenerationQueue(
            max_prompt_len=eng.max_prompt_len,
            page_size=self.PS if eng.paged else None, **kw)

    def _drain(self, eng, q, reqs, max_steps=400):
        for _ in range(max_steps):
            if all(r.done() for r in reqs):
                break
            eng.step(q)
        return [[int(t) for t in r.result(timeout=0)] for r in reqs]

    # -- the correctness pin: all four cache modes + paged x int8 ----
    @pytest.mark.parametrize('paged,int8_kv,chunk', [
        (False, False, None),        # slab
        (True, False, None),         # paged
        (False, True, None),         # int8-KV slab
        (True, False, 4),            # paged + chunked prefill
        (True, True, None),          # paged + int8-KV (rollback pin)
    ])
    def test_exact_equivalence_with_oracle(self, paged, int8_kv,
                                           chunk):
        """6 prompts through 2 slots (several refill generations):
        speculative output == oracle output token-for-token, with
        decode/draft/verify trace counts FLAT after warmup (rollback
        and refills never retrace)."""
        target, tparams, draft, dparams = self._models()
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 32, size=n).tolist()
                   for n in (3, 7, 12, 5, 14, 9)]
        oracle = self._engine(target, tparams, paged=paged,
                              chunk=chunk, int8_kv=int8_kv)
        oracle.warmup()
        q = self._queue(oracle, max_queue=16)
        want = self._drain(oracle, q, [q.submit(p, 6)
                                       for p in prompts])
        eng = self._engine(target, tparams, paged=paged, chunk=chunk,
                           int8_kv=int8_kv, spec=(draft, dparams))
        eng.warmup()
        traces = (eng.decode_trace_count, eng.draft_trace_count,
                  eng.verify_trace_count)
        q2 = self._queue(eng, max_queue=16)
        got = self._drain(eng, q2, [q2.submit(p, 6)
                                    for p in prompts])
        assert got == want
        assert (eng.decode_trace_count, eng.draft_trace_count,
                eng.verify_trace_count) == traces
        st = eng.stats()['speculative']
        assert st['verify_steps'] > 0
        assert st['draft_proposed'] > 0

    def test_low_acceptance_pure_fallback_still_exact(self):
        """A disagreeing draft degrades THROUGHPUT, never output:
        with an independently-initialized draft most ticks reject at
        position 0 (the pure fallback step -- one target correction
        emitted), and the output still matches the oracle."""
        target, tparams, draft, dparams = self._models()
        rng = np.random.RandomState(1)
        prompts = [rng.randint(1, 32, size=n).tolist()
                   for n in (4, 9, 6, 11)]
        oracle = self._engine(target, tparams)
        oracle.warmup()
        q = self._queue(oracle, max_queue=16)
        want = self._drain(oracle, q, [q.submit(p, 8)
                                       for p in prompts])
        eng = self._engine(target, tparams, spec=(draft, dparams))
        eng.warmup()
        q2 = self._queue(eng, max_queue=16)
        got = self._drain(eng, q2, [q2.submit(p, 8)
                                    for p in prompts])
        assert got == want
        st = eng.stats()['speculative']
        # an untrained draft rarely matches the target's argmax: the
        # m=0 fallback path is exercised, and every emitted token in
        # a fallback tick is the target's own correction
        assert st['draft_accepted'] < st['draft_proposed']

    def test_perfect_draft_amortization(self):
        """draft == target -> every proposal accepted: rate 1.0 and
        STRICTLY fewer target executions than generated tokens per
        sequence (the ISSUE's CPU-measurable amortization claim,
        counted via trace-marked executables)."""
        target, tparams, _, _ = self._models()
        eng = self._engine(target, tparams, paged=True,
                           spec=(target, tparams))
        eng.warmup()
        q = self._queue(eng, max_queue=16)
        reqs = [q.submit([3, 5, 7], 8), q.submit([2, 4], 8)]
        self._drain(eng, q, reqs)
        st = eng.stats()['speculative']
        assert st['accepted_draft_rate'] == 1.0
        tokens = eng.tokens_generated
        # k=4: full acceptance commits 4 tokens per verify pass
        assert st['verify_steps'] < tokens
        assert st['verify_steps'] <= -(-tokens // 2)

    def test_eos_inside_accepted_prefix(self):
        """EOS landing INSIDE an accepted draft prefix must end the
        request exactly where the oracle loop stops -- accepted
        tokens past the EOS are rolled back, not emitted."""
        target, tparams, _, _ = self._models()
        probe = self._engine(target, tparams)
        probe.warmup()
        q = self._queue(probe)
        req = q.submit([5], 6)
        out = self._drain(probe, q, [req])[0]
        eos = out[2]                  # third token -> mid-window EOS
        oracle = self._engine(target, tparams, eos_id=eos)
        oracle.warmup()
        q1 = self._queue(oracle)
        want = self._drain(oracle, q1, [q1.submit([5], 50)])[0]
        # perfect draft: the whole window is accepted every tick, so
        # the EOS is committed from inside an accepted prefix
        eng = self._engine(target, tparams, eos_id=eos,
                           spec=(target, tparams))
        eng.warmup()
        q2 = self._queue(eng)
        got = self._drain(eng, q2, [q2.submit([5], 50)])[0]
        assert got == want
        assert got[-1] == eos and len(got) < 50

    def test_window_clipped_by_max_new_tokens(self):
        """max_new_tokens=2 with spec_tokens=4: the window proposes
        past the budget and the commit clips -- exactly 2 tokens,
        equal to the oracle's."""
        target, tparams, _, _ = self._models()
        oracle = self._engine(target, tparams)
        oracle.warmup()
        q1 = self._queue(oracle)
        want = self._drain(oracle, q1, [q1.submit([7, 9], 2)])[0]
        eng = self._engine(target, tparams, spec=(target, tparams))
        eng.warmup()
        q2 = self._queue(eng)
        got = self._drain(eng, q2, [q2.submit([7, 9], 2)])[0]
        assert got == want and len(got) == 2

    def test_paged_rollback_releases_window_pages(self):
        """Paged rollback accounting: after the fleet drains, the
        speculative engine pins exactly as many pool pages as the
        oracle (rejected window growth went BACK to the pool; only
        banked prefix pages remain)."""
        target, tparams, draft, dparams = self._models()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(1, 32, size=n).tolist()
                   for n in (9, 9, 13, 6)]
        oracle = self._engine(target, tparams, paged=True)
        oracle.warmup()
        q1 = self._queue(oracle, max_queue=16)
        self._drain(oracle, q1, [q1.submit(p, 6) for p in prompts])
        eng = self._engine(target, tparams, paged=True,
                           spec=(draft, dparams))
        eng.warmup()
        q2 = self._queue(eng, max_queue=16)
        self._drain(eng, q2, [q2.submit(p, 6) for p in prompts])
        assert eng.pool.in_use() == oracle.pool.in_use()

    # -- construction contract ---------------------------------------
    def test_ctor_validation_typed(self):
        target, tparams, draft, dparams = self._models()
        with pytest.raises(ValueError, match='draft_params'):
            self._engine(target, tparams,
                         spec=(draft, None))
        with pytest.raises(ValueError, match='spec_tokens'):
            self._engine(target, tparams, spec=(draft, dparams),
                         spec_tokens=1)
        from chainermn_tpu.models import TransformerLM
        other_vocab = TransformerLM(vocab_size=16, d_model=32,
                                    n_heads=4, n_layers=1, d_ff=32,
                                    max_len=64)
        op = other_vocab.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 4), jnp.int32))['params']
        with pytest.raises(ValueError, match='vocab'):
            self._engine(target, tparams, spec=(other_vocab, op))

    # -- telemetry + SLO recognition ---------------------------------
    def test_capture_carries_spec_phases_and_rate(self, tmp_path):
        """The observability satellite end to end: a speculative
        serve capture replays with (1) the accepted-draft-rate block
        in serve_summary's generate view, (2) the live SLO monitor's
        windowed speculative block, and (3) the doctor recognizing
        the capture (serve_draft / serve_verify are SERVE_PHASES)."""
        from chainermn_tpu.telemetry import diagnosis
        from chainermn_tpu.telemetry import slo as slo_mod
        from chainermn_tpu.telemetry.report import SERVE_PHASES
        assert 'serve_draft' in SERVE_PHASES
        assert 'serve_verify' in SERVE_PHASES
        assert 'serve_draft' in diagnosis.ANOMALY_PHASES
        assert 'serve_verify' in diagnosis.ANOMALY_PHASES
        target, tparams, draft, dparams = self._models()
        eng = self._engine(target, tparams, paged=True,
                           spec=(draft, dparams))
        eng.warmup()
        q = self._queue(eng, max_queue=16)
        cap = str(tmp_path / 'cap')
        monitor = slo_mod.SLOMonitor(n_slots=2)
        rep = serving.open_loop_generate(
            eng, q, rate=400.0, n_requests=6, seed=5,
            prompt_len_range=(1, 8), max_new_tokens=4,
            capture_dir=cap, slo_monitor=monitor)
        spec = rep['speculative']
        assert spec and spec['draft_proposed'] > 0
        assert spec['verify_per_token'] is not None
        assert spec['verify_per_token'] <= 1.0
        verdict = monitor.evaluate()
        assert verdict['speculative'] is not None
        assert (verdict['speculative']['draft_proposed']
                == spec['draft_proposed'])
        diag = diagnosis.quick_verdict(cap)
        assert diag is not None
        gen = diag['serve']['generate']
        assert gen['speculative']['draft_proposed'] > 0
        rate = gen['speculative']['accepted_draft_rate']
        assert rate is None or 0.0 <= rate <= 1.0
