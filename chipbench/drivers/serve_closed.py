"""Serving cells under a closed loop: ``n_clients`` callers, each
submitting its next request the moment its last one ends, against one
``GenerationEngine`` ticked from this thread.  Stamps are taken in
``GenRequest.on_token``; every latency is reduced from them."""

import importlib
import json
import time

import numpy as np

from chipbench import stats, traffic


class ClosedLoop:
    """The clients, the tick loop and the stamps."""

    def __init__(self, engine, queue, stream, n_clients, span):
        self.engine, self.queue, self.stream = engine, queue, stream
        self.span = span
        self.records = []
        self.ticks = []            # (t0, t1, admitted, tokens)
        self.resubmit_delays = []  # (t, seconds)
        self._live = [None] * n_clients
        self._next = 0

    def _submit(self, client, after=None):
        prompt, n_out = self.stream.request(self._next)
        rec = {'i': self._next, 'prompt': prompt, 'n_out': n_out,
               'tokens': [], 'out': [], 'error': None}
        self._next += 1

        def on_token(_request_id, tokens, rec=rec):
            now = time.perf_counter()
            for tok in tokens:
                rec['tokens'].append(now)
                rec['out'].append(tok)

        rec['submit'] = time.perf_counter()
        rec['request'] = self.queue.submit(prompt, n_out,
                                           on_token=on_token)
        if after is not None:
            self.resubmit_delays.append((rec['submit'],
                                         rec['submit'] - after))
        self._live[client] = rec
        self.records.append(rec)

    def tick(self, resubmit=True):
        """One scheduler tick, then every client whose request ended
        submits its next."""
        eng = self.engine
        prefills, tokens = eng.prefills, eng.tokens_generated
        t0 = time.perf_counter()
        with self.span('chipbench:engine.step'):
            eng.step(self.queue)
        t1 = time.perf_counter()
        self.ticks.append((t0, t1, eng.prefills - prefills,
                           eng.tokens_generated - tokens))
        for client, rec in enumerate(self._live):
            if rec is None:
                if resubmit:
                    self._submit(client)
                continue
            if not rec['request'].done():
                continue
            try:
                rec['request'].result(timeout=0)
            except Exception as e:  # a shed request is a failed one
                rec['error'] = repr(e)
            self._live[client] = None
            if resubmit:
                self._submit(client, after=rec['tokens'][-1]
                             if rec['tokens'] else t1)

    def run_for(self, seconds, on_elapsed=None):
        t0 = time.perf_counter()
        while True:
            self.tick()
            now = time.perf_counter()
            if on_elapsed is not None:
                on_elapsed(now - t0)
            if now - t0 >= seconds:
                return t0, now

    def drain_first_tokens(self, t0, t1, limit_s=30.0):
        """Tick (no new submits) until every request submitted in the
        window has its first token."""
        deadline = time.perf_counter() + limit_s
        while any(t0 <= r['submit'] < t1 and not r['tokens']
                  and r['error'] is None for r in self.records):
            self.tick(resubmit=False)
            if time.perf_counter() > deadline:
                break


def failed_requests(records, t0, t1):
    """Of the requests submitted in the window: those shed, and those
    that ended before their ``max_new_tokens``."""
    return sum(1 for r in records if t0 <= r['submit'] < t1 and (
        r['error'] is not None
        or (r['request'].done() and len(r['out']) != r['n_out'])))


def check_sample(records, t0, t1, seed, k):
    """``k`` of the requests that finished in the window, the longest
    among them, the rest drawn from the seed."""
    done = [r for r in records if r['error'] is None
            and len(r['out']) == r['n_out'] and r['tokens']
            and t0 <= r['tokens'][-1] < t1]
    if not done:
        return []
    done.sort(key=lambda r: (-(len(r['prompt']) + r['n_out']), r['i']))
    rest = done[1:]
    order = np.random.default_rng([int(seed), 5]).permutation(len(rest))
    return [done[0]] + [rest[int(j)] for j in order[:k - 1]]


def run(run):
    cfg, mix, seed = run.spec.cfg, run.spec.mix, run.seed
    family = cfg['family']
    ref = importlib.import_module('chipbench.reference.' + family)
    adapter = importlib.import_module('chipbench.adapters.' + family)

    import jax
    params = jax.block_until_ready(
        ref.init_params(cfg, seed, adapter.PARAM_DTYPE['serve']))
    run.say('seeded weights made')
    engine, queue = adapter.build_engine(cfg, mix, params)
    run.say('engine warm: %d executables' % engine.compile_count)
    loop = ClosedLoop(engine, queue,
                      traffic.RequestStream(mix, cfg['vocab_size'], seed),
                      mix['n_clients'], run.span)
    # the clients start before the window opens, so that the slots are
    # busy and out of step when it does: part of set-up
    loop.run_for(mix['warm_seconds'])
    compiled = engine.compile_count

    # ---- the measured window -----------------------------------------
    run.setup_done()
    t0, t1 = loop.run_for(run.seconds, on_elapsed=run.maybe_start_trace)
    run.stop_trace()
    run.window = (t0, t1)
    loop.drain_first_tokens(t0, t1)
    run.read_memory_peak()

    records = loop.records
    family_ms = stats.latency_family(records, t0, t1)
    run.say('family ' + json.dumps(family_ms))
    window = t1 - t0
    run.e2e['serve_tokens_per_s'] = stats.tokens_in_window(
        records, t0, t1) / window
    for name in ('ttft_p75_ms', 'tpot_p90_ms'):
        run.e2e[name] = family_ms[name]
    run.attempted = sum(1 for r in records if t0 <= r['submit'] < t1)
    run.failed = failed_requests(records, t0, t1)
    run.records, run.family_ms = records, family_ms
    run.ticks = [t for t in loop.ticks if t0 <= t[0] and t[1] <= t1]
    run.resubmit_delays = [d for t, d in loop.resubmit_delays
                           if t0 <= t < t1]
    run.counters.update(n_slots=mix['engine']['n_slots'])

    # ---- the plain reference over a sample of what was served ---------
    t_ref = time.perf_counter()
    sample = check_sample(records, t0, t1, seed, mix['check_requests'])
    served = ([np.concatenate([r['prompt'], r['out']]) for r in sample],
              [len(r['prompt']) for r in sample], mix['check_pad_to'])
    gaps = ref.served_token_gaps(params, cfg, *served)
    n_tokens = sum(len(g) for g in gaps)
    run.say('reference: %d requests, %d served tokens, %.1f s'
            % (len(sample), n_tokens, time.perf_counter() - t_ref))
    every = np.concatenate(gaps) if gaps else np.asarray([np.inf])
    run.check('served_logit_gap_widest', every.max())
    run.check('served_logit_gap_mean', every.mean())
    if run.control:
        low = np.concatenate(ref.served_token_gaps(
            params, cfg, *served, control='fp8'))
        run.control_reading('served_logit_gap_widest', low.max())
        run.control_reading('served_logit_gap_mean', low.mean())
    run.check('failed_requests', run.failed)
    run.check('compiles_in_window', engine.compile_count - compiled)
