"""Synthetic open-loop load generators for the serving engines
(:func:`open_loop` for the batch :class:`InferenceEngine`,
:func:`open_loop_generate` for the autoregressive
:class:`GenerationEngine`).

OPEN loop means arrivals are scheduled by a clock, not by completions
(a closed-loop generator waits for each response and therefore can
never observe queueing collapse -- the p99 it reports under overload
is a fiction).  Requests are submitted at ``t0 + i/rate`` regardless
of how the engine is doing; when the engine falls behind, the bounded
queue fills and submissions start shedding with the typed
``OverloadError`` -- which is the MEASUREMENT, not a failure: the
report separates served throughput/latency from shed fraction, so a
rate above capacity shows up as graceful degradation, never a wedge.

Determinism: the size mix comes from a seeded ``numpy`` rng, so two
runs at the same (seed, rate, n) offer the identical request
sequence.  Latency percentiles come from the telemetry registry's
raw-sample histograms (exact merge semantics), never from averaged
percentiles.
"""

import threading
import time

import numpy as np

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.utils import chaos as _chaos
from chainermn_tpu.utils.failure import OverloadError


def _hist_summary(reg, name):
    if reg is None:
        return {}
    snap = reg.snapshot().get(name)
    return (snap or {}).get('summary') or {}


def _worst_request(recorder):
    """The worst traced request's stage decomposition from the live
    recorder's in-memory records (``report.request_summary`` over the
    same ``kind='request'`` stream the offline report reads) -- what
    the bench rows carry so a bad p99 names its stage even when no
    capture directory was kept.  None when nothing was traced."""
    if recorder is None:
        return None
    try:
        from chainermn_tpu.telemetry.report import request_summary
        summary = request_summary(list(recorder.events))
    except Exception:
        return None
    if not summary:
        return None
    return {'e2e_ms': summary.get('e2e_ms'),
            'stage_p99_ms': summary.get('stage_p99_ms'),
            'worst': summary.get('worst'),
            'completed': summary.get('completed'),
            'shed': summary.get('shed')}


def open_loop_generate(engine, queue, rate, n_requests, seed=0,
                       prompt_len_range=None, max_new_tokens=16,
                       vocab_size=None, deadline_s=None,
                       result_timeout=60.0, clock=time.monotonic,
                       capture_dir=None, slo_monitor=None):
    """Open-loop driver for the autoregressive
    :class:`~chainermn_tpu.serving.GenerationEngine` -- same
    clock-scheduled arrival contract as :func:`open_loop` (shedding
    IS the measurement), but the unit of work is a SEQUENCE and the
    report's currency is TOKENS: generated tokens/s over the serve
    window, time-to-first-token and inter-token p50/p99 from the
    telemetry raw-sample histograms, plus the prefill/decode split's
    compile/trace accounting (flat decode trace count across slot
    refills is the continuous-batching no-recompile pin).

    Args:
      rate: offered request rate (req/s).
      prompt_len_range: ``(lo, hi)`` inclusive prompt-length mix
        (default ``(1, engine.max_prompt_len)``).
      max_new_tokens: tokens to generate per request.
      vocab_size: token-id range for the synthetic prompts (default
        the engine model's).
      deadline_s: per-request deadline -- expiry mid-generation sheds
        typed through the serve_cancel path.
      slo_monitor: optional
        :class:`~chainermn_tpu.telemetry.slo.SLOMonitor` attached to
        the recorder for the serve window; its live verdict rides in
        the report's ``slo`` field (and its ``slo_snapshot.json`` is
        written periodically when the monitor has an outdir).
    """
    lo, hi = prompt_len_range or (1, engine.max_prompt_len)
    vocab = vocab_size or engine.model.vocab_size
    rng = np.random.RandomState(seed)
    lens = rng.randint(lo, hi + 1, size=n_requests)
    prompts = [rng.randint(0, vocab, size=n).astype(np.int32)
               for n in lens]

    _installed = None
    if _telemetry.live() is None:
        _installed = _telemetry.enable()
    recorder = _telemetry.active()
    if slo_monitor is not None:
        slo_monitor.attach(recorder)

    st0 = engine.stats()
    stop = threading.Event()
    worker = threading.Thread(target=engine.run, args=(queue, stop),
                              daemon=True)
    worker.start()

    try:
        admitted, shed_submit = [], 0
        t0 = clock()
        longprompt_injected = 0
        for i, prompt in enumerate(prompts):
            target = t0 + i / float(rate)
            delay = target - clock()
            if delay > 0:
                time.sleep(delay)
            # serve_longprompt chaos: a burst of worst-case prefill
            # work (max-length prompts) landing at this arrival point
            # -- injected THROUGH the normal bounded submission path,
            # so the engine's prefill scheduling (monolithic vs
            # chunked) is what decides whether live sequences' inter-
            # token SLO survives the burst
            n_long = (_chaos.on_serve_longprompt()
                      if _chaos._active is not None else 0)
            for j in range(n_long):
                long_prompt = rng.randint(
                    0, vocab,
                    size=engine.max_prompt_len).astype(np.int32)
                try:
                    admitted.append(queue.submit(
                        long_prompt, max_new_tokens,
                        deadline=(None if deadline_s is None
                                  else clock() + deadline_s)))
                    longprompt_injected += 1
                except OverloadError:
                    shed_submit += 1
            try:
                admitted.append(queue.submit(
                    prompt, max_new_tokens,
                    deadline=(None if deadline_s is None
                              else clock() + deadline_s)))
            except OverloadError:
                shed_submit += 1
        served = shed_deadline = errored = 0
        tokens_served = 0
        for req in admitted:
            try:
                out = req.result(timeout=result_timeout)
                served += 1
                tokens_served += len(out)
            except OverloadError:
                shed_deadline += 1
            except Exception:
                errored += 1
        t1 = clock()
        reg = _telemetry.registry()
    finally:
        stop.set()
        worker.join(timeout=result_timeout)
        queue.close()
        if slo_monitor is not None:
            slo_monitor.detach()
            slo_monitor.write_snapshot()   # final live verdict
        if capture_dir is not None and _telemetry.active() is not None:
            try:
                _telemetry.active().flush(capture_dir)
            except Exception:
                pass  # the report below is the primary artifact
        worst = _worst_request(recorder)
        if _installed is not None:
            _telemetry.disable()
    ttft = _hist_summary(reg, 'serve_ttft_seconds')
    itl = _hist_summary(reg, 'serve_intertoken_seconds')
    dstep = _hist_summary(reg, 'serve_decode_seconds')
    st = engine.stats()
    wall = max(t1 - t0, 1e-9)
    offered = int(n_requests) + longprompt_injected
    shed = shed_submit + shed_deadline
    return {
        'offered': offered,
        'longprompt_injected': longprompt_injected,
        'offered_rate': float(rate),
        'admitted': len(admitted),
        'served': served,
        'shed_submit': shed_submit,
        'shed_deadline': shed_deadline,
        'errored': errored,
        'shed_fraction': shed / float(offered) if offered else 0.0,
        'served_req_per_s': served / wall,
        'tokens_served': tokens_served,
        'tokens_generated': (st['tokens_generated']
                             - st0['tokens_generated']),
        'tokens_per_s': tokens_served / wall,
        'wall_s': wall,
        'ttft_p50_ms': (ttft.get('p50') or 0.0) * 1e3
        if ttft else None,
        'ttft_p99_ms': (ttft.get('p99') or 0.0) * 1e3
        if ttft else None,
        'intertoken_p50_ms': (itl.get('p50') or 0.0) * 1e3
        if itl else None,
        'intertoken_p99_ms': (itl.get('p99') or 0.0) * 1e3
        if itl else None,
        'decode_step_p50_ms': (dstep.get('p50') or 0.0) * 1e3
        if dstep else None,
        'prefills': st['prefills'] - st0['prefills'],
        'decode_steps': st['decode_steps'] - st0['decode_steps'],
        'cancelled': st['cancelled'] - st0['cancelled'],
        'compile_count': st['compile_count'],
        'prefill_trace_count': st['prefill_trace_count'],
        'decode_trace_count': st['decode_trace_count'],
        'aot': st['aot'],
        'int8_kv': st['int8_kv'],
        'quantized': st['quantized'],
        'n_slots': st['n_slots'],
        'paged': ({k: st.get(k) for k in (
            'page_size', 'n_pages', 'pages_in_use', 'pages_free',
            'peak_pages_in_use', 'prefill_chunk', 'prefill_chunks',
            'cow_copies', 'copy_trace_count', 'prefix_lookups',
            'prefix_hits', 'prefix_hit_rate', 'prefix_evictions',
            'prefix_tokens_reused')} if st.get('paged') else None),
        'worst_request': worst,
        'speculative': _spec_report(st, st0),
        'slo': (slo_monitor.evaluate() if slo_monitor is not None
                else None),
    }


def _spec_report(st, st0):
    """The speculative-decoding slice of a generate report: windowed
    deltas of the engine's draft/verify accounting plus the two
    derived ratios the bench row banks -- ``accepted_draft_rate``
    (draft tokens whose target argmax agreed, over proposed) and
    ``verify_per_token`` (target executable invocations per generated
    token: < 1 IS the amortization).  ``None`` on non-speculative
    engines."""
    spec, spec0 = st.get('speculative'), st0.get('speculative')
    if not spec:
        return None
    spec0 = spec0 or {}
    proposed = (spec['draft_proposed']
                - spec0.get('draft_proposed', 0))
    accepted = (spec['draft_accepted']
                - spec0.get('draft_accepted', 0))
    verify_steps = (spec['verify_steps']
                    - spec0.get('verify_steps', 0))
    tokens = (st['tokens_generated'] - st0['tokens_generated'])
    return {
        'spec_tokens': spec['spec_tokens'],
        'draft_steps': spec['draft_steps'] - spec0.get(
            'draft_steps', 0),
        'verify_steps': verify_steps,
        'draft_proposed': proposed,
        'draft_accepted': accepted,
        'accepted_draft_rate': (accepted / proposed
                                if proposed else None),
        'verify_per_token': (verify_steps / tokens
                             if tokens else None),
        'draft_trace_count': spec['draft_trace_count'],
        'verify_trace_count': spec['verify_trace_count'],
    }


def open_loop(engine, queue, rate, n_requests, seed=0,
              max_request_items=None, deadline_s=None,
              result_timeout=30.0, clock=time.monotonic,
              capture_dir=None):
    """Drive ``engine`` through ``queue`` with an open-loop arrival
    process and return the serving report.

    Args:
      rate: offered request rate (req/s); arrivals at ``i / rate``.
      n_requests: total offered requests.
      seed: request-size mix seed (sizes uniform in
        ``[1, max_request_items]``).
      max_request_items: per-request item-count cap (default: half
        the queue's max_batch, so coalescing has something to do).
      deadline_s: per-request deadline; expired requests shed typed.
      result_timeout: drain allowance after the last arrival.
      capture_dir: when set, the telemetry window (events + serve
        histograms) is flushed there -- a capture ``python -m
        chainermn_tpu.telemetry doctor`` can read.

    Returns a dict: offered/admitted/served/shed counts + fractions,
    measured req/s over the serve window, latency and queue-wait
    p50/p99 (ms, from raw-sample histograms), pad-waste fraction,
    bucket hit-rate, and the engine's compile/trace accounting.
    """
    max_items = max_request_items or max(1, queue.max_batch // 2)
    rng = np.random.RandomState(seed)
    sizes = rng.randint(1, max_items + 1,
                        size=n_requests).astype(int)
    item_shape = engine._item_shape
    payload = rng.rand(max_items, *item_shape).astype(np.float32) \
        if np.issubdtype(engine._in_dtype, np.floating) else \
        rng.randint(0, 2, size=(max_items,) + item_shape)

    # latency/wait/pad percentiles come from the telemetry registry;
    # when the caller runs telemetry-free, install an in-memory
    # recorder for the window (the bench skew-capture idiom) so the
    # report never fabricates and never comes back empty-handed
    _installed = None
    if _telemetry.live() is None:
        _installed = _telemetry.enable()
    recorder = _telemetry.active()

    compiles_before = engine.compile_count
    stop = threading.Event()
    worker = threading.Thread(target=engine.run, args=(queue, stop),
                              daemon=True)
    worker.start()

    try:
        admitted, shed_submit = [], 0
        t0 = clock()
        for i, n in enumerate(sizes):
            target = t0 + i / float(rate)
            delay = target - clock()
            if delay > 0:
                time.sleep(delay)
            try:
                admitted.append(queue.submit(
                    payload[:n],
                    deadline=(None if deadline_s is None
                              else clock() + deadline_s)))
            except OverloadError:
                shed_submit += 1
        # drain: wait for every admitted request to resolve (result
        # or typed shed), then stop the worker
        served = shed_deadline = errored = 0
        for req in admitted:
            try:
                req.result(timeout=result_timeout)
                served += 1
            except OverloadError:
                shed_deadline += 1
            except Exception:
                errored += 1
        t1 = clock()
        reg = _telemetry.registry()
    finally:
        stop.set()
        worker.join(timeout=result_timeout)
        queue.close()
        if capture_dir is not None and _telemetry.active() is not None:
            try:
                _telemetry.active().flush(capture_dir)
            except Exception:
                pass  # the report below is the primary artifact
        worst = _worst_request(recorder)
        if _installed is not None:
            _telemetry.disable()
    lat = _hist_summary(reg, 'serve_latency_seconds')
    wait = _hist_summary(reg, 'serve_queue_wait')
    pad = _hist_summary(reg, 'serve_pad_waste')
    st = engine.stats()
    warm = len(st['buckets'])
    wall = max(t1 - t0, 1e-9)
    offered = int(n_requests)
    shed = shed_submit + shed_deadline
    return {
        'offered': offered,
        'offered_rate': float(rate),
        'admitted': len(admitted),
        'served': served,
        'shed_submit': shed_submit,
        'shed_deadline': shed_deadline,
        'errored': errored,
        'shed_fraction': shed / float(offered) if offered else 0.0,
        'served_req_per_s': served / wall,
        'wall_s': wall,
        'latency_p50_ms': (lat.get('p50') or 0.0) * 1e3
        if lat else None,
        'latency_p99_ms': (lat.get('p99') or 0.0) * 1e3
        if lat else None,
        'queue_wait_p50_ms': (wait.get('p50') or 0.0) * 1e3
        if wait else None,
        'queue_wait_p99_ms': (wait.get('p99') or 0.0) * 1e3
        if wait else None,
        'pad_waste_fraction': (pad.get('mean') if pad else None),
        # hit rate: executions that reused an executable compiled
        # BEFORE the traffic window -- with an eager warmup every
        # execution is a hit; a miss means the batcher produced a
        # bucket warmup did not compile (the signature guard refuses
        # shapes outside the edge set entirely)
        'bucket_hit_rate': (
            (st['executions']
             - max(0, st['compile_count'] - compiles_before))
            / float(st['executions']) if st['executions'] else None),
        'buckets_compiled': warm,
        'compile_count': st['compile_count'],
        'trace_count': st['trace_count'],
        'executions': st['executions'],
        'aot': st['aot'],
        'quantized': st['quantized'],
        'worst_request': worst,
    }
