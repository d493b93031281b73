#!/usr/bin/env python
"""Flash-attention microbench: Pallas kernel vs plain-XLA attention.

Times the repo's fused blockwise attention (``chainermn_tpu.ops``)
against the unfused jnp oracle (``mha_reference``: materializes the
(T, T) score matrix and lets XLA fuse what it can) on the SAME chip,
fwd and fwd+bwd, across sequence lengths -- and, with ``--sweep``,
times each of the three kernels alone over explicit tiles at the
training cell's shapes, beside the tile the rule derives, and the
paged decode kernel alone over pages a grid step at the three serving
cells' shapes (``--sweep-paged`` for that half alone, ``--case=<part
of a name>`` for some of its cases).  This
quantifies the custom hot-path the reference delegates to hand-written
native code (``/root/reference/chainermn/nccl/nccl.pyx:153-199``); here
the native analogue is the Mosaic-compiled kernel.

Measurement follows ``bench.py``: a per-call Python loop times
dispatch as much as the kernel, so each
sample is a ``lax.scan`` chain of attention calls compiled into ONE
program, synced by ``jax.device_get`` of a scalar slice, and the
per-call time is the marginal slope fit over three chain lengths
(median-of-reps; worst segment-slope deviation recorded per row as
``*_linearity_rel_err`` and suspect-gated at ``bench.LINEARITY_GATE``).

Usage::

    python benchmarks/flash_attention_bench.py            # real TPU
    python benchmarks/flash_attention_bench.py --cpu      # plumbing
    python benchmarks/flash_attention_bench.py --sweep    # + tile sweep
    python benchmarks/flash_attention_bench.py --sweep-paged  # only the
                                          # paged decode kernel's sweep
    python benchmarks/flash_attention_bench.py --sweep-paged --case=olmo

Writes JSONL to ``benchmarks/results/flash_attention_<platform>.jsonl``
(one line per measurement) and prints a summary table.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import (  # noqa: E402 (needs the sys.path insert above)
    BF16_PEAK_TFLOPS, LINEARITY_GATE, SIGNAL_MULT, _noise_estimate,
    adaptive_marginal_time, spec_lookup)


def attn_flops(b, t, h, d, causal, bwd):
    # QK^T + PV: each is t^2*d MACs = 2*t^2*d FLOPs per (batch, head)
    f = 4.0 * b * h * t * t * d
    if causal:
        f *= 0.5
    if bwd:
        f *= 3.5  # fwd + recompute + dq/dk/dv passes
    return f


def bench_config(b, t, h, d, causal, dtype, use_pallas, bwd,
                 block_q=None, block_k=None, quick=False):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chainermn_tpu import ops
    from chainermn_tpu.ops.flash_attention import mha_reference

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = (jax.random.normal(kq, (b, t, h, d), jnp.float32) * 0.5
         ).astype(dtype)
    k = (jax.random.normal(kk, (b, t, h, d), jnp.float32) * 0.5
         ).astype(dtype)
    v = (jax.random.normal(kv, (b, t, h, d), jnp.float32) * 0.5
         ).astype(dtype)

    if use_pallas:
        def attn(qq, kk_, vv_):
            return ops.flash_attention(qq, kk_, vv_, causal=causal,
                                       block_q=block_q,
                                       block_k=block_k)
    else:
        def attn(qq, kk_, vv_):
            return mha_reference(qq, kk_, vv_, causal=causal)

    if bwd:
        def one(qq):
            # differentiate wrt ALL of q/k/v: grads over q alone let
            # XLA dead-code the dK/dV matmuls on the unfused arm and
            # skew the comparison against attn_flops's full 3.5x
            # backward accounting
            dq, dk, dv = jax.grad(
                lambda q_, k_, v_: (attn(q_, k_, v_).astype(
                    jnp.float32) ** 2).sum(),
                argnums=(0, 1, 2))(qq, k, v)
            return (dq + dk + dv).astype(qq.dtype)
    else:
        def one(qq):
            return attn(qq, k, v).astype(qq.dtype)

    def make(n):
        @jax.jit
        def run():
            def body(c, _):
                # fold the output back into the carry so the chain is
                # data-dependent (XLA cannot elide steps)
                return one(c), ()
            out, _ = lax.scan(body, q, None, length=n)
            return out[0, 0, 0, :1].astype(jnp.float32)
        return run

    # reuse bench.py's measurement primitive (same contract: make(k)
    # returns a compiled thunk; marginal slope fit over three chain
    # lengths, median-of-reps, devget-synced)
    # no length-1 even in quick mode: XLA special-cases a scan of 1
    # and its time sits off the k>=2 line (see bench.py's cpu path).
    # Adaptive escalation (bench.py SIGNAL_MULT): a ~0.1ms attention
    # step is invisible under host timing jitter at
    # short scans; the floor (a LOWER bound on per-step time: analytic
    # flops at 2x this chip's table peak) plans the span so the
    # escalated scan is long enough on the first retry
    ks = (2, 3, 4) if quick else (2, 4, 6)
    kind = jax.devices()[0].device_kind
    floor = None  # the CPU plumbing run has no table peak: plan blind
    if jax.default_backend() != 'cpu':
        peak = spec_lookup(BF16_PEAK_TFLOPS, kind)
        floor = attn_flops(b, t, h, d, causal, bwd) / (2 * peak * 1e12)
    per, _overhead, times, lin, ks_used, _esc = adaptive_marginal_time(
        make, ks, reps=3, per_item_floor=floor, max_rep_s=15.0)
    # below-signal result: positive-but-jitter slope must not be
    # published as a real kernel time (same gate as bench.measure)
    weak = (per * (ks_used[-1] - ks_used[0])
            < SIGNAL_MULT * _noise_estimate(times, 3))
    return per, lin, weak


def main():
    argv = sys.argv[1:]
    cpu = '--cpu' in argv
    sweep = '--sweep' in argv
    sweep_paged_only = '--sweep-paged' in argv
    cases = [a.split('=', 1)[1] for a in argv if a.startswith('--case=')]
    quick = '--quick' in argv or cpu
    if cpu:
        os.environ.setdefault(
            'XLA_FLAGS', '--xla_force_host_platform_device_count=1')
        import jax
        jax.config.update('jax_platforms', 'cpu')
    import jax
    import jax.numpy as jnp

    platform = jax.default_backend()
    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.join(
        here, 'results', 'flash_attention_%s%s.jsonl' % (
            'paged_' if sweep_paged_only else '', platform))
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    # write rows to a temp file, renamed into place at the end AND on
    # any partial failure with >=1 row -- an aborted run neither
    # truncates the previously committed results nor loses what it
    # measured
    tmp_path = out_path + '.tmp'
    out_file = open(tmp_path, 'w')
    n_rows = 0

    def record(row):
        nonlocal n_rows
        out_file.write(json.dumps(row) + '\n')
        out_file.flush()
        n_rows += 1
        print(json.dumps(row), flush=True)

    # CPU: tiny plumbing shapes (interpret-mode Pallas is slow);
    # TPU: the real long-context sweep
    if cpu:
        configs = [(1, 256, 2, 64)]
        seqs_note = 'cpu plumbing check'
    else:
        configs = [(4, 1024, 8, 64), (4, 2048, 8, 64),
                   (2, 4096, 8, 64), (1, 8192, 8, 64)]
        seqs_note = 'tpu'
    dtype = jnp.float32 if cpu else jnp.bfloat16

    done = False
    try:
        if not sweep_paged_only:
            _run_all(configs, seqs_note, dtype, cpu, sweep, quick,
                     platform, record)
        if sweep:
            sweep_tiles(record, cpu)
        if sweep or sweep_paged_only:
            sweep_paged(record, cpu, cases)
        done = True
    finally:
        out_file.close()
        if done:
            os.replace(tmp_path, out_path)
            print('wrote %s (%d rows)' % (out_path, n_rows))
        elif n_rows:
            # keep what was measured WITHOUT clobbering a previously
            # complete results file
            os.replace(tmp_path, out_path + '.partial')
            print('aborted; kept %d rows in %s.partial'
                  % (n_rows, out_path))
        else:
            try:
                os.unlink(tmp_path)
            except OSError:
                pass


def _run_all(configs, seqs_note, dtype, cpu, sweep, quick, platform,
             record):
    for b, t, h, d in configs:
        for causal in (False, True):
            for bwd in (False, True):
                row = {'b': b, 't': t, 'h': h, 'd': d,
                       'causal': causal, 'bwd': bwd,
                       'dtype': str(dtype.__name__),
                       'platform': platform, 'note': seqs_note}
                try:
                    for name, use_pallas in (('pallas', True),
                                             ('xla', False)):
                        per, lin, weak = bench_config(
                            b, t, h, d, causal, dtype, use_pallas,
                            bwd, quick=quick)
                        row[name + '_ms'] = per * 1e3
                        row[name + '_tflops'] = attn_flops(
                            b, t, h, d, causal, bwd) / per / 1e12
                        row[name + '_linearity_rel_err'] = round(
                            lin, 4)
                        if lin > LINEARITY_GATE:
                            row['suspect'] = True
                            row['suspect_reason'] = (
                                row.get('suspect_reason', '') +
                                '%s arm timing nonlinear (%.0f%%); '
                                % (name, lin * 100))
                        if weak:
                            row['suspect'] = True
                            row['suspect_reason'] = (
                                row.get('suspect_reason', '') +
                                '%s arm signal below noise floor; '
                                % name)
                    row['speedup'] = row['xla_ms'] / row['pallas_ms']
                except Exception as e:  # keep earlier rows (OOM etc.)
                    row['error'] = str(e)[-300:]
                record(row)



def kernel_chain(kernel, bh, t, d, dtype, block_q, block_k, n):
    """``n`` calls of ONE of the three flash kernels at explicit tiles,
    chained through the operand its result replaces, in one program."""
    import importlib

    import jax
    import jax.numpy as jnp
    from jax import lax
    fa = importlib.import_module('chainermn_tpu.ops.flash_attention')

    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v, g = ((jax.random.normal(kk, (bh, t, d), jnp.float32) * 0.5
                   ).astype(dtype) for kk in keys[:4])
    lse = jnp.log(jnp.arange(1, t + 1, dtype=jnp.float32)
                  )[None, None, :].repeat(bh, 0)
    delta = jax.random.normal(keys[4], (bh, 1, t), jnp.float32) * 0.1
    scale = d ** -0.5

    def body(_, c):
        if kernel == 'fwd':
            return fa._fwd_pallas(c, k, v, True, scale, t, block_q,
                                  block_k)[0]
        if kernel == 'dq':
            return fa._bwd_dq_pallas(c, k, v, g, lse, delta, True,
                                     scale, t, block_q, block_k)
        return fa._bwd_dkv_pallas(q, c, v, g, lse, delta, True, scale,
                                  t, block_q, block_k)[0]

    start = k if kernel == 'dkv' else q
    run = jax.jit(lambda x: lax.fori_loop(0, n, body, x))
    return lambda: run(start).block_until_ready()


def sweep_tiles(record, cpu):
    """Each of the three kernels alone over (block_q, block_k) at the
    training cell's shapes (``gpt2m-train-1k``: bf16[128,1024,64],
    causal), next to the tile ``_flash_blocks`` derives: the rule is
    written to reproduce the winners (PERF.md section 6, PR 28)."""
    import importlib
    import time

    import jax.numpy as jnp
    fa = importlib.import_module('chainermn_tpu.ops.flash_attention')

    bh, t, d, n = (2, 256, 64, 2) if cpu else (128, 1024, 64, 24)
    dtype = jnp.float32 if cpu else jnp.bfloat16
    sizes = (128, 256) if cpu else (128, 256, 512, 1024)
    for kernel in ('fwd', 'dq', 'dkv'):
        derived = fa._flash_blocks(t, t, d, dtype, kernel=kernel)
        for bq in sizes:
            for bk in sizes:
                row = {'sweep': kernel, 'block_q': bq, 'block_k': bk,
                       'bh': bh, 't': t, 'd': d, 'calls': n,
                       'derived': (bq, bk) == derived}
                try:
                    once = kernel_chain(kernel, bh, t, d, dtype, bq, bk,
                                        n)
                    once()                         # compile, warm
                    best = float('inf')
                    for _ in range(3):
                        t0 = time.perf_counter()
                        once()
                        best = min(best, time.perf_counter() - t0)
                    row['ms_per_call'] = best / n * 1e3
                except Exception as e:  # Mosaic lowering limits
                    row['error'] = str(e)[-300:]
                record(row)


def _cell_lengths(rows, prompt, output, seed=0):
    """Live lengths of ``rows`` sequences caught mid-generation: a
    prompt and a uniform share of an output, each log-normal
    ``(median, sigma, lo, hi)`` like the serving cells' traffic
    files."""
    import numpy as np
    rng = np.random.RandomState(seed)

    def draw(median, sigma, lo, hi):
        return np.clip(np.exp(rng.normal(np.log(median), sigma, rows)),
                       lo, hi)

    return (draw(*prompt) + rng.uniform(size=rows) * draw(*output)
            ).astype(np.int32) + 1


# the serving cells' paged decode calls: rows, the page pool, the
# table's width, the window and the traffic's prompts and outputs
PAGED_CASES = {
    'gpt2m-serve-closed32': dict(
        rows=32, pool=(2049, 16, 16, 128), n_max=64,
        prompt=(128, 0.8, 16, 512), output=(96, 0.6, 16, 256)),
    # the same 16 heads of 64, two a 128-lane row of a head-major page
    # (PERF.md section 6, PR 44): the float pool's layout since then;
    # the case above is what an int8 pool still reads
    'gpt2m-serve-closed32.packed': dict(
        rows=32, pool=(2049, 8, 16, 128), n_max=64, group=2,
        head_major=True,
        prompt=(128, 0.8, 16, 512), output=(96, 0.6, 16, 256)),
    'trinity-mini-serve-closed64.full': dict(
        rows=64, pool=(4097, 4, 64, 128), n_max=64, group=8,
        head_major=True,
        prompt=(1024, 0.8, 128, 3072), output=(384, 0.6, 64, 1024)),
    'trinity-mini-serve-closed64.window': dict(
        rows=64, pool=(2113, 4, 64, 128), n_max=33, group=8,
        head_major=True, window=2048,
        prompt=(1024, 0.8, 128, 3072), output=(384, 0.6, 64, 1024)),
}
# ``olmo-hybrid-serve-closed48``: 30 K/V heads of 128 at group 1, the
# same 48 x 4,096 positions in pages of 64, 32 (the cell's) and 16
PAGED_CASES.update({
    'olmo-hybrid-serve-closed48.page%d' % ps: dict(
        rows=48, pool=(48 * 4096 // ps + 1, 30, ps, 128),
        n_max=4096 // ps, head_major=True,
        prompt=(1024, 0.8, 128, 3072), output=(384, 0.6, 64, 1024))
    for ps in (64, 32, 16)})


def paged_chain(case, lengths, pages, n, cpu=False):
    """``n`` calls of the paged decode kernel at ``pages`` pages a grid
    step, chained through the query, in one program."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    fa = importlib.import_module('chainermn_tpu.ops.flash_attention')

    rows, pool, n_max = case['rows'], case['pool'], case['n_max']
    head_major = case.get('head_major', False)
    group = case.get('group', 1)
    if cpu:
        pool = (rows * n_max + 1,) + pool[1:]
    heads = pool[1] if head_major else pool[2]
    dtype = jnp.float32 if cpu else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (rows, heads * group, pool[-1]),
                          dtype)
    k = jax.random.normal(keys[1], pool, dtype)
    v = jax.random.normal(keys[2], pool, dtype)
    # distinct pages a row, scattered over the pool as an allocator
    # that has served many requests leaves them
    tables = jnp.asarray(np.stack([
        1 + np.random.RandomState(r).permutation(pool[0] - 1)[:n_max]
        for r in range(rows)]), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    scale = pool[-1] ** -0.5

    def body(_, c):
        return fa._decode_paged_pallas(
            c, k, v, tables, lens, scale, group=group,
            window=case.get('window'), head_major=head_major,
            pages=pages, interpret=fa.interpret_flag())

    run = jax.jit(lambda x: lax.fori_loop(0, n, body, x))
    return lambda: run(q).block_until_ready()


def sweep_paged(record, cpu, only=()):
    """The paged decode kernel alone over pages a grid step, at the
    serving cells' shapes (those whose name holds one of ``only``, or
    all) and a length mix like theirs, next to
    what ``_paged_pages_per_step`` derives (PERF.md section 6, PR 30).
    ``gb_per_s`` counts the K and V pages a row's live positions touch,
    as the pool stores them.  The ``one`` rows give every sequence one
    live position, one page and one step: what a call costs before it
    reads anything.  A configuration that has not answered in three
    minutes ends the process (rows are written as they come): on the
    chip, 32 pages of 16 a step at 30 heads crashed the compiler and
    its crash handler hung for the 45 minutes the call had left."""
    import faulthandler
    import importlib
    import time

    import jax.numpy as jnp
    import numpy as np
    fa = importlib.import_module('chainermn_tpu.ops.flash_attention')

    for name, case in PAGED_CASES.items():
        if only and not any(part in name for part in only):
            continue
        if cpu:
            case = dict(case, rows=2, n_max=min(case['n_max'], 9),
                        pool=case['pool'][:-1] + (128,),
                        prompt=(64, 0.8, 16, 128),
                        output=(32, 0.6, 8, 64),
                        window=case.get('window') and 256)
        rows, pool, n_max = case['rows'], case['pool'], case['n_max']
        head_major = case.get('head_major', False)
        ps = pool[2] if head_major else pool[1]
        dtype = jnp.float32 if cpu else jnp.bfloat16
        derived = fa._paged_pages_per_step(
            pool[1:], dtype, n_max, head_major=head_major)
        n = 2 if cpu else 48
        mix = _cell_lengths(rows, case['prompt'], case['output'])
        if case.get('window') is None:
            mix = np.minimum(mix, n_max * ps)      # what the table holds
        for label, lengths in (('cell', mix),
                               ('one', np.ones(rows, np.int32))):
            live = fa._paged_live(np.asarray(lengths, np.int64), ps,
                                  n_max, case.get('window'), xp=np)[1]
            read = int(live.sum())
            page_bytes = 2 * int(np.prod(pool[1:])) * jnp.dtype(
                dtype).itemsize
            for pages in (1, 2, 4, 8, 16, 32):
                if pages > n_max or (label == 'one'
                                     and pages not in (1, derived)):
                    continue
                row = {'sweep': 'decode_paged', 'case': name,
                       'lengths': label, 'pages_per_step': pages,
                       'rows': rows, 'pool': list(pool),
                       'n_max': n_max, 'calls': n,
                       'mean_length': float(np.mean(lengths)),
                       'pages_read': read,
                       'grid_steps': int((-(-live // pages)).sum()),
                       'derived': pages == derived}
                faulthandler.dump_traceback_later(180, exit=True)
                try:
                    once = paged_chain(case, lengths, pages, n, cpu)
                    once()                         # compile, warm
                    best = float('inf')
                    for _ in range(3):
                        t0 = time.perf_counter()
                        once()
                        best = min(best, time.perf_counter() - t0)
                    row['ms_per_call'] = best / n * 1e3
                    row['gb_per_s'] = read * page_bytes / (best / n) / 1e9
                except Exception as e:  # Mosaic lowering limits
                    row['error'] = str(e)[-300:]
                faulthandler.cancel_dump_traceback_later()
                record(row)


if __name__ == '__main__':
    main()
