#!/usr/bin/env python3
"""Does the system still start on the chip?  One command, one process.

    python chip_smoke.py            # one TPU chip: train + serve
    python chip_smoke.py --chips 4  # one four-chip host: the mesh only

Drives the two main paths through the entry points a user calls, at
the widths ``bench.py`` uses, with random weights made from a seed:

- *train*: ``create_communicator('xla')`` ->
  ``create_multi_node_optimizer`` -> ``StandardUpdater.update()`` x 3
  for ResNet-50 (batch 32, 224 px, ``Policy.bf16()``) and for
  ``TransformerLM`` d512 / L6 / V32k at batch 8 x seq 1024;
- *serve*: ``GenerationEngine`` + ``GenerationQueue`` on the d512 / L6
  / V32k model (32 slots, cache 512, prompts <= 128, 32 new tokens),
  slab cache and paged cache, 8 seeded requests each; then the decode
  and prefill executables at the benchmark's serving shapes
  (gpt2-medium, 2,049 pages of 16), compiled and read for copies of
  the KV page pool -- what only the chip's compiler can show;
- *serve, a family* of ``FAMILIES`` (``afmoe``, ``olmo_hybrid``,
  ``xing4``, ``phi4flash``, ``solar_open2``: the families served from
  the paged cache only): the row's small model, with the family's
  every mechanism,
  through the same engine, its served tokens held against the float32
  forward; then the same pool check at the shapes of the family's
  benchmark cell, every kind of cache leaf it has (K/V pools, rings,
  the latent, state rows and convolution tails);
- ``--chips 4`` runs ONLY the transformer step over four devices
  (data-parallel, then dp 2 x tp 2) and the one-device loss both are
  compared with.

Every phase checks what came out (finite moving losses, the Pallas
kernels IN the compiled programs, agreement with the kernel-free path
of the same model on the same chip) and any failure fails the run.
Without a TPU it exits non-zero and prints no result.  Earlier lines
are information -- seconds printed here are NOT benchmark numbers.
The last line of stdout is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import collections
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

SEED = 0
#: ``tests/test_tpu_mosaic.py``'s bound, max |a - b| / (|b| + 1): losses
#: of the kernel path, the kernel-free path and the meshes
TOLERANCE = 2e-2
#: the same measure on bf16 LOGITS: the repo's own bound for the cache
#: path against the full forward in bf16 (``tests/test_transformer.py``
#: ``TestIncrementalDecode``, rtol = atol = 5e-2).  At d512 / L6 two
#: differently compiled bf16 programs sit ~3e-2 apart, each ~3e-2 off
#: float32 (chip run, PR 21), so 2e-2 is below the dtype's own noise.
BF16_LOGITS_BOUND = 5e-2
KERNEL_MARK = 'tpu_custom_call'
_T0 = time.monotonic()


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def say(msg):
    print('[chip_smoke %6.1fs] %s' % (time.monotonic() - _T0, msg),
          flush=True)


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b) / (np.abs(b) + 1.0)))


def summary_line(device):
    """The contract's last line (the driver reads these keys); only a
    run in which every phase passed gets to print it."""
    return json.dumps({'ok': True, 'device': {
        'platform': device['platform'], 'kind': device['kind'],
        'count': int(device['count'])}})


@contextlib.contextmanager
def kernel_free():
    """Trace what runs inside with every Pallas kernel off (the jnp
    oracle of a kernel-backed model on the SAME device):
    ``pallas_mode()`` reads the switch at trace time, so jit a fresh
    function inside."""
    prior = os.environ.get('CHAINERMN_TPU_PALLAS')
    os.environ['CHAINERMN_TPU_PALLAS'] = '0'
    try:
        yield
    finally:
        if prior is None:
            del os.environ['CHAINERMN_TPU_PALLAS']
        else:
            os.environ['CHAINERMN_TPU_PALLAS'] = prior


def require_kernels(text, kernels, what):
    """On the chip the kernels must be IN the program, not bypassed
    (interpret-mode rehearsals lower them to plain HLO)."""
    if kernels == 'native':
        require(KERNEL_MARK in text,
                '%s holds no %s: its kernels were bypassed'
                % (what, KERNEL_MARK))
        return text.count(KERNEL_MARK)
    return 0


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get('peak_bytes_in_use')


# ----------------------------------------------------------------------
# device

def check_device(chips, platform='tpu', kernels='native'):
    """The device as JAX reports it; fails unless it is the chip this
    program was written for, with the kernels on and its peaks known."""
    import jax

    import bench
    from chainermn_tpu.ops._common import pallas_mode

    devices = jax.devices()
    first = devices[0]
    require(first.platform == platform,
            'no %s: jax.devices() gives %r' % (platform, devices))
    require(len(devices) >= chips,
            'need %d chip(s), JAX sees %d' % (chips, len(devices)))
    require(pallas_mode() == kernels,
            'pallas_mode() is %r, not %r' % (pallas_mode(), kernels))
    peak = bench.spec_lookup(bench.BF16_PEAK_TFLOPS, first.device_kind)
    hbm = bench.spec_lookup(bench.HBM_SPEC_GBS, first.device_kind)
    device = {'platform': first.platform, 'kind': first.device_kind,
              'count': len(devices)}
    say('device: %s x%d (%s), table peaks %.0f bf16 TFLOP/s, %.0f GB/s;'
        ' jax %s' % (first.device_kind, len(devices), first.platform,
                     peak, hbm, jax.__version__))
    return device


# ----------------------------------------------------------------------
# train

def run_updater(upd, steps, what):
    """``update()`` x steps ending in ``block_until_ready``; losses
    must be finite and moving."""
    import jax

    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(upd.update()['loss'])
        walls.append(time.perf_counter() - t0)
    jax.block_until_ready(upd.params)
    say('%s: losses %s; update() wall s %s (first holds the compile)'
        % (what, ['%.5f' % v for v in losses],
           ['%.3f' % w for w in walls]))
    require(all(np.isfinite(losses)), '%s: loss not finite' % what)
    require(losses[-1] != losses[0],
            '%s: loss did not move in %d steps' % (what, steps))
    return losses


def train_resnet(model=None, batch=32, insize=224, n_classes=1000,
                 steps=3):
    """The conv trainer: ResNet-50 under ``Policy.bf16()`` the way a
    user builds it (``model`` swaps in a shallower net for the CPU
    rehearsal)."""
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu
    from chainermn_tpu import training
    from chainermn_tpu.models import ResNet50, StatefulClassifier

    if model is None:
        model = ResNet50(num_classes=n_classes)
    rng = np.random.RandomState(SEED)
    x = rng.rand(batch, insize, insize, 3).astype(np.float32)
    y = rng.randint(0, n_classes, batch).astype(np.int32)
    dataset = [(x[i], y[i]) for i in range(batch)]

    comm = chainermn_tpu.create_communicator('xla')
    variables = model.init({'params': jax.random.PRNGKey(SEED)},
                           jnp.zeros((1, insize, insize, 3)),
                           train=False)
    model_state = {k: v for k, v in variables.items() if k != 'params'}
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm)
    upd = training.StandardUpdater(
        training.SerialIterator(dataset, batch, shuffle=False),
        optimizer, StatefulClassifier(model).loss, variables['params'],
        comm, model_state=model_state,
        policy=chainermn_tpu.Policy.bf16())
    losses = run_updater(upd, steps, 'train %s b%d/%dpx'
                         % (type(model).__name__, batch, insize))
    return {'losses': losses}


def init_lm(model, seq):
    import jax
    import jax.numpy as jnp
    return model.init(jax.random.PRNGKey(SEED),
                      jnp.zeros((1, seq), jnp.int32))['params']


def build_lm_updater(model, params, batch, seq, comm, param_specs=None):
    """Multi-node optimizer -> StandardUpdater on ``comm`` over a
    seeded token dataset (one global batch, repeated)."""
    import optax

    import chainermn_tpu
    from chainermn_tpu import training
    from chainermn_tpu.models import lm_loss

    rng = np.random.RandomState(SEED)
    toks = rng.randint(0, model.vocab_size, (batch, seq)).astype(np.int32)
    tgts = rng.randint(0, model.vocab_size, (batch, seq)).astype(np.int32)
    loss = lm_loss(lambda p, t: model.apply({'params': p}, t))
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(1e-3), comm)
    upd = training.StandardUpdater(
        training.SerialIterator(
            [(toks[i], tgts[i]) for i in range(batch)], batch,
            shuffle=False),
        optimizer, loss, params, comm, has_aux=True,
        param_specs=param_specs)
    return upd, toks, tgts


def train_transformer(d_model=512, n_heads=8, n_layers=6, d_ff=2048,
                      vocab=32000, seq=1024, batch=8, steps=3,
                      kernels='native'):
    """The LM trainer at ``bench.py``'s transformer widths."""
    import bench
    import chainermn_tpu
    from chainermn_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          d_ff=d_ff, max_len=seq)
    params = init_lm(model, seq)
    upd, toks, tgts = build_lm_updater(
        model, params, batch, seq,
        chainermn_tpu.create_communicator('xla'))
    what = 'train TransformerLM d%d/L%d/V%d b%dxseq%d' % (
        d_model, n_layers, vocab, batch, seq)

    # the step the updater is about to compile, as lowered
    fn, args = upd.traceable_step(upd.shard_batch(
        [(toks[i], tgts[i]) for i in range(batch)]))
    n_kernels = require_kernels(fn.lower(*args).as_text(), kernels,
                                'the lowered transformer step')
    say('%s: %d %s in the lowered step' % (what, n_kernels,
                                           KERNEL_MARK))

    # kernel path vs kernel-free path, same params and batch, on this
    # device (the comparison bench.py --check has)
    check = bench._transformer_numerics_check(model, params, toks, tgts)
    say('%s: kernel vs kernel-free loss rel err %.2e, grad-norm rel '
        'err %.2e' % (what, check['numerics_loss_rel_err'],
                      check['numerics_gnorm_rel_err']))
    require(check['numerics_vs_oracle_ok'],
            '%s: kernel path disagrees with the kernel-free path: %r'
            % (what, check))

    losses = run_updater(upd, steps, what)
    return {'losses': losses, 'n_kernels': n_kernels, 'check': check}


# ----------------------------------------------------------------------
# serve

def _full_forward_logits(model, params, prompts, next_tokens=None):
    """Plain full-sequence forward (``model.apply``, no cache): the
    logits that follow ``prompt`` (-> the first generated token) and,
    given ``next_tokens``, those that follow ``prompt + next_token``
    (-> the first decode step)."""
    import jax
    import jax.numpy as jnp

    lens = np.asarray([len(p) for p in prompts])
    rows = np.zeros((len(prompts), lens.max() + 1), np.int32)
    for i, p in enumerate(prompts):
        rows[i, :len(p)] = p
    idx = np.arange(len(prompts))
    if next_tokens is not None:
        rows[idx, lens] = next_tokens  # causal: the pad after is unseen
    logits = np.asarray(
        jax.jit(lambda p, t: model.apply({'params': p}, t))(
            params, jnp.asarray(rows)), np.float32)
    return logits[idx, lens - 1], logits[idx, lens]


def _float32_logits(model, params, prompts, next_tokens):
    """The same forward as plain float32 jnp: no kernels, float32
    weights and activations, full-precision matmuls."""
    import jax
    import jax.numpy as jnp

    wide = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), params)
    with kernel_free(), jax.default_matmul_precision('highest'):
        return _full_forward_logits(model.clone(dtype=jnp.float32),
                                    wide, prompts, next_tokens)


def _cache_logits(model, params, prompts, next_tokens, n_slots,
                  max_len, buckets, page_size=None):
    """Prefill each prompt into the cache, then ONE decode step of
    ``next_tokens`` for all of them, through the models' serving API
    (what the engine's executables wrap): ``(prefill logits, first
    decode-step logits)`` per prompt."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import models as M

    n = len(prompts)
    if page_size is None:
        cache = M.init_kv_cache(model, n_slots, max_len)
        prefill = jax.jit(lambda p, c, t, ln, i: M.prefill(
            model, p, c, t, ln, i), donate_argnums=(1,))
        decode = jax.jit(lambda p, c, t, pos: M.decode_step(
            model, p, c, t, pos), donate_argnums=(1,))
        extra = ()
    else:
        per_seq = -(-max_len // page_size)
        cache = M.init_paged_kv_cache(model, 1 + n_slots * per_seq,
                                      page_size)
        # page 0 is the allocator's scratch page; idle rows point there
        tables = np.zeros((n_slots, per_seq), np.int32)
        tables[:n] = 1 + np.arange(n * per_seq).reshape(n, per_seq)
        tables = jnp.asarray(tables)
        prefill = jax.jit(lambda p, c, t, ln, i: M.prefill_paged(
            model, p, c, t, ln, tables[i], 0), donate_argnums=(1,))
        decode = jax.jit(lambda p, c, t, pos, tb: M.decode_step_paged(
            model, p, c, t, pos, tb), donate_argnums=(1,))
        extra = (tables,)
    first = []
    for i, prompt in enumerate(prompts):
        width = next(b for b in buckets if b >= len(prompt))
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, cache = prefill(params, cache, jnp.asarray(padded),
                                jnp.asarray(len(prompt), jnp.int32),
                                jnp.asarray(i, jnp.int32))
        first.append(np.asarray(logits, np.float32))
    first = np.stack(first)
    tokens = np.zeros((n_slots,), np.int32)
    positions = np.zeros((n_slots,), np.int32)
    tokens[:n] = next_tokens
    positions[:n] = [len(p) for p in prompts]
    logits, cache = decode(params, cache, jnp.asarray(tokens),
                           jnp.asarray(positions), *extra)
    return first, np.asarray(logits, np.float32)[:n]


def _serve_requests(engine, prompts, max_new, kernels, what):
    """Warm the engine up, push the prompts through a
    ``GenerationQueue`` and drain it: the token stream of each."""
    from chainermn_tpu import serving

    t0 = time.perf_counter()
    aot = engine.warmup()
    warm_s = time.perf_counter() - t0
    require(all(aot['prefill'].values()) and all(aot['decode'].values()),
            '%s: an executable was not compiled ahead of time' % what)
    n_kernels = min(
        require_kernels(exe.as_text(), kernels,
                        '%s decode executable (bucket %d)' % (what, b))
        for b, (exe, _) in sorted(engine._decode.items()))
    queue = serving.GenerationQueue(
        max_prompt_len=engine.max_prompt_len,
        max_queue=4 * engine.n_slots,
        page_size=engine.page_size if engine.paged else None)
    t0 = time.perf_counter()
    requests = [queue.submit(p, max_new) for p in prompts]
    deadline = time.monotonic() + 300.0
    while not all(r.done() for r in requests):
        engine.step(queue)
        require(time.monotonic() < deadline,
                '%s: requests still open after 300 s' % what)
    streams = [r.result(timeout=1.0).tolist() for r in requests]
    serve_s = time.perf_counter() - t0
    require(all(len(s) == max_new for s in streams),
            '%s: a request came back short: %r'
            % (what, [len(s) for s in streams]))
    say('%s: warm-up %.1f s (%d executables, AOT), %d/%d requests '
        'completed x %d tokens in %.2f s, %d decode steps, >= %d %s '
        'per decode executable'
        % (what, warm_s, engine.compile_count, len(streams),
           len(prompts), max_new, serve_s, engine.decode_steps,
           n_kernels, KERNEL_MARK))
    return streams


def _served_gaps(model, engine, prompts, streams, width, what, held):
    """Every served token of a frozen-dataclass family held against the
    float32 kernel-free forward of the same weights: the gap by which
    its logit lies below that forward's best (``held``: what the cache
    held at its peak, for the line)."""
    import jax
    import jax.numpy as jnp

    exact = dataclasses.replace(model, dtype=jnp.float32)
    wide = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  engine.params)
    gaps = []
    with kernel_free(), jax.default_matmul_precision('highest'):
        forward = jax.jit(exact.apply)
        for prompt, out in zip(prompts, streams):
            row = np.zeros((1, width), np.int32)
            seq = np.concatenate([prompt, out])
            row[0, :len(seq)] = seq
            at = np.arange(len(prompt) - 1, len(seq) - 1)
            logits = np.asarray(forward(wide, jnp.asarray(row)))[0, at]
            gaps.append(logits.max(-1)
                        - logits[np.arange(len(at)), seq[at + 1]])
    gaps = np.concatenate(gaps)
    spread = float(np.std(logits))
    say('%s: %d served tokens lie below the float32 forward\'s best '
        'logit by at most %.4f, %.5f in the mean (logits spread %.3f); '
        '%s' % (what, gaps.size, gaps.max(), gaps.mean(), spread, held))
    require(np.all(np.isfinite(gaps)) and gaps.mean() < 0.1 * spread,
            '%s: served tokens are %.5f below the float32 forward\'s '
            'best in the mean, logits spreading %.3f'
            % (what, gaps.mean(), spread))
    return {'streams': streams, 'gap_widest': float(gaps.max()),
            'gap_mean': float(gaps.mean())}


def _require_parting_at_ties(model, params, prompts, slab, got, what):
    """Two engines' greedy streams of the same requests may part only
    where the model is undecided.  Up to a request's FIRST differing
    token both saw the same context; at that token the float32 forward
    must hold BOTH choices within the logits bound of its best logit
    (what follows a parting has another context and says nothing)."""
    parted = [(r, next(i for i, (a, b) in enumerate(zip(s, g)) if a != b))
              for r, (s, g) in enumerate(zip(slab, got)) if s != g]
    if not parted:
        return
    contexts = [np.concatenate([prompts[r], slab[r][:i]]).astype(np.int32)
                for r, i in parted]
    after, _ = _float32_logits(model, params, contexts, None)
    for (r, i), logits in zip(parted, after):
        best = float(logits.max())
        room = BF16_LOGITS_BOUND * (abs(best) + 1.0)
        picks = (slab[r][i], got[r][i])
        below = [best - float(logits[t]) for t in picks]
        say('%s: request %d parts from the slab at token %d, %d / %d: '
            '%.4f / %.4f below the float32 forward\'s best (room %.4f)'
            % ((what, r, i) + picks + tuple(below) + (room,)))
        require(max(below) < room,
                '%s leaves the slab at token %d of request %d where the '
                'float32 forward is decided: slab %d, paged %d lie %r '
                'below its best logit, room %.4f'
                % ((what, i, r) + picks + (below, room)))


def serve(d_model=512, n_heads=8, n_layers=6, d_ff=2048, vocab=32000,
          max_len=512, n_slots=32, max_prompt=128, max_new=32,
          n_requests=8, page_sizes=(16, 128), kernels='native'):
    """The generation server on ``bench.py``'s non-quick model, slab
    cache and paged cache."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.precision import Policy

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          d_ff=d_ff, max_len=max_len)
    params = model.init(jax.random.PRNGKey(SEED),
                        jnp.zeros((1, 8), jnp.int32))['params']
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(0, vocab, size=int(n)).astype(np.int32)
               for n in rng.randint(4, max_prompt + 1, size=n_requests)]
    policy = Policy.bf16()
    base = 'serve d%d/L%d/V%d %d slots' % (d_model, n_layers, vocab,
                                           n_slots)

    def engine(**kw):
        return serving.GenerationEngine(
            model, params, n_slots=n_slots, max_prompt_len=max_prompt,
            policy=policy, **kw)

    slab = engine()
    streams = {'slab': _serve_requests(slab, prompts, max_new, kernels,
                                       base + ' slab')}
    for page in page_sizes:
        name = 'paged%d' % page
        streams[name] = _serve_requests(
            engine(paged=True, page_size=page), prompts, max_new,
            kernels, '%s paged(page %d)' % (base, page))

    # correctness at the logits, all on the SAME (policy-cast) params:
    # the cache path (prefill, then one decode step) against the plain
    # full-sequence forward in the serving dtype AND against plain
    # float32 jnp, both inside BF16_LOGITS_BOUND
    served = slab.params
    plain_first, _ = _full_forward_logits(model, served, prompts)
    next_tokens = plain_first.argmax(-1)
    plain = _full_forward_logits(model, served, prompts, next_tokens)
    exact = _float32_logits(model, served, prompts, next_tokens)
    say('%s plain forward (%s) vs float32 jnp: rel err %.2e / %.2e '
        '(after prompt / after first token)'
        % (base, np.dtype(model.dtype).name, rel_err(plain[0], exact[0]),
           rel_err(plain[1], exact[1])))
    errors = {}
    for name, page in [('slab', None)] + [('paged%d' % p, p)
                                          for p in page_sizes]:
        got = _cache_logits(model, served, prompts, next_tokens,
                            n_slots, max_len, slab.prefill_edges,
                            page_size=page)
        require(got[1].shape == (n_requests, vocab)
                and np.all(np.isfinite(got[1])),
                '%s %s: bad decode logits' % (base, name))
        errors[name] = tuple(rel_err(g, r) for ref in (plain, exact)
                             for g, r in zip(got, ref))
        say('%s %s: prefill / first decode-step logits vs plain '
            'forward: rel err %.2e / %.2e; vs float32 jnp: %.2e / '
            '%.2e (bound %.0e)'
            % ((base, name) + errors[name] + (BF16_LOGITS_BOUND,)))
        require(max(errors[name]) < BF16_LOGITS_BOUND,
                '%s %s: logits off the plain forward / float32 jnp by '
                '%r' % (base, name, errors[name]))

    # token streams, slab vs paged.  A float page pool is read by the
    # paged kernel's head-major branch (``p`` rounded to the pool's
    # dtype for the MXU) and the slab by float32 products, so the two
    # may part where the model itself is undecided, and only there: up
    # to a request's FIRST differing token both saw the same context,
    # and at that token the float32 forward must hold BOTH choices
    # within the logits bound of its best (217 of 256 tokens agreed on
    # the chip at pages of 16 and of 128, PR 44)
    total = n_requests * max_new
    for page in page_sizes:
        got = streams['paged%d' % page]
        same = sum(a == b for s, g in zip(streams['slab'], got)
                   for a, b in zip(s, g))
        say('%s: slab vs paged(page %d) token streams agree on %d/%d'
            % (base, page, same, total))
        _require_parting_at_ties(
            model, served, prompts, streams['slab'], got,
            '%s paged(page %d)' % (base, page))

    # an int8 pool keeps the page-major layout and the kernel branch
    # that shares its arithmetic with the slab kernel (float32
    # products, the same online-softmax recurrence): where the page IS
    # the slab's key block the two engines emit the same tokens, and
    # that is REQUIRED
    slab_block = min(128, max_len)    # flash_attention_decode's key block
    if slab_block in page_sizes:
        int8 = {name: _serve_requests(
            engine(int8_kv=True, **kw), prompts, max_new, kernels,
            '%s int8 %s' % (base, name))
            for name, kw in (('slab', {}), ('paged%d' % slab_block, dict(
                paged=True, page_size=slab_block)))}
        streams.update(('int8_' + name, got) for name, got in int8.items())
        require(int8['paged%d' % slab_block] == int8['slab'],
                '%s: int8 paged(page %d) must equal the int8 slab token '
                'for token: %r vs %r'
                % (base, slab_block, int8['paged%d' % slab_block],
                   int8['slab']))
        say('%s: int8 slab vs int8 paged(page %d) token streams agree on '
            '%d/%d' % (base, slab_block, total, total))
    say('%s: slab stream of request 0: %s' % (base, streams['slab'][0]))
    return {'streams': streams, 'errors': errors}


# ----------------------------------------------------------------------
# the KV page pool stays where it lies

#: result-producing HLO instructions that move no data of their own
_PLUMBING = ('parameter', 'tuple', 'get-tuple-element', 'bitcast')
_WRITES = ('scatter', 'dynamic-update-slice')
#: ... and the Pallas calls that are the write of a head-major pool
#: (``ops.paged_kv_append``), of a recurrent state leaf
#: (``ops.gated_delta_step``, ``ops.selective_scan_step``) and of a
#: convolution tail leaf (``ops.causal_conv_step``): the leaves are
#: their aliased outputs
_WRITE_KERNEL = ('paged_kv_append', 'gated_delta_step',
                 'selective_scan_step', 'causal_conv_step')
_HLO_DTYPE = {'bfloat16': 'bf16', 'float32': 'f32', 'int8': 's8'}


def _hlo_instructions(text):
    """``(computation, name, result type, opcode, text)`` of each
    instruction of an HLO module's text."""
    computation = None
    for line in text.splitlines():
        line = line.strip()
        if line.endswith('{') and ' = ' not in line.split('(')[0]:
            computation = line.split('(')[0].split()[-1].lstrip('%')
            continue
        head, eq, rest = line.partition(' = ')
        if not eq:
            continue
        if rest.startswith('('):           # a tuple type: match it
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == '(') - (ch == ')')
                if depth == 0:
                    break
            rtype, tail = rest[:end + 1], rest[end + 1:]
        else:
            rtype, _, tail = rest.partition(' ')
        yield (computation, head.split()[-1].lstrip('%'), rtype,
               tail.split('(')[0].strip(), line)


def pool_shaped(text, leaves):
    """The instructions of a compiled module that make a value of a
    cache leaf's shape, other than the write itself (the scatter, and
    the fusion that holds nothing pool-shaped but it) and plumbing:
    ``[(opcode, name)]``.  Each one is a pass over a whole leaf, the
    copies that were 84% of the device's time before PR 26."""
    marks = {'%s[%s]' % (_HLO_DTYPE[np.dtype(leaf.dtype).name],
                         ','.join(map(str, leaf.shape)))
             for leaf in leaves}
    hits, writers = [], set()
    calls = {}
    for comp, name, rtype, opcode, line in _hlo_instructions(text):
        if not any(m in rtype for m in marks) or opcode in _PLUMBING:
            continue
        if opcode in _WRITES or name.startswith(_WRITE_KERNEL):
            writers.add(comp)
        elif opcode == 'fusion':
            calls[name] = line.split('calls=')[1].split(',')[0].lstrip(
                '%')
        else:
            hits.append((comp, opcode, name))
    dirty = {comp for comp, _, _ in hits}
    hits = [(opcode, name) for _, opcode, name in hits]
    hits += [('fusion', name) for name, callee in calls.items()
             if callee not in writers or callee in dirty]
    return hits


def _pool_check(engine, what, prompt_bucket):
    """The decode and prefill executables of ``engine``, compiled by
    the chip's own compiler, hold NO instruction that makes a value of
    a pool leaf's shape except the in-place write, need less scratch
    than one leaf, and keep the cache at its nominal bytes (what the
    executable aliases in place is the leaves as they lie on the
    device, padding and all)."""
    import jax

    leaves = jax.tree_util.tree_leaves(engine._cache)
    leaf_bytes = max(leaf.nbytes for leaf in leaves)
    nominal = sum(leaf.nbytes for leaf in leaves)
    say('%s: cache leaves %s' % (what, ', '.join(
        '%d x %s%r = %d bytes' % (n, np.dtype(dtype).name, shape,
                                  n * int(np.prod(shape))
                                  * np.dtype(dtype).itemsize)
        for (shape, dtype), n in sorted(collections.Counter(
            (leaf.shape, leaf.dtype) for leaf in leaves).items(),
            key=str))))
    out = {}
    for name, exe in (('decode', engine._get_decode(engine.n_slots)),
                      ('prefill', engine._get_prefill(prompt_bucket))):
        hits = pool_shaped(exe.as_text(), leaves)
        memory = exe.memory_analysis()
        temp, held = memory.temp_size_in_bytes, memory.alias_size_in_bytes
        say('%s %s executable: %d pool-shaped instruction(s) besides '
            'the write %r, temp_size_in_bytes %d (one layer\'s leaf: '
            '%d), cache on the device %d bytes (nominal %d)'
            % (what, name, len(hits), hits[:6], temp, leaf_bytes, held,
               nominal))
        require(not hits, '%s %s executable makes pool-shaped values '
                'outside the write: %r' % (what, name, hits))
        require(temp < leaf_bytes,
                '%s %s executable needs %d bytes of scratch, more '
                'than one layer\'s leaf (%d): a copy of the pool'
                % (what, name, temp, leaf_bytes))
        require(nominal <= held <= 1.01 * nominal,
                '%s %s executable holds the cache in %d bytes, not '
                'its nominal %d: a leaf is padded on the device'
                % (what, name, held, nominal))
        out[name] = {'pool_shaped': hits, 'temp_bytes': temp,
                     'cache_bytes': held}
    out['leaf_bytes'] = leaf_bytes
    return out


def serving_pool_check(d_model=1024, n_heads=16, n_layers=24,
                       d_ff=4096, vocab=50257, max_len=1024,
                       n_slots=32, max_prompt=512, page_size=16,
                       prompt_bucket=128):
    """The check the CPU cannot make, at the shapes of the benchmark's
    ``gpt2m-serve-closed32`` cell (gpt2-medium, 32 slots, 2,049 pages
    of 16): :func:`_pool_check` on a real ``GenerationEngine``.

    A jaxpr shows that the program asks for no copy
    (``tests/test_transformer.py``); it cannot show what XLA
    materialises on the TPU, where a ``(pages, 16, 16, 64)`` array
    lies page-minor (hence the pool's rows of 128 lanes: two 64-wide
    heads side by side, head-major, since PR 44)
    and a custom call takes a buffer, never a view: PR 25's trace held
    two copies of the whole pool in every call behind a jaxpr pin
    that passed.  Weights are zeros: nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import TransformerLM
    from chainermn_tpu.precision import Policy

    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers,
                          d_ff=d_ff, max_len=max_len)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(SEED),
                           jnp.zeros((1, 8), jnp.int32))['params'])
    params = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, x.dtype), shapes)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        policy=Policy.bf16())
    return _pool_check(engine, 'serve d%d/L%d %d slots, %d pages of %d'
                       % (d_model, n_layers, n_slots, engine.n_pages,
                          page_size), prompt_bucket)


# ----------------------------------------------------------------------
# the families served from the paged cache only, from one table

#: ``AfmoeLM`` at the ``trinity-mini`` cell's widths and depth
#: (``chipbench/configs/trinity-mini.json``); the defaults are the
#: published widths
TRINITY_MINI = dict(num_hidden_layers=5, num_dense_layers=1,
                    layer_types=('sliding_attention',) * 4
                    + ('full_attention',))
#: ``OlmoHybridLM`` at the ``olmo-hybrid-7b`` cell's depth
#: (``chipbench/configs/olmo-hybrid-7b.json``: two whole periods)
OLMO_HYBRID = dict(num_hidden_layers=8,
                   layer_types=(('linear_attention',) * 3
                                + ('full_attention',)) * 2)
#: ``Xing4LM`` at the ``xing4-29b-a4b`` cell's depth
#: (``chipbench/configs/xing4-29b-a4b.json``: one dense layer and five
#: expert layers)
YARN = dict(type='yarn', factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
XING4 = dict(num_hidden_layers=6, first_k_dense_replace=1,
             rope_scaling=YARN)
#: ``SolarOpen2LM`` at the ``solar-open2-250b`` cell's share
#: (``chipbench/configs/solar-open2-250b.json``: one period, 40 of 320
#: experts, an eighth of the vocabulary)
SOLAR_OPEN2 = dict(num_hidden_layers=4, gqa_layers=(0,),
                   n_routed_experts=40, router_experts=320,
                   vocab_size=24576)


def _window_edges(model, page_size, longest):
    return model.sliding_window - 7, model.sliding_window + 9


#: a family a row.  ``cls``; ``cell``, what the benchmark's cell changes
#: of the published defaults, and ``engine``, that cell's engine (the
#: pool check's shapes); ``policy``: does the engine cast the weights
#: (not where float32 leaves must stay so: ``hc_*``; ``A_log``, ``D``,
#: the lambdas); ``small``, the model the smoke serves, with the
#: family's every mechanism; ``page_size`` and ``edges``, two prompt
#: lengths either side of what the family counts in; ``reuse``: the
#: requests come twice over, so that every slot, page and row is used
#: again; ``what``, the model in the phase's lines; of
#: ``engine.stats()`` after the drain (and ``n_slots``), ``ok``, what
#: is said when it is not (``short``) and what the cache ``held``.
FAMILIES = {
    'afmoe': dict(
        cls='AfmoeLM', cell=TRINITY_MINI, policy=True,
        engine=dict(n_slots=64, max_prompt=3072, max_len=4096,
                    page_size=64, prompt_bucket=1024),
        # grouped K/V heads at the published head size, three window
        # layers and a full one behind a dense one, dropless experts
        small=dict(TRINITY_MINI, hidden_size=512, intermediate_size=1024,
                   moe_intermediate_size=256, num_attention_heads=8,
                   num_key_value_heads=2, head_dim=128, num_experts=8,
                   num_experts_per_tok=2, sliding_window=128),
        page_size=64, edges=_window_edges, reuse=False,
        what='{m.num_experts} experts top-{m.num_experts_per_tok}, '
             'window {m.sliding_window}',
        ok=lambda s: s['peak_window_pages_in_use']
        <= s['n_slots'] * s['window_ring'],
        short='%(peak_window_pages_in_use)d window pages in use, over '
              '%(n_slots)d rings of %(window_ring)d',
        held='%(peak_window_pages_in_use)d window pages at the peak in '
             'rings of %(window_ring)d'),
    'olmo_hybrid': dict(
        cls='OlmoHybridLM', cell=OLMO_HYBRID, policy=True,
        engine=dict(n_slots=48, max_prompt=3072, max_len=4096,
                    page_size=32, prompt_bucket=1024),
        # two periods of three gated-delta-rule layers and a full one,
        # the published head sizes 96 / 192 / 128, the convolution
        small=dict(OLMO_HYBRID, hidden_size=512, intermediate_size=1024,
                   num_attention_heads=4, num_key_value_heads=4,
                   linear_num_key_heads=4, linear_num_value_heads=4),
        # on and one over two chunks of the rule (64 at the default)
        page_size=32, reuse=True,
        edges=lambda model, page_size, longest: (longest // 4,
                                                 longest // 4 + 1),
        what='{m.linear_num_value_heads} heads of '
             '{m.linear_key_head_dim} x {m.linear_value_head_dim}',
        ok=lambda s: s['state_rows_in_use'] == 0
        and 0 < s['peak_state_rows_in_use'] <= s['n_slots'],
        short='%(state_rows_in_use)d state rows in use after the drain, '
              '%(peak_state_rows_in_use)d at the peak of %(n_slots)d '
              'slots',
        held='%(peak_state_rows_in_use)d state rows at the peak'),
    'xing4': dict(
        cls='Xing4LM', cell=XING4, policy=False,
        engine=dict(n_slots=48, max_prompt=6144, max_len=7680,
                    page_size=64, prompt_bucket=2048),
        # the published latent (512 + 64 values a position, heads of
        # 128 + 64 / 128), four streams, a dense layer before two
        # expert layers; prefill expanded, decode absorbed
        small=dict(hidden_size=512, intermediate_size=1024,
                   moe_intermediate_size=256, num_hidden_layers=3,
                   first_k_dense_replace=1, num_attention_heads=4,
                   q_lora_rank=192, n_routed_experts=8,
                   num_experts_per_tok=2, rope_scaling=dict(
                       YARN, original_max_position_embeddings=64)),
        page_size=64, reuse=True,
        edges=lambda model, page_size, longest: (page_size,
                                                 page_size + 1),
        what='latent {m.kv_lora_rank} + {m.qk_rope_head_dim}, '
             '{m.n_routed_experts} experts top-{m.num_experts_per_tok}',
        ok=lambda s: s['pages_in_use'] == 0,
        short='%(pages_in_use)d latent pages in use after the drain',
        held='%(peak_pages_in_use)d latent pages at the peak'),
    'phi4flash': dict(
        cls='Phi4FlashLM', cell={}, policy=False,     # the model WHOLE
        engine=dict(n_slots=96, max_prompt=1024, max_len=5120,
                    page_size=64, prompt_bucket=1024),
        # the published head size (pairs of 64: 128-lane K/V rows), two
        # Mamba / window pairs, the memory layer, the K/V layer, a
        # gated memory unit and a cross layer, the tied head
        small=dict(hidden_size=512, intermediate_size=1024,
                   num_hidden_layers=8, num_attention_heads=8,
                   num_key_value_heads=4, sliding_window=128),
        page_size=64, edges=_window_edges, reuse=True,
        what='{m.num_attention_heads} heads of {m.head_dim}, window '
             '{m.sliding_window}',
        ok=lambda s: s['state_rows_in_use'] == s['pages_in_use']
        == s['window_pages_in_use'] == 0
        and 0 < s['peak_state_rows_in_use'] <= s['n_slots']
        and s['peak_window_pages_in_use']
        <= s['n_slots'] * s['window_ring'],
        short='after the drain %(state_rows_in_use)d state rows, '
              '%(pages_in_use)d pages and %(window_pages_in_use)d '
              'window pages in use',
        held='%(peak_state_rows_in_use)d state rows and '
             '%(peak_window_pages_in_use)d window pages at the peak'),
    'solar_open2': dict(
        cls='SolarOpen2LM', cell=SOLAR_OPEN2, policy=True,
        engine=dict(n_slots=64, max_prompt=12288, max_len=13824,
                    page_size=64, prompt_bucket=2048),
        # one period: a gated gqa layer (8 query on 2 K/V heads of the
        # published 128) and three Kimi-delta layers (heads of 128 x
        # 128, a decay per key channel, the three convolutions), every
        # layer sparse: a share of 4 of the router's 16 experts
        small=dict(hidden_size=512, moe_intermediate_size=256,
                   num_hidden_layers=4, num_attention_heads=8,
                   num_key_value_heads=2,
                   linear_attn_config=dict(num_heads=4),
                   n_routed_experts=4, router_experts=16, first_expert=4,
                   num_experts_per_tok=2),
        # on and one over a chunk of the per-channel rule (64)
        page_size=64, reuse=True,
        edges=lambda model, page_size, longest: (longest // 4,
                                                 longest // 4 + 1),
        what='{m.linear_heads} kda heads of {m.linear_head_dim}, '
             '{m.n_routed_experts} of {m.router_width} experts '
             'top-{m.num_experts_per_tok}',
        ok=lambda s: s['state_rows_in_use'] == s['pages_in_use'] == 0
        and 0 < s['peak_state_rows_in_use'] <= s['n_slots'],
        short='%(state_rows_in_use)d state rows and %(pages_in_use)d '
              'pages in use after the drain, '
              '%(peak_state_rows_in_use)d rows at the peak of '
              '%(n_slots)d slots',
        held='%(peak_state_rows_in_use)d state rows at the peak'),
}


def _family_engine(name, model, params, n_slots, max_prompt, max_len,
                   page_size):
    from chainermn_tpu import serving
    from chainermn_tpu.precision import Policy

    return serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False,
        policy=Policy.bf16() if FAMILIES[name]['policy'] else None)


def serving_pool_check_family(name, **shape):
    """:func:`_pool_check` at the shapes of the family's benchmark cell
    (``FAMILIES[name]``: the model's ``cell`` at the published widths
    in the cell's ``engine``; ``shape`` overrides either): every kind
    of cache leaf the family has -- K/V pools, rings, the latent, state
    and tail leaves -- under the copy check.  Weights are zeros:
    nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import models

    row = FAMILIES[name]
    sizes = {k: shape.pop(k, v) for k, v in row['engine'].items()}
    bucket = sizes.pop('prompt_bucket')
    model = getattr(models, row['cls'])(**dict(row['cell'], **shape))
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(SEED),
                                          jnp.bfloat16)))
    engine = _family_engine(name, model, params, **sizes)
    pools = (('pages', engine.pool), ('window pages', engine.window_pool),
             ('state rows', engine.state_pool))
    return _pool_check(
        engine, 'serve %s d%d/L%d %d slots, pages of %d: %s'
        % (name, model.hidden_size, model.num_hidden_layers,
           engine.n_slots, engine.page_size, ', '.join(
               '%d %s' % (pool.n_pages, kind) for kind, pool in pools
               if pool is not None)), bucket)


def serve_family(name, n_slots=8, max_prompt=256, max_len=512,
                 max_new=48, n_requests=6, page_size=None, vocab_size=4096,
                 kernels='native', **shape):
    """The family's ``small`` model (``FAMILIES[name]``; ``shape``
    overrides its fields) through ``GenerationEngine`` +
    ``GenerationQueue``: prompts of 3 tokens, of the family's two
    ``edges``, of the longest the engine takes and of seeded lengths
    between, every served token held against the float32 kernel-free
    forward of the same weights (which runs every layer at every
    position), the pools' counts against what the family may hold."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import models

    row = FAMILIES[name]
    page_size = page_size or row['page_size']
    model = getattr(models, row['cls'])(**dict(
        row['small'], vocab_size=vocab_size,
        max_position_embeddings=max_len, **shape))
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    lengths = [3, *row['edges'](model, page_size, max_prompt),
               max_prompt] + list(
        rng.randint(4, max_prompt + 1, size=n_requests - 4))
    if row['reuse']:
        lengths = lengths + lengths[::-1] + lengths[:n_slots // 2]
    prompts = [rng.randint(0, vocab_size, size=int(n)).astype(np.int32)
               for n in lengths]
    what = 'serve %s d%d/L%d/V%d, %s' % (
        name, model.hidden_size, model.num_hidden_layers, vocab_size,
        row['what'].format(m=model))
    engine = _family_engine(name, model, params, n_slots, max_prompt,
                            max_len, page_size)
    streams = _serve_requests(engine, prompts, max_new, kernels, what)
    stats = dict(engine.stats(), n_slots=n_slots)
    require(row['ok'](stats), '%s: %s' % (what, row['short'] % stats))
    return _served_gaps(model, engine, prompts, streams,
                        max_prompt + max_new, what, row['held'] % stats)


# ----------------------------------------------------------------------
# four chips

def _distinct_devices(tree):
    import jax
    return {s.device for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}


def train_multichip(n_devices=4, d_model=512, n_heads=8, n_layers=6,
                    d_ff=2048, vocab=32000, seq=1024, global_batch=32,
                    tp=2, steps=3, kernels='native'):
    """The transformer step over ``n_devices``: data-parallel through
    ``create_communicator('xla')``, then ``MeshPlan.create(tp=tp)``
    (dp x tp); the first-step loss of each against the one-device
    loss of the same global batch."""
    import jax

    import chainermn_tpu
    from chainermn_tpu.communicators.mesh_utility import detect_topology
    from chainermn_tpu.models import (TransformerLM, lm_loss,
                                      tp_param_specs)
    from chainermn_tpu.parallel.meshplan import MeshPlan

    devices = jax.devices()[:n_devices]
    topology = detect_topology(devices)
    require(topology == (1, n_devices),
            'detect_topology gives %r, not (1, %d)'
            % (topology, n_devices))
    shape = dict(vocab_size=vocab, d_model=d_model, n_heads=n_heads,
                 n_layers=n_layers, d_ff=d_ff, max_len=seq)
    model = TransformerLM(**shape)
    what = 'TransformerLM d%d/L%d/V%d global b%dxseq%d' % (
        d_model, n_layers, vocab, global_batch, seq)
    out = {}

    def run(name, upd, toks, tgts, sharded_leaf=None):
        arrays = upd.shard_batch([(toks[i], tgts[i])
                                  for i in range(global_batch)])
        fn, args = upd.traceable_step(arrays)
        n_kernels = require_kernels(fn.lower(*args).as_text(), kernels,
                                    'the lowered %s step' % name)
        for label, tree in (('parameters', upd.params),
                            ('batch', arrays)):
            on = _distinct_devices(tree)
            require(on == set(devices),
                    '%s: %s live on %d device(s), not %d: %r'
                    % (name, label, len(on), n_devices, sorted(
                        str(d) for d in on)))
        shard = arrays[0].addressable_shards[0].data.shape
        note = ''
        if sharded_leaf is not None:
            leaf = sharded_leaf(upd.params)
            local = leaf.addressable_shards[0].data.shape
            require(local != leaf.shape,
                    '%s: tensor-parallel kernel %r is not split'
                    % (name, leaf.shape))
            note = ', qkv kernel %r -> %r per device' % (leaf.shape,
                                                         local)
        say('%s %s: mesh %r, batch shard %r%s, %d %s in the lowered '
            'step' % (what, name, dict(upd.comm.mesh.shape), shard,
                      note, n_kernels, KERNEL_MARK))
        out[name] = run_updater(upd, steps, '%s %s' % (what, name))

    params = init_lm(model, seq)
    upd, toks, tgts = build_lm_updater(
        model, params, global_batch, seq,
        chainermn_tpu.create_communicator('xla', devices=devices))

    # what both are compared with: the same global batch on ONE device
    loss = lm_loss(lambda p, t: model.apply({'params': p}, t))
    one = float(jax.jit(lambda p, t, y: loss(p, t, y)[0])(
        *jax.device_put((params, toks, tgts), devices[0])))
    say('%s: one-device loss %.5f' % (what, one))
    out['one_device'] = one

    run('dp%d' % n_devices, upd, toks, tgts)
    del upd

    plan = MeshPlan.create(tp=tp, devices=devices)
    require(plan.model_size == tp,
            'MeshPlan gave tp=%d, not %d' % (plan.model_size, tp))
    # the tp model's parameter tree IS the unsharded model's
    upd, toks, tgts = build_lm_updater(
        TransformerLM(tp_axis=plan.model_axis, **shape), params,
        global_batch, seq, plan.communicator(),
        param_specs=tp_param_specs(params, plan.model_axis))
    run('dp%dxtp%d' % (plan.data_size, tp), upd, toks, tgts,
        sharded_leaf=lambda p: p['block_0']['qkv']['kernel'])

    for name, losses in out.items():
        if name == 'one_device':
            continue
        err = abs(losses[0] - one) / (abs(one) + 1.0)
        say('%s %s: first-step loss %.5f vs one device %.5f (rel err '
            '%.2e, bound %.0e)' % (what, name, losses[0], one, err,
                                   TOLERANCE))
        require(err < TOLERANCE,
                '%s %s: loss %r is off the one-device loss %r'
                % (what, name, losses[0], one))
    return out


# ----------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument(
        '--chips', type=int, choices=(1, 4), default=1,
        help='4: run only the four-chip mesh phase and its one-device '
             'comparison (default 1: train + serve on one chip)')
    parser.add_argument(
        '--phases', default=None,
        help='comma-separated names: run only these one-chip phases '
             '(default: all of them, in order)')
    args = parser.parse_args(argv)

    if os.environ.get('CHAINERMN_TPU_PALLAS') == '0':
        say('FAILED: CHAINERMN_TPU_PALLAS=0 turns every kernel off; '
            'unset it')
        return 1
    phase = 'device'
    try:
        from chainermn_tpu.utils import enable_compilation_cache
        say('compilation cache: %s' % enable_compilation_cache())
        device = check_device(args.chips)
        import jax
        if args.chips == 4:
            phases = [('multichip', train_multichip)]
        else:
            phases = [('train_resnet', train_resnet),
                      ('train_transformer', train_transformer),
                      ('serve', serve),
                      ('serving_pool', serving_pool_check)]
            for name in FAMILIES:
                phases += [
                    ('serve_' + name,
                     functools.partial(serve_family, name)),
                    ('serving_pool_' + name,
                     functools.partial(serving_pool_check_family, name))]
            if args.phases:
                asked = args.phases.split(',')
                unknown = set(asked) - {name for name, _ in phases}
                if unknown:
                    raise SmokeFailure('no phase %s' % sorted(unknown))
                phases = [p for p in phases if p[0] in asked]
        for phase, fn in phases:
            t0 = time.perf_counter()
            fn()
            say('phase %s passed in %.1f s; peak_bytes_in_use %s'
                % (phase, time.perf_counter() - t0,
                   peak_bytes(jax.devices()[0])))
    except Exception as e:
        import traceback
        traceback.print_exc()
        say('FAILED in phase %s: %s: %s' % (phase, type(e).__name__, e))
        return 1
    print(summary_line(device), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
