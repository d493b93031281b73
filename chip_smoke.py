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
- *serve, afmoe*: a small ``AfmoeLM`` (grouped K/V heads, window and
  full layers, dropless experts) through the same engine, its served
  tokens held against the float32 forward; then the same pool check at
  the ``trinity-mini`` cell's shapes, both kinds of cache leaf;
- *serve, olmo_hybrid*: a small ``OlmoHybridLM`` (gated-delta-rule
  layers beside full attention, a recurrent state row a sequence)
  through the same engine, against the float32 forward; then the pool
  check at the ``olmo-hybrid-7b`` cell's shapes: K/V pools, state
  leaves and convolution tails;
- *serve, xing4* and *serve, phi4flash*: the same pair for the latent
  (MLA) family and for the Mamba / differential-attention hybrid
  (``Phi4FlashLM``: full pages, ring pages and a state row in one
  table; the pool check at the ``phi4-mini-flash`` cell's shapes, the
  model whole);
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
# the afmoe family

#: ``AfmoeLM`` at the ``trinity-mini`` cell's widths and depth
#: (``chipbench/configs/trinity-mini.json``); the defaults are the
#: published widths
TRINITY_MINI = dict(num_hidden_layers=5, num_dense_layers=1,
                    layer_types=('sliding_attention',) * 4
                    + ('full_attention',))


def serving_pool_check_afmoe(n_slots=64, max_prompt=3072, max_len=4096,
                             page_size=64, prompt_bucket=1024,
                             **shape):
    """:func:`_pool_check` at the shapes of the benchmark's
    ``trinity-mini-serve-closed64`` cell: two kinds of cache leaf,
    ``(pages, 4, 64, 128)`` with the full layer's 4,097 pages or a
    window layer's 2,113 (64 rings of 33), each of whose minor pair is
    one whole bfloat16 tile.  Weights are zeros: nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import AfmoeLM
    from chainermn_tpu.precision import Policy

    model = AfmoeLM(**dict(TRINITY_MINI, **shape))
    params = jax.tree_util.tree_map(
        lambda shape: jnp.zeros(shape, jnp.bfloat16),
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False, policy=Policy.bf16())
    return _pool_check(
        engine, 'serve afmoe d%d/L%d %d slots, %d + %d pages of %d'
        % (model.hidden_size, model.num_hidden_layers, n_slots,
           engine.n_pages, engine.window_pool.n_pages, page_size),
        prompt_bucket)


def serve_afmoe(hidden=512, heads=8, kv_heads=2, head_dim=128,
                experts=8, top_k=2, width=256, dense_width=1024,
                vocab=4096, window=128, page_size=64, n_slots=8,
                max_prompt=256, max_len=512, max_new=48, n_requests=6,
                kernels='native'):
    """A small ``AfmoeLM`` with the family's every mechanism (grouped
    K/V heads at the published head size, three window layers and a
    full one behind a dense one, dropless experts beside a shared one)
    through ``GenerationEngine`` + ``GenerationQueue``: prompts on
    both sides of the window, every served token held against the
    float32 kernel-free forward of the same weights."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import AfmoeLM
    from chainermn_tpu.precision import Policy

    model = AfmoeLM(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=dense_width,
        moe_intermediate_size=width, num_attention_heads=heads,
        num_key_value_heads=kv_heads, head_dim=head_dim,
        num_experts=experts, num_experts_per_tok=top_k,
        sliding_window=window, max_position_embeddings=max_len,
        **TRINITY_MINI)
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    lengths = [3, window - 7, window + 9, max_prompt] + list(
        rng.randint(4, max_prompt + 1, size=n_requests - 4))
    prompts = [rng.randint(0, vocab, size=int(n)).astype(np.int32)
               for n in lengths]
    what = 'serve afmoe d%d/L5/V%d %d experts top-%d, window %d' % (
        hidden, vocab, experts, top_k, window)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False, policy=Policy.bf16())
    streams = _serve_requests(engine, prompts, max_new, kernels, what)
    stats = engine.stats()
    require(stats['peak_window_pages_in_use']
            <= n_slots * stats['window_ring'],
            '%s: %d window pages in use, over %d rings of %d'
            % (what, stats['peak_window_pages_in_use'], n_slots,
               stats['window_ring']))

    return _served_gaps(
        model, engine, prompts, streams, max_prompt + max_new, what,
        '%d window pages at the peak in rings of %d'
        % (stats['peak_window_pages_in_use'], stats['window_ring']))


# ----------------------------------------------------------------------
# the olmo_hybrid family

#: ``OlmoHybridLM`` at the ``olmo-hybrid-7b`` cell's depth
#: (``chipbench/configs/olmo-hybrid-7b.json``: two whole periods); the
#: defaults are the published widths
OLMO_HYBRID = dict(num_hidden_layers=8,
                   layer_types=(('linear_attention',) * 3
                                + ('full_attention',)) * 2)


def serving_pool_check_olmo_hybrid(n_slots=48, max_prompt=3072,
                                   max_len=4096, page_size=32,
                                   prompt_bucket=1024, **shape):
    """:func:`_pool_check` at the shapes of the benchmark's
    ``olmo-hybrid-serve-closed48`` cell: K/V pools ``(6,145, 30, 32,
    128)`` for the two full layers only, and per linear layer a state
    leaf ``(49, 15, 96, 384)`` float32 (two heads side by side in the
    lanes) and a tail leaf ``(49, 288, 128)`` (3 positions of 96 rows
    of lanes).  Weights are zeros: nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import OlmoHybridLM
    from chainermn_tpu.precision import Policy

    model = OlmoHybridLM(**dict(OLMO_HYBRID, **shape))
    params = jax.tree_util.tree_map(
        lambda shape: jnp.zeros(shape, jnp.bfloat16),
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False, policy=Policy.bf16())
    return _pool_check(
        engine, 'serve olmo_hybrid d%d/L%d %d slots, %d pages of %d, '
        '%d state rows' % (model.hidden_size, model.num_hidden_layers,
                           n_slots, engine.n_pages, page_size,
                           engine.state_pool.n_pages), prompt_bucket)


def serve_olmo_hybrid(hidden=512, heads=4, key_dim=96, value_dim=192,
                      width=1024, vocab=4096, page_size=32, n_slots=8,
                      max_prompt=256, max_len=512, max_new=48,
                      n_requests=6, kernels='native'):
    """A small ``OlmoHybridLM`` with the family's every mechanism (two
    periods of three gated-delta-rule layers and a full one, the
    published head sizes 96 / 192 / 128, the convolution) through
    ``GenerationEngine`` + ``GenerationQueue``: prompts that do and do
    not fill their bucket, slots and state rows reused, every served
    token held against the float32 kernel-free forward of the same
    weights."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import OlmoHybridLM
    from chainermn_tpu.precision import Policy

    model = OlmoHybridLM(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=width,
        num_attention_heads=heads, num_key_value_heads=heads,
        linear_num_key_heads=heads, linear_num_value_heads=heads,
        linear_key_head_dim=key_dim, linear_value_head_dim=value_dim,
        max_position_embeddings=max_len, **OLMO_HYBRID)
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    # on and one over two chunks of the rule (64 at the default size)
    lengths = [3, max_prompt // 4, max_prompt // 4 + 1, max_prompt] + list(
        rng.randint(4, max_prompt + 1, size=n_requests - 4))
    # twice the slots' worth of requests: every slot and row is reused
    lengths = lengths + lengths[::-1] + lengths[:n_slots // 2]
    prompts = [rng.randint(0, vocab, size=int(n)).astype(np.int32)
               for n in lengths]
    what = 'serve olmo_hybrid d%d/L8/V%d, %d heads of %d x %d' % (
        hidden, vocab, heads, key_dim, value_dim)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False, policy=Policy.bf16())
    streams = _serve_requests(engine, prompts, max_new, kernels, what)
    stats = engine.stats()
    require(stats['state_rows_in_use'] == 0
            and 0 < stats['peak_state_rows_in_use'] <= n_slots,
            '%s: %d state rows in use after the drain, %d at the peak '
            'of %d slots' % (what, stats['state_rows_in_use'],
                             stats['peak_state_rows_in_use'], n_slots))

    return _served_gaps(
        model, engine, prompts, streams, max_prompt + max_new, what,
        '%d state rows at the peak' % stats['peak_state_rows_in_use'])


# ----------------------------------------------------------------------
# the xing4 family

#: ``Xing4LM`` at the ``xing4-29b-a4b`` cell's depth
#: (``chipbench/configs/xing4-29b-a4b.json``: one dense layer and five
#: expert layers); the other defaults are the published widths
YARN = dict(type='yarn', factor=64, original_max_position_embeddings=4096,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
XING4 = dict(num_hidden_layers=6, first_k_dense_replace=1,
             rope_scaling=YARN)


def serving_pool_check_xing4(n_slots=48, max_prompt=6144, max_len=7680,
                             page_size=64, prompt_bucket=2048, **shape):
    """:func:`_pool_check` at the shapes of the benchmark's
    ``xing4-serve-closed48-long`` cell: ONE latent leaf a layer,
    ``(5,761, 1, 64, 640)`` (a position's 512 + 64 values in one row of
    five lane tiles), 2.83 GB over six layers beside 9.59 GB of
    weights.  Weights are zeros: nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import Xing4LM

    model = Xing4LM(**dict(XING4, **shape))
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(SEED),
                                          jnp.bfloat16)))
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False)    # no Policy: the hc_* leaves stay f32
    return _pool_check(
        engine, 'serve xing4 d%d/L%d %d slots, %d latent pages of %d'
        % (model.hidden_size, model.num_hidden_layers, n_slots,
           engine.n_pages, page_size), prompt_bucket)


def serve_xing4(hidden=512, heads=4, experts=8, top_k=2, width=256,
                dense_width=1024, q_rank=192, vocab=4096, page_size=64,
                n_slots=8, max_prompt=256, max_len=512, max_new=48,
                n_requests=6, kernels='native', **shape):
    """A small ``Xing4LM`` with the family's every mechanism (the
    published latent: 512 + 64 values a position, heads of 128 + 64 /
    128; four streams; a dense layer before two expert layers) through
    ``GenerationEngine`` + ``GenerationQueue``: prefill expanded,
    decode absorbed over the latent pages, slots and pages reused,
    every served token held against the float32 kernel-free forward of
    the same weights."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import Xing4LM

    model = Xing4LM(
        vocab_size=vocab, hidden_size=hidden,
        intermediate_size=dense_width, moe_intermediate_size=width,
        num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=heads, q_lora_rank=q_rank,
        n_routed_experts=experts, num_experts_per_tok=top_k,
        rope_scaling=dict(YARN, original_max_position_embeddings=64),
        max_position_embeddings=max_len, **shape)
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    lengths = [3, page_size, page_size + 1, max_prompt] + list(
        rng.randint(4, max_prompt + 1, size=n_requests - 4))
    lengths = lengths + lengths[::-1] + lengths[:n_slots // 2]
    prompts = [rng.randint(0, vocab, size=int(n)).astype(np.int32)
               for n in lengths]
    what = 'serve xing4 d%d/L3/V%d, latent %d + %d, %d experts top-%d' % (
        hidden, vocab, model.kv_lora_rank, model.qk_rope_head_dim,
        experts, top_k)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False)    # no Policy: the hc_* leaves stay f32
    streams = _serve_requests(engine, prompts, max_new, kernels, what)
    stats = engine.stats()
    require(stats['pages_in_use'] == 0,
            '%s: %d latent pages in use after the drain'
            % (what, stats['pages_in_use']))
    return _served_gaps(
        model, engine, prompts, streams, max_prompt + max_new, what,
        '%d latent pages at the peak' % stats['peak_pages_in_use'])


# ----------------------------------------------------------------------
# the phi4flash family

def serving_pool_check_phi4flash(n_slots=96, max_prompt=1024,
                                 max_len=5120, page_size=64,
                                 prompt_bucket=1024, **shape):
    """:func:`_pool_check` at the shapes of the benchmark's
    ``phi4flash-serve-closed96-think`` cell, the model WHOLE (7.7 GB of
    zero weights): ONE full K/V leaf pair ``(7,681, 10, 64, 128)`` (K/V
    heads packed by pair: 128 lanes) that layer 17 writes and 8 layers
    read, 8 window layers' ring leaves ``(865, 10, 64, 128)``, and per
    Mamba layer a state leaf ``(97, 1, 16, 5120)`` float32 and a tail
    leaf ``(97, 144, 128)``.  Weights are zeros: nothing runs."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import Phi4FlashLM

    model = Phi4FlashLM(**shape)
    params = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(SEED),
                                          jnp.bfloat16)))
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False)    # no Policy: A_log, D, lambdas stay f32
    return _pool_check(
        engine, 'serve phi4flash d%d/L%d %d slots, %d + %d pages of %d, '
        '%d state rows' % (model.hidden_size, model.num_hidden_layers,
                           n_slots, engine.n_pages,
                           engine.window_pool.n_pages, page_size,
                           engine.state_pool.n_pages), prompt_bucket)


def serve_phi4flash(hidden=512, heads=8, kv_heads=4, width=1024,
                    layers=8, vocab=4096, window=128, page_size=64,
                    n_slots=8, max_prompt=256, max_len=512, max_new=48,
                    n_requests=6, kernels='native'):
    """A small ``Phi4FlashLM`` with the family's every mechanism at the
    published head size (pairs of 64: 128-lane K/V rows): two Mamba /
    window pairs, the memory layer, the K/V layer, a gated memory unit
    and a cross layer, the tied head; through ``GenerationEngine`` +
    ``GenerationQueue``: prompts on both sides of the window, slots,
    pages, ring pages and state rows reused, every served token held
    against the float32 kernel-free forward of the same weights (which
    runs every layer at every position)."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import serving
    from chainermn_tpu.models import Phi4FlashLM

    model = Phi4FlashLM(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=width,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, sliding_window=window,
        max_position_embeddings=max_len)
    params = model.init(jax.random.PRNGKey(SEED), jnp.bfloat16)
    rng = np.random.RandomState(SEED)
    lengths = [3, window - 7, window + 9, max_prompt] + list(
        rng.randint(4, max_prompt + 1, size=n_requests - 4))
    lengths = lengths + lengths[::-1] + lengths[:n_slots // 2]
    prompts = [rng.randint(0, vocab, size=int(n)).astype(np.int32)
               for n in lengths]
    what = 'serve phi4flash d%d/L%d/V%d, %d heads of %d, window %d' % (
        hidden, layers, vocab, heads, model.head_dim, window)
    engine = serving.GenerationEngine(
        model, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_len=max_len, paged=True, page_size=page_size,
        prefix_sharing=False)    # no Policy: A_log, D, lambdas stay f32
    streams = _serve_requests(engine, prompts, max_new, kernels, what)
    stats = engine.stats()
    require(stats['state_rows_in_use'] == 0 and stats['pages_in_use'] == 0
            and stats['window_pages_in_use'] == 0
            and 0 < stats['peak_state_rows_in_use'] <= n_slots
            and stats['peak_window_pages_in_use']
            <= n_slots * stats['window_ring'],
            '%s: after the drain %d state rows, %d pages and %d window '
            'pages in use' % (what, stats['state_rows_in_use'],
                              stats['pages_in_use'],
                              stats['window_pages_in_use']))
    return _served_gaps(
        model, engine, prompts, streams, max_prompt + max_new, what,
        '%d state rows and %d window pages at the peak'
        % (stats['peak_state_rows_in_use'],
           stats['peak_window_pages_in_use']))


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
                      ('serving_pool', serving_pool_check),
                      ('serve_afmoe', serve_afmoe),
                      ('serving_pool_afmoe', serving_pool_check_afmoe),
                      ('serve_olmo_hybrid', serve_olmo_hybrid),
                      ('serving_pool_olmo_hybrid',
                       serving_pool_check_olmo_hybrid),
                      ('serve_xing4', serve_xing4),
                      ('serving_pool_xing4', serving_pool_check_xing4),
                      ('serve_phi4flash', serve_phi4flash),
                      ('serving_pool_phi4flash',
                       serving_pool_check_phi4flash)]
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
