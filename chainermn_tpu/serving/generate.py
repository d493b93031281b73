"""Autoregressive generation: bucketed KV-cache decode with
continuous token-level batching over a prefill/decode AOT split.

The forward-only :class:`~chainermn_tpu.serving.InferenceEngine`
serves one batch per request mix; token-by-token generation is a
different machine with a different bound in each phase:

- **Prefill** (the prompt pass) is compute-bound -- whole-prompt
  matmuls through the fused flash kernel -- and its natural bucket
  axis is PROMPT LENGTH: one AOT executable per power-of-two token
  length, one prompt per call, writing every layer's K/V into one
  cache SLOT (:func:`chainermn_tpu.models.prefill`).
- **Decode** (every subsequent token) is HBM-bandwidth-bound -- one
  query row per live sequence against its cached K/V
  (:func:`chainermn_tpu.ops.flash_attention_decode`, one HBM pass)
  -- and its bucket axis is ACTIVE-SLOT COUNT: one AOT executable per
  power-of-two slot count over the SAME persistent cache
  (:func:`chainermn_tpu.models.decode_step`).

Between the two sits **continuous batching**: admission happens at
TOKEN granularity, not batch granularity.  A sequence that finishes
(or whose deadline expires mid-generation -- the ``serve_cancel``
chaos site drives exactly this) frees its cache slot, and the slot is
refilled from the queue at the NEXT decode step; the rest of the
in-flight batch never waits for stragglers, which is what makes
tokens/s/chip under a mixed-length workload approach the steady-state
decode rate instead of the worst sequence's (the batch-level
alternative idles every finished slot until the whole batch drains).
The decode tick runs ONE call ahead of the host's reads: call t+1 is
dispatched with call t's tokens still on the device, and the host
reads, emits and prepares under it (:meth:`GenerationEngine.
_decode_once`; ``docs/serving.md``, "The tick, one call ahead").

Both executable families reuse the engine machinery wholesale: AOT
compilation (``jit(...).lower(...).compile()``) over the persistent
compilation cache, the SL007
``abstract_signature`` set as a runtime no-recompile guard (refused,
never retraced -- the static twin is the ``step:decode_forward``
shardlint target), :class:`~chainermn_tpu.parallel.MeshPlan`
tensor-parallel sharding (cache heads shard with the attention
weights, :func:`chainermn_tpu.models.kv_cache_specs`), float policies
cast weights at load, :class:`~chainermn_tpu.precision.Int8Policy`
quantizes them, and ``int8_kv=True`` stores the CACHE itself int8
with per-(position, head) scales
(:func:`~chainermn_tpu.precision.quantize_kv`) -- halving the bytes
the decode step is bound by.

The cache is DONATED into every prefill/decode executable and the
returned buffer rebound, so steady-state decode allocates nothing
cache-sized.  Telemetry: one ``serve_tick`` span a scheduler tick
whose children tile it (``docs/serving.md``, "The tick's anatomy"),
among them ``serve_prefill``/``serve_decode`` (``iteration`` = decode
step index) with the dispatch and the wait as spans of their own, a
``device_idle`` record where a launch found the device starved, a
per-step ``active_slots`` gauge, ``serve_ttft_seconds`` /
``serve_intertoken_seconds`` /
``serve_decode_seconds`` raw-sample histograms and
``serve_tokens_total`` -- the ``telemetry report``/``doctor`` serve
section renders tokens/s and TTFT from them (``docs/serving.md``).
"""

import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from chainermn_tpu import telemetry as _telemetry
from chainermn_tpu.telemetry import NULL_SPAN
from chainermn_tpu.analysis.walker import abstract_signature
from chainermn_tpu.serving.batcher import (bucket_edges, bucket_of,
                                           next_request_id,
                                           record_shed)
from chainermn_tpu.utils import chaos as _chaos
from chainermn_tpu.utils.platform import enable_compilation_cache
from chainermn_tpu.utils.failure import OverloadError

#: default admission knobs (the generation twins of batcher's)
DEFAULT_MAX_QUEUE = 256

#: why a decode call did not go out ahead of its predecessor's read
#: (``reason`` on its ``serve_decode`` span, ``stats()['settles']``):
#: the call in flight was settled for a row's foreseen ``end``, a
#: change of ``bucket``, a ``drained`` table, a ``spec``ulative tick or
#: ``swap_params``; or it is the call that ``prime``s the pipeline
#: after one of those
SETTLE_REASONS = ('end', 'bucket', 'prime', 'drained', 'spec', 'swap')
#: a wait shorter than this found its vector ready (seconds)
_BLOCKED_S = 50e-6


def _as_given(*operands):
    return operands


#: what a warm-up call feeds an operand that cannot be all zeros
_WARM_VALUES = {
    'slots': lambda shape, dtype: jnp.arange(shape[0], dtype=dtype),
    'length': jnp.ones,
}


def _merged(tokens, prev, src):
    """A decode call's input tokens, made on the device: row ``i``
    takes the PREVIOUS call's sampled token of its row ``src[i]``
    (``prev`` is that call's result as it left the executable, its
    counters behind its tokens, which the host may not have read
    yet), or with ``src[i] < 0`` the host's ``tokens[i]``."""
    return jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)


def _named(fn, name):
    """``fn`` under the name a jitted executable is to carry."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return call


def _struct_and_signature(cache):
    """A cache's ``ShapeDtypeStruct`` tree and its
    :func:`abstract_signature` (a leaf per layer): neither changes
    over an engine's life, so they are taken once and not once a
    tick."""
    struct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
    return struct, abstract_signature(struct)


class GenRequest:
    """One in-flight generation request: ``prompt`` (1-D int32 token
    ids), ``max_new_tokens``, optional absolute ``deadline``
    (``clock()`` units, enforced at admission AND between decode
    steps), and a one-shot completion cell filled with the generated
    token ids or a typed error.  ``request_id`` is the process-unique
    trace id (monotonic admission stamp in the suffix); ``t_trace0``
    is the admission instant on the telemetry recorder's clock (None
    when telemetry was off) -- the t0 of the ``queue_wait`` stage.

    ``prefix_key`` (stamped by a paged-engine queue at admission) is
    a STABLE hash of the shareable prompt prefix
    (:func:`chainermn_tpu.serving.paged.prefix_key`): a pure function
    of the token ids, so arrival order can never change it -- the
    scheduler uses it to co-admit shared-prefix requests.

    ``on_token`` (optional) streams committed tokens incrementally:
    the engine calls ``on_token(request_id, [int, ...])`` from the
    scheduler thread each time tokens are emitted (first token at
    prefill completion, one per decode tick, an accepted window per
    speculative tick).  The callback is passed at SUBMIT time (not
    attached later) so there is no race against the scheduler thread;
    it must be cheap and never raise -- the engine guards it, but a
    slow callback stalls the tick.  The fleet front's crash-safe
    request journal rides exactly this hook."""

    __slots__ = ('prompt', 'max_new_tokens', 'deadline', 'seq',
                 't_submit', 'synthetic', 'request_id', 't_trace0',
                 'prefix_key', 'on_token', '_done', '_result',
                 '_error')

    def __init__(self, prompt, max_new_tokens, deadline=None, seq=0,
                 t_submit=0.0, synthetic=False, request_id=None,
                 prefix_key=None, on_token=None):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError('empty prompt')
        if max_new_tokens < 1:
            raise ValueError('max_new_tokens must be >= 1, got %d'
                             % max_new_tokens)
        self.max_new_tokens = int(max_new_tokens)
        self.deadline = deadline
        self.seq = seq
        self.t_submit = t_submit
        self.synthetic = synthetic
        self.prefix_key = prefix_key
        self.on_token = on_token
        self.request_id = request_id or next_request_id()
        rec = _telemetry.live()
        self.t_trace0 = rec.now() if rec is not None else None
        self._done = threading.Event()
        self._result = None
        self._error = None

    def set_result(self, tokens):
        self._result = np.asarray(tokens, np.int32)
        self._done.set()

    def notify_tokens(self, tokens):
        """Stream newly COMMITTED tokens to ``on_token`` (no-op when
        no callback was registered).  Guarded: a journal/stream
        callback failure must never take the scheduler thread down
        with it -- the request still completes via ``set_result``."""
        if self.on_token is None or not tokens:
            return
        try:
            self.on_token(self.request_id,
                          [int(t) for t in tokens])
        except Exception:
            pass

    def set_error(self, exc):
        self._error = exc
        self._done.set()

    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        """Block for the generated tokens; re-raises the typed shed
        error (``OverloadError`` with reason queue_full / deadline /
        shutdown)."""
        if not self._done.wait(timeout):
            raise TimeoutError('request %d not completed within %rs'
                               % (self.seq, timeout))
        if self._error is not None:
            raise self._error
        return self._result


class GenerationQueue:
    """Bounded admission queue for generation requests.

    Unlike the batch queue there is no packing: the engine pops AT
    MOST as many requests as it has free cache slots each decode step
    (token-level admission).  The bounded-backlog / typed-shed /
    ``serve_burst`` contracts are identical to
    :class:`~chainermn_tpu.serving.RequestQueue`.

    ``page_size`` (set when feeding a paged engine) stamps each
    admitted request's :attr:`GenRequest.prefix_key` -- the stable
    hash of its page-aligned prompt prefix -- and unlocks
    ``pop(..., group_prefix=True)`` co-admission."""

    def __init__(self, max_prompt_len, max_queue=DEFAULT_MAX_QUEUE,
                 clock=time.monotonic, label=None, page_size=None):
        self.label = label  # fleet replica name (shed forensics)
        self.max_prompt_len = int(max_prompt_len)
        self.page_size = int(page_size) if page_size else None
        self.max_queue = int(max_queue)
        self._clock = clock
        self._lock = threading.Lock()
        self._waiting = []
        self._seq = 0
        self._closed = False
        self.submitted = 0
        self.shed_queue_full = 0
        self.shed_deadline = 0

    def submit(self, prompt, max_new_tokens, deadline=None,
               request_id=None, on_token=None):
        """Enqueue one prompt; returns the :class:`GenRequest`.
        Over-length prompts raise ``ValueError`` before touching
        queue state; a full or closed queue sheds typed.
        ``request_id`` lets an admission front (the fleet) pre-assign
        the trace id it already routed on; ``on_token`` is the
        incremental token-stream callback installed at admission (see
        :class:`GenRequest`)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size > self.max_prompt_len:
            raise ValueError(
                'prompt of %d tokens exceeds max_prompt_len %d; '
                'truncate client-side or raise the engine limit'
                % (prompt.size, self.max_prompt_len))
        burst = (_chaos.on_serve_submit()
                 if _chaos._active is not None else 0)
        with self._lock:
            req = self._admit(prompt, max_new_tokens, deadline,
                              request_id=request_id,
                              on_token=on_token)
            for _ in range(burst):
                try:
                    self._admit(prompt, max_new_tokens, deadline,
                                synthetic=True)
                except OverloadError:
                    break
        return req

    def _admit(self, prompt, max_new_tokens, deadline,
               synthetic=False, request_id=None, on_token=None):
        if self._closed:
            raise OverloadError('generation queue is shut down',
                                reason='shutdown',
                                queue_depth=len(self._waiting))
        if len(self._waiting) >= self.max_queue:
            self.shed_queue_full += 1
            record_shed('queue_full',
                        request_id=request_id or next_request_id(),
                        queue_depth=len(self._waiting),
                        **self._shed_attrs())
            raise OverloadError(
                'generation queue full (%d waiting); retry with '
                'backoff' % len(self._waiting),
                reason='queue_full', queue_depth=len(self._waiting))
        self._seq += 1
        self.submitted += 1
        key = None
        if self.page_size is not None:
            from chainermn_tpu.serving.paged import prefix_key
            key = prefix_key(prompt, self.page_size)
        req = GenRequest(prompt, max_new_tokens, deadline=deadline,
                         seq=self._seq, t_submit=self._clock(),
                         synthetic=synthetic, request_id=request_id,
                         prefix_key=key, on_token=on_token)
        self._waiting.append(req)
        return req

    def _shed_attrs(self):
        return {'replica': self.label} if self.label else {}

    def pop(self, k, group_prefix=False):
        """Up to ``k`` live requests in arrival order; requests whose
        deadline already expired while queued are shed typed here (the
        queue-side twin of the engine's mid-generation expiry).

        ``group_prefix=True`` (the paged engine's admission): after
        the head request is taken in arrival order, later waiters
        sharing its ``prefix_key`` are pulled forward so
        shared-prefix requests land in the SAME admission wave --
        their suffix prefills all read the prefix banked by the first
        completer.  Relative order within a key group is preserved,
        and requests without a key are never reordered past each
        other."""
        now = self._clock()
        out = []
        with self._lock:
            head_key = None
            while self._waiting and len(out) < k:
                idx = 0
                if group_prefix and head_key is not None:
                    idx = next(
                        (j for j, r in enumerate(self._waiting)
                         if r.prefix_key == head_key), 0)
                req = self._waiting.pop(idx)
                if req.deadline is not None and now > req.deadline:
                    self.shed_deadline += 1
                    record_shed('deadline',
                                request_id=req.request_id,
                                queue_depth=len(self._waiting),
                                waited_ms=round(
                                    (now - req.t_submit) * 1e3, 3),
                                **self._shed_attrs())
                    req.set_error(OverloadError(
                        'deadline expired after %.1f ms in queue'
                        % ((now - req.t_submit) * 1e3),
                        reason='deadline'))
                    continue
                if not out and group_prefix:
                    head_key = req.prefix_key
                out.append(req)
        return out

    def depth(self):
        with self._lock:
            return len(self._waiting)

    def close(self):
        with self._lock:
            self._closed = True
            pending, self._waiting = self._waiting, []
        for req in pending:
            record_shed('shutdown', request_id=req.request_id,
                        queue_depth=len(pending), count_total=False,
                        **self._shed_attrs())
            req.set_error(OverloadError('generation queue shut down',
                                        reason='shutdown'))

    def stats(self):
        return {'submitted': self.submitted,
                'shed_queue_full': self.shed_queue_full,
                'shed_deadline': self.shed_deadline,
                'depth': self.depth()}


class _Slot:
    """Host-side state of one cache slot."""

    __slots__ = ('request', 'position', 'remaining', 'generated',
                 't_last_token', 't_stage_end', 'pages', 'ring',
                 'state_row')

    def __init__(self, request, position, remaining, first_token,
                 t_now, t_stage_end=None, pages=None, ring=(),
                 state_row=0):
        self.request = request
        # both advance when a decode call is DISPATCHED, not when its
        # token is read: the host runs one call ahead of the device
        self.position = position          # next call's position
        self.remaining = remaining        # calls still to dispatch
        self.generated = [first_token]
        self.t_last_token = t_now
        # telemetry-clock end of this request's newest recorded trace
        # stage: each decode stage span starts here, so the stages
        # tile the request's lifetime gap-free (None: telemetry off)
        self.t_stage_end = t_stage_end
        # paged engine: this sequence's page table (one pool ref per
        # entry, released on completion/cancel); None on slot engines
        self.pages = pages
        # ... and its window layers' ring (a model with none: empty)
        self.ring = ring
        # ... and its recurrent layers' state row (a model with
        # none: 0, the scratch row)
        self.state_row = state_row


class _Flight:
    """One decode call whose sampled vector the host has not read.
    ``toks`` is that vector, still on the device; ``rows`` the slot id
    of every row and ``slots`` the :class:`_Slot` each row held AT
    DISPATCH: a row whose slot has gone since (expired, shed, ended a
    call earlier on an EOS) or holds another request by now gets its
    token dropped.  ``last`` marks the rows that end in this call by
    length; ``attrs`` is what the ``serve_decode`` span that reads the
    vector says of the call (``None``: telemetry was off)."""

    __slots__ = ('toks', 'rows', 'slots', 'row_of', 'last', 'ends',
                 'k', 'bucket', 'attrs')

    def __init__(self, toks, rows, slots, last, k, bucket, attrs):
        self.toks = toks
        self.rows = rows
        self.slots = slots
        self.row_of = {slot: i for i, slot in enumerate(slots)
                       if slot is not None}
        self.last = last
        self.ends = any(last)     # a foreseen end: a settle point
        self.k = k
        self.bucket = bucket
        self.attrs = attrs


class _PrefillState:
    """Host-side state of one sequence whose prompt is still being
    prefilled (paged engine only): chunked prefill runs one chunk per
    scheduler tick, so a long prompt spends several ticks here before
    graduating to a :class:`_Slot`."""

    __slots__ = ('request', 'pages', 'ring', 'state_row', 'pos',
                 'matched', 'chunks', 't_pop', 't_stage_end')

    def __init__(self, request, pages, pos, matched, t_pop=None,
                 t_stage_end=None, state_row=0):
        self.request = request
        self.pages = pages       # page table so far (refs held)
        self.ring = []           # window layers' ring pages so far
        self.state_row = state_row     # recurrent layers' row (whole)
        self.pos = pos           # next absolute position to prefill
        self.matched = matched   # prefix tokens reused from the index
        self.chunks = 0          # chunks dispatched so far
        self.t_pop = t_pop
        self.t_stage_end = t_stage_end


class GenerationEngine:
    """Continuous-batching autoregressive server for one causal LM.
    The engine names no model family: the cache constructors and the
    prefill / decode / verify bodies are METHODS OF THE MODEL
    (:class:`~chainermn_tpu.models._served.ServedLM` states them;
    ``docs/serving.md``, "the model protocol"), which
    :class:`~chainermn_tpu.models.TransformerLM`,
    :class:`~chainermn_tpu.models.AfmoeLM` and
    :class:`~chainermn_tpu.models.OlmoHybridLM` all have.

    Args:
      model: the flax module (``tp_axis`` set when serving over
        ``plan``/``param_specs``).
      params: the parameter pytree (the UNSHARDED oracle tree; tp
        placement is spec-driven).
      n_slots: cache slots = max concurrent sequences.  Decode
        executables are bucketed by power-of-two ACTIVE-slot count up
        to this.
      max_prompt_len: prompt-length cap; prefill executables are
        bucketed by power-of-two prompt length up to this.
      max_len: cache depth per slot (prompt + generated tokens;
        default ``model.max_len``).
      eos_id: optional stop token (greedy decode stops early on it).
      policy: float policy casts weights at load;
        :class:`~chainermn_tpu.precision.Int8Policy` quantizes them
        (dequant in-graph; refused under ``param_specs`` like the
        batch engine).
      int8_kv: store the KV cache int8 with per-(position, head)
        scales -- half the decode-bound HBM bytes of bf16.
      paged: replace the private per-slot cache slabs with a PAGED
        pool (:func:`chainermn_tpu.models.init_paged_kv_cache`):
        ``n_pages`` pages of ``page_size`` tokens shared by all
        sequences through per-sequence page tables, with refcounted
        prefix sharing (a radix index over completed prompts -- N
        requests with one system prompt read ONE banked copy),
        copy-on-write at divergence, and LRU eviction of banked
        prefixes when the pool runs dry.  Greedy outputs are
        IDENTICAL to the slot engine (tests/test_serving.py).
      page_size / n_pages: paged-mode geometry.  ``n_pages`` defaults
        to ``1 + n_slots * ceil(max_len / page_size)`` -- the slot
        engine's capacity plus the scratch page; LOWER it to
        oversubscribe (prefix sharing is what makes that safe).
      prefill_chunk: paged mode only -- split prompts into chunks of
        this many tokens, ONE chunk per scheduler tick interleaved
        with decode steps (SARATHI-style), so a long-prompt burst
        cannot freeze inter-token latency (the ``serve_longprompt``
        chaos site is the acceptance driver).  ``None`` prefills each
        prompt in one tick.
      prefix_sharing: disable the radix index (pages still pool, no
        cross-request reuse) -- an ablation knob for the bench.
      draft_model / draft_params: enable SPECULATIVE DECODING -- a
        smaller ``TransformerLM`` (fewer layers/heads, SAME vocab,
        never tensor-parallel) that autoregressively proposes
        ``spec_tokens - 1`` tokens per scheduler tick; the target
        scores the whole window in ONE verify executable
        (:func:`chainermn_tpu.models.spec_verify`) and the longest
        draft prefix whose argmaxes agree is committed plus the
        target's own next token (the correction at the first
        divergence, the bonus on full acceptance).  Greedy outputs
        are EXACTLY the non-speculative engine's token for token --
        acceptance rate only changes THROUGHPUT, never content
        (tests/test_serving.py pins all four cache modes).  The draft
        rides its own KV cache through the same slot ids, page
        tables, pool refcounts, prefix-shared pages and CoW copies
        as the target; rejected positions roll back by position
        rewind (+ page-table tail release in paged mode) -- stale
        rows are masked exactly like a reused slot.
      spec_tokens: verify window width ``k`` (>= 2): one tick runs
        ``k`` draft-decode steps and one k-token verify, committing
        1..k tokens, so accepted drafts amortize the HBM-bound
        target cache read (``verify_steps / tokens_generated < 1``
        whenever anything is accepted).
      plan / param_specs: MeshPlan tensor-parallel serving (the cache
        shards its head dim over ``plan.model_axis``).
      aot: the engine's AOT knob, verbatim (the persistent
        compilation cache is always on, placed by
        :func:`~chainermn_tpu.utils.platform.enable_compilation_cache`).
      label / version: fleet identity (the engine.py contract): when
        ``label`` is set, serve-path records carry
        ``replica``/``version`` attrs for per-replica SLO filtering;
        ``version`` is the boot parameter version and
        :meth:`swap_params` advances it.

    Decoding is GREEDY (argmax in-graph -- the sampled token never
    round-trips a vocab-sized buffer to the host), which also makes
    every test and A/B deterministic.
    """

    def __init__(self, model, params, n_slots=8, max_prompt_len=64,
                 max_len=None, eos_id=None, policy=None,
                 int8_kv=False, paged=False, page_size=16,
                 n_pages=None, prefill_chunk=None, prefix_sharing=True,
                 draft_model=None, draft_params=None, spec_tokens=4,
                 plan=None, param_specs=None, aot=True, label=None,
                 version=0):
        from chainermn_tpu.serving.paged import (PagePool,
                                                 RadixPrefixIndex)

        # a serving process may build neither a communicator nor an
        # updater: the variable is read here too
        _telemetry.maybe_enable_from_env()
        _telemetry.install_compile_log()
        self.model = model
        # a family refuses here, in one message, what it has no path for
        model.check_serving(
            paged=paged, int8_kv=int8_kv, prefill_chunk=prefill_chunk,
            prefix_sharing=bool(paged and prefix_sharing),
            draft_model=draft_model is not None, plan=plan is not None)
        self.label = label
        self.param_version = int(version)
        self._boot_version = self.param_version
        self.n_slots = int(n_slots)
        #: admissions per scheduler tick cap (None: every free slot).
        #: The fleet degradation ladder's "shrink admission" rung sets
        #: this to 1 and restores None on recovery.
        self.admit_cap = None
        self.max_prompt_len = int(max_prompt_len)
        self.max_len = int(max_len or model.max_len)
        if self.max_prompt_len > self.max_len:
            raise ValueError('max_prompt_len %d exceeds cache depth '
                             '%d' % (self.max_prompt_len, self.max_len))
        self.eos_id = eos_id
        self.policy = policy
        self.plan = plan
        if param_specs is not None and plan is None:
            raise ValueError('param_specs requires a plan')
        self.param_specs = param_specs
        if (plan is not None) != (model.tp_axis is not None):
            raise ValueError(
                'serve a tp_axis model over a plan and a plain model '
                'without one (tp_axis=%r, plan=%r)'
                % (model.tp_axis, plan))
        self.cache_dir = enable_compilation_cache()
        self.aot_requested = bool(aot)

        self.prefill_edges = bucket_edges(self.max_prompt_len)
        self.decode_edges = bucket_edges(self.n_slots)

        # load-time parameter transform, the engine.py idiom
        quantize = getattr(policy, 'quantize', None)
        if quantize is not None and param_specs is not None:
            raise NotImplementedError(
                'int8 weights under tensor-parallel param_specs '
                'are not wired yet (quantize per shard after '
                'resharding); int8_kv composes with tp, int8 '
                'WEIGHTS do not')
        self.quantized = quantize is not None
        self._params_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                jnp.shape(x), x.dtype if hasattr(x, 'dtype')
                else np.asarray(x).dtype), params)
        self.params = self._place_params(params)

        self.int8_kv = bool(int8_kv)
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.prefill_chunk = (int(prefill_chunk) if prefill_chunk
                              else None)
        if self.prefill_chunk is not None and not self.paged:
            raise ValueError('prefill_chunk requires paged=True (the '
                             'slot cache prefills whole prompts)')
        if self.prefill_chunk is not None \
                and self.prefill_chunk > self.max_prompt_len:
            raise ValueError('prefill_chunk %d exceeds max_prompt_len '
                             '%d' % (self.prefill_chunk,
                                     self.max_prompt_len))
        if self.paged:
            self.pages_per_seq = -(-self.max_len // self.page_size)
            self.n_pages = int(
                n_pages or 1 + self.n_slots * self.pages_per_seq)
            self.pool = PagePool(self.n_pages, self.page_size)
            self._prefix_index = (RadixPrefixIndex(self.pool)
                                  if prefix_sharing else None)
            # window layers keep their pages in a RING per sequence,
            # out of a pool of their own leaf shape; a sequence's two
            # tables ride one operand, [full table | ring]
            self._ring = int(model.window_ring(self.page_size))
            self._window = (int(model.sliding_window) if self._ring
                            else None)
            self.window_pool = (
                PagePool(1 + self.n_slots * self._ring, self.page_size)
                if self._ring else None)
            # recurrent layers keep a fixed-size STATE a sequence,
            # neither a page list nor a ring: one row of leaves of
            # their own, held from admission to release; it rides the
            # same operand, [full table | ring | state row]
            self.state_pool = (PagePool(1 + self.n_slots, 1)
                               if model.has_state_row() else None)
            self._table_width = (self.pages_per_seq + self._ring
                                 + (self.state_pool is not None))
        else:
            if n_pages is not None:
                raise ValueError('n_pages requires paged=True')
            self.pages_per_seq = None
            self.n_pages = None
            self.pool = None
            self._prefix_index = None
            self._ring, self._window, self.window_pool = 0, None, None
            self.state_pool = None
            self._table_width = None
        # the GLOBAL cache is built unsharded; specs shard it ``tp``
        # ways (a paged pool lays its rows out for that many)
        tp = plan.model_size if plan is not None else 1
        cache = self._new_cache(model, tp)
        self._cache_specs = (
            model.kv_cache_specs(cache, plan.model_axis)
            if plan is not None else None)
        self._cache = jax.device_put(cache, self._cache_sharding())
        self._cache_struct, self._cache_sig = _struct_and_signature(
            cache)
        # bytes of (one K/V page, one state row[, one window page])
        # over the layers that HOLD one: what the tick's cache-bytes
        # attributes are counted in; a family whose page is not K/V
        # names its count (``page_counter``)
        self._page_counter = model.page_counter
        # what of a stored row of the pool a decode call reads is K/V,
        # where the family says (``serve_decode``'s lane attributes:
        # none from a family that gives no lanes)
        self._kv_lanes = (
            dict(zip(('kv_live_lanes', 'kv_lanes'),
                     model.kv_lanes(self._cache_struct)))
            if self.paged else {})
        self._cache_bytes = (
            model.paged_cache_bytes(self._cache_struct)
            if self.state_pool is not None or self._page_counter
            else None)
        # the compiler's options for the executables, where the family
        # has some for the platform it is served on
        self._compiler_options = model.serve_compiler_options(
            jax.devices()[0].platform)

        # -- speculative decoding: the draft twin ----------------------
        self.spec_tokens = int(spec_tokens)
        self.draft_model = draft_model
        self.speculative = draft_model is not None
        if draft_params is not None and draft_model is None:
            raise ValueError('draft_params requires draft_model')
        self._draft_params = None
        self._draft_cache = None
        if self.speculative:
            if draft_params is None:
                raise ValueError('draft_model requires draft_params')
            if self.spec_tokens < 2:
                raise ValueError('spec_tokens must be >= 2 (1 is '
                                 'plain decode), got %d'
                                 % self.spec_tokens)
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    'draft vocab %d != target vocab %d -- speculative '
                    'decoding compares token ids, so the tokenizer '
                    'must be shared' % (draft_model.vocab_size,
                                        model.vocab_size))
            if draft_model.max_len < self.max_len:
                raise ValueError(
                    'draft max_len %d cannot cover the cache depth %d'
                    % (draft_model.max_len, self.max_len))
            if draft_model.tp_axis is not None:
                raise ValueError(
                    'the draft model is small by construction and '
                    'runs replicated; build it without tp_axis')
            host = draft_params
            if self.policy is not None and not self.quantized:
                from chainermn_tpu.precision import cast_floating
                host = cast_floating(host, self.policy.compute_dtype)
            self._draft_params = jax.device_put(
                host, self._draft_sharding())
            # SAME geometry as the target's: a paged draft cache is
            # addressed through the same page tables and refcounts, so
            # one allocation/CoW/eviction decision serves both
            dcache = self._new_cache(draft_model)
            self._draft_cache = jax.device_put(
                dcache, self._draft_sharding())
            self._draft_cache_struct, self._draft_cache_sig = \
                _struct_and_signature(dcache)

        # prefill executable widths: chunked paged mode compiles ONE
        # fixed-width chunk executable; otherwise one per prompt bucket
        self._prefill_widths = (
            (self.prefill_chunk,) if self.prefill_chunk is not None
            else tuple(self.prefill_edges))

        self._slots = {}      # slot id -> _Slot (decode phase)
        self._inflight = None # the decode call not read yet (_Flight)
        self._zero_prev = {}  # slot bucket -> a call's all-zero `prev`
        self._prefilling = {} # slot id -> _PrefillState (paged only)
        self._free = list(range(self.n_slots))
        self._prefill = {}    # prompt/chunk bucket -> callable
        self._decode = {}     # slot bucket -> callable
        self._copy = None     # paged CoW page-copy executable
        self._draft_prefill = {}  # speculative: draft prompt buckets
        self._draft_decode = {}   # speculative: draft slot buckets
        self._verify = {}         # speculative: k-token verify buckets
        self._draft_copy = None   # speculative paged: draft CoW copy
        self._signatures = set()
        self._lock = threading.Lock()
        self.prefill_trace_count = 0
        self.decode_trace_count = 0
        self.copy_trace_count = 0
        self.draft_trace_count = 0
        self.verify_trace_count = 0
        self.compile_count = 0
        self.prefills = 0
        self.prefill_chunks = 0
        self.cow_copies = 0
        self.decode_steps = 0
        self.decode_calls = 0        # decode calls dispatched ...
        self.decode_calls_ahead = 0  # ... before their predecessor was read
        # ... and why the others were not (SETTLE_REASONS)
        self.settles = dict.fromkeys(SETTLE_REASONS, 0)
        self.admissions = 0          # requests popped from the queue
        self.pages_allocated = 0     # by _alloc_page, evicting or not
        self.draft_steps = 0
        self.verify_steps = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.tokens_generated = 0
        self.cancelled = 0
        self._step_index = 0
        self._last_queue_depth = 0
        # the starved-device probe (a recorder live only): the result
        # of the newest call launched, the first boundary that saw it
        # ready ``(t, after, exact)``, what the next priming decode
        # call follows (``end`` | ``other``: the last settle's reason)
        # and whether a prefill went out since the last decode call
        self._last_call = None
        self._idle_since = None
        self._primes_after = 'other'
        self._admitting = False
        self._gauges = None   # the per-tick gauges, looked up once

    # -- sharding ------------------------------------------------------
    def _param_sharding(self):
        if self.plan is None:
            return jax.devices()[0]
        if self.param_specs is None:
            return self.plan.replicated()
        return self.plan.param_shardings(self.param_specs)

    def _place_params(self, params):
        """Load-time transform + placement, shared by construction
        and hot-swaps (the engine.py contract)."""
        if self.quantized:
            return jax.device_put(self.policy.quantize(params),
                                  self._param_sharding())
        host = params
        if self.policy is not None:
            from chainermn_tpu.precision import cast_floating
            host = cast_floating(host, self.policy.compute_dtype)
        return jax.device_put(host, self._param_sharding())

    def _ident(self):
        if self.label is None:
            return {}
        return {'replica': self.label, 'version': self.param_version}

    # -- live weight hot-swap (fleet roll) -----------------------------
    def swap_params(self, params, version=None, validate=True):
        """Hot-swap the served parameter tree without recompiling
        (executables are shape-keyed; ``decode_trace_count`` stays
        flat across a swap).

        REFUSED (typed :class:`~chainermn_tpu.utils.failure.
        WeightSwapError`, engine unchanged) while sequences are in
        flight: their KV caches were banked under the incumbent
        weights, and decoding them under new weights would silently
        corrupt the tail of every live generation -- the fleet drains
        the replica first, which is exactly the per-replica
        drain -> swap -> rejoin ladder.  Validation runs the
        full-slot decode executable once with the new tree over the
        (all-free) cache -- the warmup garbage-write contract -- and
        checks the sampled tokens materialize; only then is
        ``self.params`` cut over and the old buffer freed."""
        from chainermn_tpu.utils.failure import WeightSwapError
        if self._slots or self._prefilling:
            raise WeightSwapError(
                'swap requires a drained replica: %d sequence(s) '
                'still in flight hold KV state banked under the '
                'incumbent weights'
                % (len(self._slots) + len(self._prefilling)),
                version=version)
        # a call may still be in flight whose every row has gone
        # (expired, shed, ended on an EOS found a call late)
        self._settle('swap')
        # the validation decode below is not the scheduler's call
        self._last_call = self._idle_since = None
        new = self._place_params(params)
        if validate and self.n_slots in self._decode:
            exe = self._decode[self.n_slots][0]
            try:
                tok, cache = exe(new, self._cache, *self._warm_operands(
                    'decode', self.n_slots))
                tok = jax.block_until_ready(tok)
            except Exception as e:
                raise WeightSwapError(
                    'swap validation decode failed (%s: %s) -- '
                    'keeping the incumbent parameters'
                    % (type(e).__name__, e), version=version) from e
            # the donated cache was consumed either way: rebind
            self._cache = cache
        old = self.params
        self.params = new
        self.param_version = (int(version) if version is not None
                              else self.param_version + 1)
        del old  # double buffer freed after cutover
        return self.param_version

    def swap_from_checkpoint(self, path, version=None, validate=True):
        """:meth:`swap_params` fed from an elastic-resume checkpoint
        (crc-verified load against the boot tree's shape template)."""
        from chainermn_tpu.serving.engine import load_params
        return self.swap_params(
            load_params(path, self._params_template), version=version,
            validate=validate)

    def _new_cache(self, model, tp=1):
        """Zeroed cache of this engine's geometry for ``model`` (the
        target, which a plan shards ``tp`` ways, or the replicated
        draft), from the model's own constructor."""
        if not self.paged:
            return model.init_kv_cache(self.n_slots, self.max_len,
                                       int8_kv=self.int8_kv)
        # only a family that serves under a plan is told of one
        extra = {'tp': tp} if tp > 1 else {}
        if self._ring:
            extra['n_window_pages'] = self.window_pool.n_pages
        if self.state_pool is not None:
            extra['n_state_rows'] = self.state_pool.n_pages
        return model.init_paged_kv_cache(
            self.n_pages, self.page_size, int8_kv=self.int8_kv, **extra)

    def _cache_sharding(self):
        if self.plan is None:
            return jax.devices()[0]
        return self.plan.param_shardings(self._cache_specs)

    def _draft_sharding(self):
        """The draft model is always replicated: it is small by
        construction, so sharding it would trade cheap FLOPs for
        collective latency on the critical decode path."""
        if self.plan is None:
            return jax.devices()[0]
        return self.plan.replicated()

    # -- traced bodies -------------------------------------------------
    def _prepare_params(self, params):
        if self.quantized:
            return self.policy.dequantize(params)
        return params

    @staticmethod
    def _sampled(logits, counters):
        """What an executable hands back beside the cache: the greedy
        token(s) and, for a model with ``serve_counters``, those
        float32 scalars bit-cast behind them in the SAME int32 vector,
        so one read brings both to the host."""
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not counters:
            return tok
        return jnp.concatenate([
            tok.reshape(-1), jax.lax.bitcast_convert_type(
                jnp.stack(counters).astype(jnp.float32), jnp.int32)])

    def _split_sampled(self, out):
        """The host's half of :meth:`_sampled`: ``(tokens, {counter:
        value})`` of a fetched result."""
        names = self.model.serve_counters
        out = np.asarray(out)
        if not names:
            return out, {}
        values = out[out.size - len(names):].view(np.float32)
        return out[:out.size - len(names)], {
            name: float(v) for name, v in zip(names, values)}

    def _body(self, method, arrange, counter, draft=False):
        """The traced body of one executable: it bumps the engine's
        trace counter ``counter``, calls ``method`` of the target (on
        :meth:`_prepare_params`' tree) or of the draft with the
        operands in the order ``arrange`` puts them, and hands back
        ``(_sampled(...), cache)`` -- the family's ``serve_counters``
        riding the target's tokens, never the draft's."""
        step = getattr(self.draft_model if draft else self.model, method)

        def body(params, cache, *operands):
            # trace-time counter
            setattr(self, counter, getattr(self, counter) + 1)
            if not draft:
                params = self._prepare_params(params)
            out = step(params, cache, *arrange(*operands))
            counters = out[2] if len(out) > 2 and not draft else ()
            return self._sampled(out[0], counters), out[1]

        return body

    def _copy_body(self, params, cache, src, dst):
        """Copy-on-write page duplication: every leaf's page ``src``
        row copied to page ``dst`` in one donated pass.  ``params``
        rides along unused to keep the shared ``_build`` calling
        convention (one signature family, cache donated at arg 1).
        Shape-generic: the speculative engine compiles a second
        instance of this body over the DRAFT cache, so one CoW
        decision duplicates the page in both pools."""
        del params
        self.copy_trace_count += 1     # trace-time counter
        return jax.tree_util.tree_map(
            lambda leaf: leaf.at[dst].set(leaf[src]), cache)

    def _mapped(self, body, n_operands, draft=False, sampled=True):
        """Wrap a traced body in the plan's shard_map: the target's
        params sharded per spec and its cache per its spec, the
        draft's both replicated, the small int operands replicated.
        A body hands back ``(sampled, cache)``; the page copy
        (``sampled=False``) the cache alone."""
        if self.plan is None:
            return body
        from jax.sharding import PartitionSpec as P
        pspecs = cspecs = P()
        if not draft:
            cspecs = self._cache_specs
            if self.param_specs is not None:
                pspecs = self.param_specs
        return jax.shard_map(
            body, mesh=self.plan.mesh,
            in_specs=(pspecs, cspecs) + (P(),) * n_operands,
            out_specs=(P(), cspecs) if sampled else cspecs,
            check_vma=False)

    # -- compilation ---------------------------------------------------
    #: family -> (the engine's table of it, its executables' name, the
    #: phase whose operands they take, target or draft).  The decode
    #: executables, and no other, hold ``decode`` in their name; a
    #: trace reduction keys on that.
    _FAMILIES = {
        'prefill': ('_prefill', 'serve_prefill', 'prefill', False),
        'decode': ('_decode', 'serve_decode', 'decode', False),
        'copy_page': ('_copy', 'serve_page_copy', 'copy', False),
        'draft_prefill': ('_draft_prefill', 'serve_draft_prefill',
                          'prefill', True),
        'draft_decode': ('_draft_decode', 'serve_draft_decode',
                         'decode', True),
        'verify': ('_verify', 'serve_verify', 'verify', False),
        'draft_copy_page': ('_draft_copy', 'serve_draft_page_copy',
                            'copy', True),
    }

    def _operands(self, phase, bucket, draft=False):
        """THE statement of what an executable takes after ``(params,
        cache)``: ``(method, operands, arrange)`` -- the model's
        method of that name, a ``(name, shape)`` per int32 operand in
        the order the scheduler passes them, and ``arrange`` putting
        them in the method's own order.  Verify is decode with the
        token vector widened to the ``(bucket, spec_tokens)`` window;
        the page copy is the engine's own body.

        The TARGET's decode executables take two operands more, right
        behind ``tokens``: ``prev``, the previous decode call's result
        as it left the device, and ``src``, a row each; ``arrange``
        makes the call's tokens of the three (:func:`_merged`) before
        the model's method sees them, so a call can be dispatched
        while the host has not read its predecessor's tokens.  The
        draft's decode steps and the verify run inside one
        synchronous tick and take the host's tokens alone."""
        if phase == 'copy':
            return None, (('src', ()), ('dst', ())), _as_given
        if phase == 'prefill':
            tokens, length = ('tokens', (1, bucket)), ('length', ())
            if self.paged:
                return ('prefill_paged',
                        (tokens, length, ('pos0', ()),
                         ('table', (self._table_width,))),
                        lambda t, n, pos0, table: (t, n, table, pos0))
            return 'prefill', (tokens, length, ('slot', ())), _as_given
        method, tokens = {
            'decode': ('decode_step', ('tokens', (bucket,))),
            'verify': ('spec_verify',
                       ('tokens', (bucket, self.spec_tokens)))}[phase]
        positions = ('positions', (bucket,))
        if self.paged:
            # every bucket reads THROUGH the tables, so there is no
            # full-vs-compacted split
            method += '_paged'
            rest = (positions, ('tables', (bucket, self._table_width)))
            arrange = _as_given
        elif bucket == self.n_slots:
            # full bucket: every slot decodes, the cache is read IN
            # PLACE (no slots operand); rows are slots in order
            rest, arrange = (positions,), _as_given
        else:
            # compacted bucket
            rest = (('slots', (bucket,)), positions)

            def arrange(t, slots, pos):
                return t, pos, slots
        if phase != 'decode' or draft:
            return method, (tokens,) + rest, arrange
        ahead = (('prev', (bucket + len(self.model.serve_counters),)),
                 ('src', (bucket,)))
        return (method, (tokens,) + ahead + rest,
                lambda t, prev, src, *rest: arrange(
                    _merged(t, prev, src), *rest))

    def _warm_operands(self, phase, bucket=None, draft=False):
        """Operands of :meth:`_operands`' shapes for a call whose
        result nobody reads (warm-up, swap validation, a linter's
        trace): zeros -- a zero table is the scratch page, and free
        slots mask what lands in them -- but for a compacted bucket's
        ``slots``, each row its own, and a prefill's ``length``, 1."""
        return tuple(
            _WARM_VALUES.get(name, jnp.zeros)(shape, jnp.int32)
            for name, shape in self._operands(phase, bucket, draft)[1])

    def _traceable(self, phase, bucket=None, draft=False):
        """``(fn, structs)``: the mapped callable of one executable --
        what gets AOT-compiled, and what ``traceable_decode`` /
        ``traceable_verify`` hand shardlint -- and the structs of its
        operands."""
        method, operands, arrange = self._operands(phase, bucket, draft)
        if method is None:
            body = self._copy_body
        else:
            body = self._body(
                method, arrange,
                'draft_trace_count' if draft
                else phase + '_trace_count', draft)
        return (self._mapped(body, len(operands), draft,
                             sampled=method is not None),
                tuple(jax.ShapeDtypeStruct(shape, jnp.int32)
                      for _, shape in operands))

    def _build(self, family, bucket=None):
        """The one way an executable comes to be, under the lock: two
        threads asking for one bucket compile it once.  Hands back
        its ``(exe, aot)``.  Its name is the family's, whatever wraps
        the body (``shard_map`` or not): ``jit_<name>`` on the
        profiler's ``XLA Modules`` line."""
        attr, name, phase, draft = self._FAMILIES[family]
        with self._lock:
            table = getattr(self, attr)
            hit = table if bucket is None else table.get(bucket)
            if hit is not None:
                return hit
            edges = (self._prefill_widths if phase == 'prefill'
                     else self.decode_edges)
            if bucket is not None and bucket not in edges:
                raise RuntimeError('%s bucket %d is not an edge %r'
                                   % (family, bucket, list(edges)))
            fn, structs = self._traceable(phase, bucket, draft)
            args = (self._draft_cache_struct if draft
                    else self._cache_struct,) + structs
            exe = jax.jit(_named(fn, name), donate_argnums=(1,),
                          compiler_options=self._compiler_options
                          or None)
            aot = self.aot_requested
            if aot:
                exe = exe.lower(
                    self._draft_params if draft else self.params,
                    *args).compile()
            if bucket is None:       # a page copy: the one entry
                setattr(self, attr, (exe, aot))
            else:
                table[bucket] = (exe, aot)
            self._signatures.add(abstract_signature(args))
            self.compile_count += 1
            return exe, aot

    # the tick's way to an executable: a lock-free hit, else _build
    def _get_prefill(self, bucket):
        return (self._prefill.get(bucket)
                or self._build('prefill', bucket))[0]

    def _get_decode(self, bucket):
        return (self._decode.get(bucket)
                or self._build('decode', bucket))[0]

    def _get_copy(self):
        """The CoW page-copy executable (paged only): compiled once,
        shape-keyed like every bucket executable, so admission-time
        copies never retrace."""
        return (self._copy or self._build('copy_page'))[0]

    def _get_draft_prefill(self, bucket):
        return (self._draft_prefill.get(bucket)
                or self._build('draft_prefill', bucket))[0]

    def _get_draft_decode(self, bucket):
        return (self._draft_decode.get(bucket)
                or self._build('draft_decode', bucket))[0]

    def _get_verify(self, bucket):
        return (self._verify.get(bucket)
                or self._build('verify', bucket))[0]

    def _get_draft_copy(self):
        return (self._draft_copy
                or self._build('draft_copy_page'))[0]

    def _copy_page(self, src, dst):
        """Duplicate pool page ``src`` into the private page ``dst``
        (already allocated by the caller).  A speculative engine
        duplicates the page in the DRAFT cache too: both caches are
        addressed through the same page table, so a copy-on-write
        divergence must fork them together."""
        exe = self._get_copy()
        self._cache = exe(self.params, self._cache,
                          jnp.asarray(src, jnp.int32),
                          jnp.asarray(dst, jnp.int32))
        if self.speculative:
            dexe = self._get_draft_copy()
            self._draft_cache = dexe(self._draft_params,
                                     self._draft_cache,
                                     jnp.asarray(src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))
        self.cow_copies += 1
        reg = _telemetry.registry()
        if reg is not None:
            reg.counter('serve_kv_cow_total',
                        help='copy-on-write page duplications at '
                             'prefix divergence').inc()

    def traceable_decode(self, bucket=None):
        """``(fn, args)`` for ``jax.make_jaxpr`` -- the EXACT mapped
        decode callable the engine compiles for ``bucket`` (default:
        the full-slot bucket, whose cache read is in place), on zero
        operands over the real cache/params: the shardlint
        ``step:decode_forward`` target traces production code."""
        return self._traceable_args('decode', bucket or self.n_slots)

    def traceable_verify(self, bucket=None):
        """``(fn, args)`` for ``jax.make_jaxpr`` -- the EXACT mapped
        verify callable the speculative engine compiles for
        ``bucket``, on zero operands over the real cache/params: the
        shardlint ``step:spec_verify_forward`` target traces
        production code (the :meth:`traceable_decode` contract)."""
        return self._traceable_args('verify', bucket or self.n_slots)

    def _traceable_args(self, phase, bucket):
        return (self._traceable(phase, bucket)[0],
                (self.params, self._cache)
                + self._warm_operands(phase, bucket))

    def _warm(self, family, bucket=None):
        """One executable of :meth:`warmup`, inside its span: built,
        and a plain-jit one run once on :meth:`_warm_operands` over
        its own (all-free) cache to force the compile."""
        _, _, phase, draft = self._FAMILIES[family]
        where = {} if bucket is None else {'bucket': bucket}
        with _telemetry.span('serve_warmup', kind='serve',
                             phase=family, **where):
            exe, aot = self._build(family, bucket)
            if aot:
                return
            cache = '_draft_cache' if draft else '_cache'
            out = exe(self._draft_params if draft else self.params,
                      getattr(self, cache),
                      *self._warm_operands(phase, bucket, draft))
            if phase != 'copy':
                tok, out = out
                jax.block_until_ready(tok)
            setattr(self, cache, out)

    def warmup(self):
        """Compile (or cache-load) every prefill and decode bucket
        executable eagerly, largest first.  Plain-jit (``aot=False``)
        executables are forced to compile by running them on the real
        cache -- slots are all free, so the garbage they write is
        never attended (reads mask by live length).  Returns
        ``{'prefill': {bucket: aot}, 'decode': {bucket: aot}}``, and
        of a speculative engine the draft-prefill / draft-decode /
        verify families beside them."""
        widths = sorted(self._prefill_widths, reverse=True)
        edges = sorted(self.decode_edges, reverse=True)
        for bucket in widths:
            self._warm('prefill', bucket)
        for bucket in edges:
            self._warm('decode', bucket)
        if self.paged:
            self._warm('copy_page')
        families = ['prefill', 'decode']
        if self.speculative:
            for bucket in widths:
                self._warm('draft_prefill', bucket)
            for bucket in edges:
                self._warm('draft_decode', bucket)
                self._warm('verify', bucket)
            if self.paged:
                self._warm('draft_copy_page')
            families += ['draft_prefill', 'draft_decode', 'verify']
        return {family: {b: aot for b, (_, aot) in sorted(
            getattr(self, self._FAMILIES[family][0]).items())}
            for family in families}

    def guard_signature(self, args):
        """The SL007 machinery as a runtime pin (the engine.py
        contract): refuse any operand signature outside the
        precompiled prefill/decode set instead of silently
        retracing."""
        return self._check_signature(abstract_signature(args))

    def _guard_call(self, cache_sig, operands):
        """:meth:`guard_signature` for one call of an executable over
        ``(cache, *operands)``.  The cache's half of the signature (a
        leaf per layer) never changes, so it is the one taken at
        construction and a tick abstracts its few operands only."""
        return self._check_signature(
            cache_sig + abstract_signature(operands))

    def _check_signature(self, sig):
        if sig not in self._signatures:
            raise RuntimeError(
                'no-recompile guard: operand signature %r is outside '
                'the precompiled prefill/decode bucket set -- the '
                'scheduler and executables disagree on bucket '
                'geometry' % (sig,))
        return sig

    # -- the tick's own account (a recorder live only) ------------------
    def _phase(self, rec, name, **attrs):
        """One child span of the tick on ``rec``: every line of
        :meth:`_tick` runs under exactly one, so what ``serve_tick``
        leaves uncovered is a few attribute loads.  Its end is a
        boundary of :meth:`_probe`.  Call sites guard on the recorder
        (``... if rec is not None else NULL_SPAN``), so with telemetry
        off no argument is built."""
        span = rec.span(name, kind='serve', step=self._step_index,
                        **attrs, **self._ident())
        span.at_exit = self._phase_end
        return span

    def _phase_end(self, span):
        self._probe(span.recorder, span.name)

    def _probe(self, rec, after):
        """The starved-device probe's boundary: has the device finished
        the newest call the engine launched?  ``is_ready()`` does not
        block.  The FIRST boundary that sees it ready is kept, with the
        name of the span that had just ended (``client``: the time
        between two ticks)."""
        if self._idle_since is None and self._last_call is not None \
                and self._last_call.is_ready():
            self._idle_since = (rec.now(), after, 0)

    def _launching(self, rec, t_launch, cause):
        """Called where a call is about to go out, ``t_launch`` its
        dispatch span's start: where a boundary saw the predecessor
        done, ONE ``device_idle`` record from that boundary to the
        launch.

        The record is a LOWER bound on the time the device had nothing
        to run: the launch's own latency and the time before the first
        boundary that noticed are left out.  ``exact`` = 1 where its
        start is the return of a read that had to wait for the call
        (the return IS the call's end).  ``cause`` is ``admission`` (a
        prefill, or the first decode call after one, whether it primes
        the pipeline or goes out ahead of a call in flight, a settle
        between them or none), ``end`` (a priming call after a settle
        for a foreseen end or a change of bucket, no prefill since the
        last decode call), ``steady`` (a call that went out ahead and
        still found its predecessor done) or ``other`` (a drained
        table, a speculative tick, ``swap_params``)."""
        idle, self._idle_since = self._idle_since, None
        if idle is not None:
            t0, after, exact = idle
            rec.interval('device_idle', t0, t_launch, kind='serve',
                         after=after, cause=cause, exact=exact,
                         step=self._step_index, **self._ident())

    def _waited(self, rec, wait, result):
        """The end of a ``serve_*_wait`` span, a probe boundary: a read
        that BLOCKED on the newest call launched returned when the
        device finished it, so the device is idle from that instant,
        exactly."""
        if self._last_call is result \
                and wait.t1 - wait.t0 > _BLOCKED_S:
            self._idle_since = (wait.t1, wait.name, 1)
        else:
            self._probe(rec, wait.name)

    # -- the continuous-batching scheduler -----------------------------
    def _expire(self, now, force=0):
        """Shed active requests whose deadline passed (or the
        ``force`` oldest, for the serve_cancel chaos site): typed
        ``OverloadError(reason='deadline')`` NOW, slot freed for
        refill at the next step's admission."""
        doomed = []
        for sid, slot in self._slots.items():
            dl = slot.request.deadline
            if dl is not None and now > dl:
                doomed.append(sid)
        if force:
            for sid in sorted(
                    (s for s in self._slots if s not in doomed),
                    key=lambda s: self._slots[s].request.t_submit
            )[:force]:
                doomed.append(sid)
        for sid in doomed:
            slot = self._slots.pop(sid)
            # the row may be in the decode call in flight, which
            # writes these pages (its token is dropped at the read):
            # safe because whoever gets them next writes them in a
            # LATER call, ordered behind it on the one device stream,
            # and the host never touches a page's contents
            self._release_pages(slot.pages, slot.ring, slot.state_row)
            self._free.append(sid)
            self.cancelled += 1
            slot.request.set_error(OverloadError(
                'deadline expired mid-generation after %d tokens'
                % len(slot.generated), reason='deadline'))
            _telemetry.event('serve_cancel', kind='serve', slot=sid,
                             tokens=len(slot.generated))
            record_shed('deadline',
                        request_id=slot.request.request_id,
                        queue_depth=self._last_queue_depth,
                        slot=sid, tokens=len(slot.generated),
                        **self._ident())
        # mid-prefill expiry (paged): a chunked prompt can outlive its
        # deadline between chunks
        for sid in [s for s, st in self._prefilling.items()
                    if st.request.deadline is not None
                    and now > st.request.deadline]:
            state = self._prefilling.pop(sid)
            self._release_pages(state.pages, state.ring,
                                state.state_row)
            self._free.append(sid)
            self.cancelled += 1
            doomed.append(sid)
            state.request.set_error(OverloadError(
                'deadline expired mid-prefill at position %d'
                % state.pos, reason='deadline'))
            _telemetry.event('serve_cancel', kind='serve', slot=sid,
                             tokens=0)
            record_shed('deadline',
                        request_id=state.request.request_id,
                        queue_depth=self._last_queue_depth,
                        slot=sid, position=state.pos, **self._ident())
        return len(doomed)

    # -- paged-mode page accounting ------------------------------------
    def _release_pages(self, pages, ring=(), state_row=0):
        """Host bookkeeping only: a released page may still be written
        by the decode call in flight (see the call sites)."""
        if pages:
            for page in pages:
                self.pool.release(page)
        for page in ring:
            self.window_pool.release(page)
        if state_row:
            self.state_pool.release(state_row)

    def _grow_ring(self, ring, last_page):
        """Window pages of a sequence whose newest position lies in
        logical page ``last_page``: one ring column per page until the
        ring is full, then nothing, ever (nothing at all for a model
        without window layers, whose ring is 0).  The window pool
        holds a full ring for every slot, so it cannot run dry."""
        while len(ring) < min(last_page + 1, self._ring):
            ring.append(self.window_pool.alloc())

    def _alloc_page(self):
        """One free page, LRU-evicting banked prefixes when the pool
        is dry; ``None`` only when nothing is evictable either (the
        caller sheds typed)."""
        page = self.pool.alloc()
        while page is None and self._prefix_index is not None \
                and self._prefix_index.evict(1):
            page = self.pool.alloc()
        if page is not None:
            self.pages_allocated += 1
        return page

    def _paging(self):
        """Pages allocated and index references dropped so far: a
        prep span's ``pages`` / ``evicted`` are their growth under
        it."""
        idx = self._prefix_index
        return (self.pages_allocated,
                idx.evictions if idx is not None else 0)

    def _set_paging(self, span, before):
        """``pages`` / ``evicted`` on ``span``, each only where it
        is not 0 (as ``admitted`` on ``serve_tick``: a reader's mean
        over the spans that have it is pages a span that paged)."""
        pages, evicted = (now - was for now, was
                          in zip(self._paging(), before))
        if pages:
            span.set(pages=pages)
        if evicted:
            span.set(evicted=evicted)

    def _table_array(self, pages, ring=(), state_row=0, out=None):
        """One sequence's table operand, ``[full table | ring | state
        row]`` (into ``out``, a zeroed row of the decode step's
        tables)."""
        table = (np.zeros((self._table_width,), np.int32)
                 if out is None else out)
        table[:len(pages)] = pages
        if ring:
            table[self.pages_per_seq:self.pages_per_seq + len(ring)] \
                = ring
        if state_row:
            table[-1] = state_row
        return table

    def _shed_paged(self, req, pages, where, ring=(), state_row=0):
        """Typed shed when the page pool is exhausted (the paged twin
        of queue_full): pages retained so far go back, the client
        gets ``OverloadError(reason='kv_pages')``."""
        self._release_pages(pages, ring, state_row)
        self.cancelled += 1
        record_shed('kv_pages', request_id=req.request_id,
                    queue_depth=self._last_queue_depth, where=where,
                    **self._ident())
        req.set_error(OverloadError(
            'KV page pool exhausted (%d/%d pages live, nothing '
            'evictable) during %s; retry with backoff'
            % (self.pool.in_use(), self.pool.n_pages, where),
            reason='kv_pages'))

    def _admit_budget(self):
        """Admissions this tick: every free slot, unless the fleet
        degradation ladder capped it (``admit_cap``)."""
        if self.admit_cap is None:
            return len(self._free)
        return min(len(self._free), max(0, int(self.admit_cap)))

    def _admit(self, queue, now, clock):
        """Refill free slots from the queue: one PREFILL per request
        (bucketed by prompt length), TTFT recorded when its first
        token lands.  With telemetry on, each admitted request gets
        its trace stages recorded: ``queue_wait`` (admission stamp ->
        pop), ``admit_wait`` (pop -> the scheduler reaches THIS
        request: the prefills of the requests popped with it, counted
        in ``behind``), ``bucket_pack`` (-> prefill dispatch, carrying
        the prompt bucket + pad fraction) and ``prefill`` (-> first
        token), each starting where the previous ended."""
        rec = _telemetry.live()
        with (self._phase(rec, 'serve_admit') if rec is not None
              else NULL_SPAN):
            if self.paged:
                # no executable runs in here but the rare copy-on-write
                # page copy: queue pop, prefix lookup, page allocation
                self._admit_paged(queue, now, clock)
            else:
                # the slot cache prefills where it admits
                self._admit_slots(queue, clock, rec)

    def _admit_slots(self, queue, clock, rec):
        reg = _telemetry.registry()
        ident = self._ident()
        popped = queue.pop(self._admit_budget())
        self.admissions += len(popped)
        t_pop = rec.now() if rec is not None else None
        for behind, req in enumerate(popped):
            sid = self._free.pop(0)
            prompt = req.prompt
            if rec is not None:
                t0 = req.t_trace0
                if t0 is None:   # telemetry enabled mid-flight
                    t0 = t_pop - (clock() - req.t_submit)
                rec.child_span(req.request_id, 'queue_wait', t0,
                               t_pop, seq=req.seq, **ident)
                t_reach = rec.now()
                rec.child_span(req.request_id, 'admit_wait', t_pop,
                               t_reach, behind=behind, **ident)
            bucket = bucket_of(prompt.size, self.prefill_edges)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :prompt.size] = prompt
            exe = self._get_prefill(bucket)
            args = (jnp.asarray(tokens),
                    jnp.asarray(prompt.size, jnp.int32),
                    jnp.asarray(sid, jnp.int32))
            self._guard_call(self._cache_sig, args)
            t_pf0 = rec.now() if rec is not None else None
            if rec is not None:
                rec.child_span(
                    req.request_id, 'bucket_pack', t_reach, t_pf0,
                    bucket=bucket, pad_fraction=round(
                        (bucket - prompt.size) / float(bucket), 4),
                    **ident)
            if _chaos._active is not None:
                _chaos.on_serve_slow(
                    self.param_version != self._boot_version)
            with (self._phase(rec, 'serve_prefill', bucket=bucket,
                              slot=sid, iteration=self._step_index)
                  if rec is not None else NULL_SPAN):
                tok = int(self._prefill_call(rec, exe, args))
            if self.speculative:
                # the draft prefills the same prompt into ITS cache at
                # the same slot (its proposals need the prompt's K/V);
                # the draft's own first-token logits are discarded --
                # the target's token is authoritative
                self._draft_prefill_call(rec, bucket, args, slot=sid)
            self.prefills += 1
            self.tokens_generated += 1
            t_first = clock()
            t_first_tele = None
            if rec is not None:
                t_first_tele = rec.now()
                rec.child_span(req.request_id, 'prefill', t_pf0,
                               t_first_tele, bucket=bucket, slot=sid,
                               prompt_tokens=int(prompt.size),
                               **ident)
            if reg is not None:
                reg.histogram(
                    'serve_ttft_seconds',
                    help='submit-to-first-token latency (s)'
                ).observe(t_first - req.t_submit)
                reg.counter('serve_tokens_total',
                            help='generated tokens').inc()
            req.notify_tokens([tok])
            if self.eos_id is not None and tok == self.eos_id \
                    or req.max_new_tokens == 1:
                req.set_result([tok])
                self._free.append(sid)
                if rec is not None:
                    rec.event('complete', kind='request',
                              request_id=req.request_id, tokens=1,
                              slot=sid, **ident)
                continue
            self._slots[sid] = _Slot(req, prompt.size,
                                     req.max_new_tokens - 1, tok,
                                     t_first,
                                     t_stage_end=t_first_tele)

    def _prefill_call(self, rec, exe, args):
        """One prefill call inside its ``serve_prefill`` span, read
        back before the tick goes on: the dispatch and the wait for
        its result are a child span each.  Hands back the sampled
        vector."""
        with (rec.span('serve_prefill_dispatch', kind='serve',
                       step=self._step_index)
              if rec is not None else NULL_SPAN) as go:
            if rec is not None:
                self._launching(rec, go.t0, 'admission')
            tok, cache = exe(self.params, self._cache, *args)
            self._cache = cache
        with (rec.span('serve_prefill_wait', kind='serve',
                       step=self._step_index)
              if rec is not None else NULL_SPAN) as wait:
            jax.block_until_ready(tok)
        if rec is not None:
            self._last_call = tok
            self._admitting = True
            self._waited(rec, wait, tok)
        return tok

    def _draft_prefill_call(self, rec, width, args, **where):
        """The draft's prefill of the same tokens into ITS cache, read
        back before the tick goes on (``serve_draft``)."""
        dexe = self._get_draft_prefill(width)
        self._guard_call(self._draft_cache_sig, args)
        with (self._phase(rec, 'serve_draft', stage='prefill',
                          bucket=width, iteration=self._step_index,
                          **where)
              if rec is not None else NULL_SPAN) as span:
            if rec is not None:
                self._launching(rec, span.t0, 'other')
            dtok, dcache = dexe(self._draft_params,
                                self._draft_cache, *args)
            self._draft_cache = dcache
            if rec is not None:
                self._last_call = dtok
            jax.block_until_ready(dtok)

    def _admit_paged(self, queue, now, clock):
        """Paged admission: claim a slot id, walk the prefix index for
        the longest banked prefix (retaining shared FULL pages; a
        partially-covered boundary page is copy-on-write-duplicated
        ONCE, here), and park the request in ``self._prefilling`` --
        the actual prefill work happens chunk-by-chunk in
        :meth:`_prefill_tick`, interleaved with decode steps."""
        rec = _telemetry.live()
        reg = _telemetry.registry()
        ident = self._ident()
        group = self._prefix_index is not None
        popped = queue.pop(self._admit_budget(), group_prefix=group)
        self.admissions += len(popped)
        for req in popped:
            sid = self._free.pop(0)
            prompt = req.prompt
            t_pop = rec.now() if rec is not None else None
            if rec is not None:
                t0 = req.t_trace0
                if t0 is None:   # telemetry enabled mid-flight
                    t0 = t_pop - (clock() - req.t_submit)
                rec.child_span(req.request_id, 'queue_wait', t0,
                               t_pop, seq=req.seq, **ident)
            pages, matched = [], 0
            if self._prefix_index is not None:
                shared, tail_page, tail_len = \
                    self._prefix_index.lookup(prompt)
                # always recompute >= 1 prompt token: the final chunk
                # must produce first-token logits, so cap the match at
                # size-1 and demote an over-covering full page to a
                # copy-on-write tail candidate
                max_match = prompt.size - 1
                dropped = None
                while len(shared) * self.page_size > max_match:
                    dropped = shared.pop()
                for page in shared:
                    self.pool.retain(page)
                    pages.append(page)
                matched = len(shared) * self.page_size
                if dropped is not None:
                    tail_page, tail_len = dropped, self.page_size
                tail_use = (min(tail_len, max_match - matched)
                            if tail_page is not None else 0)
                if tail_use > 0:
                    dst = self._alloc_page()
                    if dst is None:
                        self._shed_paged(req, pages, 'admission')
                        self._free.append(sid)
                        continue
                    self._copy_page(tail_page, dst)
                    pages.append(dst)
                    matched += tail_use
                if reg is not None and matched:
                    reg.counter(
                        'serve_prefix_hits_total',
                        help='admissions that reused a banked '
                             'prompt prefix').inc()
                    reg.counter(
                        'serve_prefix_tokens_total',
                        help='prompt tokens served from banked '
                             'prefix pages').inc(matched)
            # the state pool holds a row for every slot: never dry
            self._prefilling[sid] = _PrefillState(
                req, pages, matched, matched, t_pop=t_pop,
                t_stage_end=t_pop,
                state_row=(self.state_pool.alloc()
                           if self.state_pool is not None else 0))

    def _prefill_tick(self, clock):
        """Advance every mid-prefill sequence by ONE chunk (SARATHI
        schedule: chunks interleave with decode ticks so a long
        prompt's compute cannot monopolize the device and blow up
        inter-token latency for live sequences).  Without
        ``prefill_chunk`` configured the whole remaining prompt runs
        as a single chunk (bucketed like slot-mode prefill).

        The final chunk -- the only one producing first-token logits
        -- emits the ``prefill`` trace stage (so TTFT accounting is
        unchanged); intermediate chunks emit ``prefill_chunk`` spans
        the SLO monitor ignores.  A finished prompt's pages are banked
        into the prefix index before the sequence moves to decode.

        Three spans a sequence: ``serve_prefill_prep`` (pages, the
        operands and their upload), ``serve_prefill`` (the call:
        ``serve_prefill_dispatch`` and ``serve_prefill_wait`` under
        it) and, behind a final chunk, ``serve_emit`` with ``first=1``
        (the first token's notify, the prefix insert, the slot).  The
        sequences are served one after the other, each read back
        before the next: a request's ``admit_wait`` stage runs from
        its pop to the moment this loop reaches it, ``behind`` the
        prefill calls the tick ran before it."""
        rec = _telemetry.live()
        reg = _telemetry.registry()
        ident = self._ident()
        worked = False
        calls = 0
        for sid in sorted(self._prefilling):
            st = self._prefilling[sid]
            req = st.request
            prompt = req.prompt
            with (self._phase(rec, 'serve_prefill_prep', slot=sid)
                  if rec is not None else NULL_SPAN) as prep:
                if rec is not None and st.chunks == 0:
                    t_reach = rec.now()
                    rec.child_span(req.request_id, 'admit_wait',
                                   st.t_stage_end, t_reach,
                                   behind=calls, **ident)
                    st.t_stage_end = t_reach
                remaining = prompt.size - st.pos
                if self.prefill_chunk is not None:
                    width = self.prefill_chunk
                else:
                    width = bucket_of(remaining, self.prefill_edges)
                n = min(width, remaining)
                last_page = (st.pos + n - 1) // self.page_size
                dry = False
                paging = self._paging() if rec is not None else None
                while len(st.pages) <= last_page:
                    page = self._alloc_page()
                    if page is None:
                        dry = True
                        break
                    st.pages.append(page)
                if rec is not None:
                    self._set_paging(prep, paging)
                if dry:
                    del self._prefilling[sid]
                    self._shed_paged(req, st.pages, 'prefill', st.ring,
                                     st.state_row)
                    self._free.append(sid)
                    continue
                self._grow_ring(st.ring, last_page)
                worked = True
                tokens = np.zeros((1, width), np.int32)
                tokens[0, :n] = prompt[st.pos:st.pos + n]
                exe = self._get_prefill(width)
                args = (jnp.asarray(tokens),
                        jnp.asarray(n, jnp.int32),
                        jnp.asarray(st.pos, jnp.int32),
                        jnp.asarray(self._table_array(
                            st.pages, st.ring, st.state_row)))
                self._guard_call(self._cache_sig, args)
                if rec is not None and st.chunks == 0:
                    t_c0 = rec.now()
                    rec.child_span(
                        req.request_id, 'bucket_pack', st.t_stage_end,
                        t_c0, bucket=width, pad_fraction=round(
                            (width - n) / float(width), 4),
                        prefix_tokens=st.matched, **ident)
                    st.t_stage_end = t_c0
                if _chaos._active is not None:
                    _chaos.on_serve_slow(
                        self.param_version != self._boot_version)
            chunk = st.chunks
            with (self._phase(rec, 'serve_prefill', bucket=width,
                              slot=sid, tokens=n, chunk=chunk,
                              pos=st.pos, iteration=self._step_index)
                  if rec is not None else NULL_SPAN) as span:
                tok, counters = self._split_sampled(
                    self._prefill_call(rec, exe, args))
                span.set(**counters)
                calls += 1
                st.pos += n
                st.chunks += 1
                self.prefill_chunks += 1
                final = st.pos >= prompt.size
                if rec is not None and not final:
                    now_tele = rec.now()
                    rec.child_span(req.request_id, 'prefill_chunk',
                                   st.t_stage_end, now_tele,
                                   bucket=width, slot=sid, chunk=chunk,
                                   pos=st.pos, **ident)
                    st.t_stage_end = now_tele
            if self.speculative:
                # same chunk, same pages, into the draft cache: banked
                # prefix pages stay valid for BOTH caches, so a future
                # prefix hit serves the draft too
                self._draft_prefill_call(rec, width, args, slot=sid,
                                         chunk=chunk)
            if not final:
                continue
            with (self._phase(rec, 'serve_emit', first=1, slot=sid)
                  if rec is not None else NULL_SPAN):
                tok = int(tok.reshape(-1)[0])
                del self._prefilling[sid]
                self.prefills += 1
                self.tokens_generated += 1
                t_first = clock()
                t_first_tele = None
                if rec is not None:
                    t_first_tele = rec.now()
                    rec.child_span(req.request_id, 'prefill',
                                   st.t_stage_end, t_first_tele,
                                   bucket=width, slot=sid,
                                   prompt_tokens=int(prompt.size),
                                   chunks=st.chunks,
                                   prefix_tokens=st.matched, **ident)
                if reg is not None:
                    reg.histogram(
                        'serve_ttft_seconds',
                        help='submit-to-first-token latency (s)'
                    ).observe(t_first - req.t_submit)
                    reg.counter('serve_tokens_total',
                                help='generated tokens').inc()
                if self._prefix_index is not None:
                    n_cover = -(-prompt.size // self.page_size)
                    self._prefix_index.insert(prompt,
                                              st.pages[:n_cover])
                req.notify_tokens([tok])
                if self.eos_id is not None and tok == self.eos_id \
                        or req.max_new_tokens == 1:
                    req.set_result([tok])
                    self._release_pages(st.pages, st.ring,
                                        st.state_row)
                    self._free.append(sid)
                    if rec is not None:
                        rec.event('complete', kind='request',
                                  request_id=req.request_id, tokens=1,
                                  slot=sid, **ident)
                    continue
                self._slots[sid] = _Slot(req, prompt.size,
                                         req.max_new_tokens - 1, tok,
                                         t_first,
                                         t_stage_end=t_first_tele,
                                         pages=st.pages, ring=st.ring,
                                         state_row=st.state_row)
        return worked

    def _decode_operands(self, pend, rec=None):
        """What the next decode call is called with: the rows (slot
        ids, padded to the smallest slot-count bucket), the
        :class:`_Slot` each holds, the live count, the bucket, its
        executable and the uploaded operands -- or None when no
        sequence is live (growing the page tables may shed them all).
        ``pend`` is the call in flight, if any: a row that was in it
        takes its token from that call's vector on the device
        (``src``), every other row the host's newest.  Nothing here
        advances a slot (:meth:`_decode_once` does, at dispatch), so
        it may be asked twice in a tick: where ``pend`` is of another
        bucket its vector cannot feed this call, nothing is uploaded
        and the operands come back None -- settle, and ask again.
        With a recorder live (``rec``) the row loops are boundaries of
        the starved-device probe too: this is the longest host phase
        of a steady tick, and the call in flight may end inside it."""
        if self.paged:
            # grow page tables across page boundaries BEFORE dispatch
            # (a sequence whose next token starts a new page gets one
            # allocated now; a dry pool sheds typed)
            for sid in sorted(self._slots):
                slot = self._slots[sid]
                need = slot.position // self.page_size
                while len(slot.pages) <= need:
                    page = self._alloc_page()
                    if page is None:
                        del self._slots[sid]
                        # the row may be in the call in flight, which
                        # writes these pages: whoever gets them next
                        # writes them in a LATER call, ordered behind
                        # it on the one device stream; the host never
                        # touches a page's contents
                        self._shed_paged(slot.request, slot.pages,
                                         'decode', slot.ring,
                                         slot.state_row)
                        self._free.append(sid)
                        break
                    slot.pages.append(page)
                else:           # not shed: its window pages too
                    self._grow_ring(slot.ring, need)
        if not self._slots:
            return None
        active = sorted(self._slots)
        k = len(active)
        bucket = bucket_of(k, self.decode_edges)
        if self.paged:
            # paged rows are positional (the page table IS the
            # addressing); pad rows carry all-zero tables, so their
            # garbage token lands on the scratch page
            rows = active + [None] * (bucket - k)
        elif bucket == self.n_slots:
            # the full-slot executable reads the cache IN PLACE (no
            # slots operand): row i IS slot i, so rows must be every
            # slot in id order even when k < n_slots -- an inactive
            # row writes a garbage token at position 0 of its FREE
            # slot, overwritten by that slot's next prefill
            rows = list(range(self.n_slots))
        else:
            # compacted bucket: pad with FREE slots (guaranteed
            # available: bucket < n_slots and only k are active) --
            # same garbage-write-to-a-free-slot contract as above
            rows = active + self._free[:bucket - k]
        held = [self._slots.get(sid) for sid in rows]
        if pend is not None and pend.bucket != bucket:
            return rows, held, k, bucket, None, None
        tokens = np.zeros((bucket,), np.int32)
        src = np.full((bucket,), -1, np.int32)
        positions = np.zeros((bucket,), np.int32)
        row_of = pend.row_of if pend is not None else {}
        if rec is not None:
            self._probe(rec, 'serve_decode_prep')
        for i, slot in enumerate(held):
            if slot is None:
                continue
            positions[i] = slot.position
            j = row_of.get(slot)
            if j is None:
                # a row a prefill just admitted, the first call, any
                # call after a settle: the host has its newest token
                tokens[i] = slot.generated[-1]
            else:
                # rows are sorted slots and shift when one ends or
                # fills: a gather, not an identity
                src[i] = j
        exe = self._get_decode(bucket)
        if self.paged:
            tables = np.zeros((bucket, self._table_width), np.int32)
            for i, slot in enumerate(held):
                if slot is not None:
                    self._table_array(slot.pages, slot.ring,
                                      slot.state_row, out=tables[i])
                if rec is not None and not i % 8:
                    self._probe(rec, 'serve_decode_prep')
            rest = (positions, tables)
        elif bucket == self.n_slots:
            rest = (positions,)
        else:
            rest = (np.asarray(rows, np.int32), positions)
        # ONE transfer for the host's operands; ``prev`` is on the
        # device already
        if rec is not None:
            self._probe(rec, 'serve_decode_prep')
        tokens, src, *rest = jax.device_put((tokens, src) + rest)
        args = (tokens,
                pend.toks if pend is not None else self._no_prev(bucket),
                src, *rest)
        self._guard_call(self._cache_sig, args)
        return rows, held, k, bucket, exe, args

    def _no_prev(self, bucket):
        """The ``prev`` operand of a call that takes every token from
        the host (``src`` all -1): zeros of a decode result's shape,
        made once a bucket."""
        zeros = self._zero_prev.get(bucket)
        if zeros is None:
            zeros = self._zero_prev[bucket] = jnp.zeros(
                (bucket + len(self.model.serve_counters),), jnp.int32)
        return zeros

    def _decode_once(self, clock):
        """One decode tick, pipelined ONE call deep: call t+1 is
        dispatched with call t's tokens still on the device (the
        executable takes them from call t's result, :func:`_merged`),
        and only then is call t's vector read and emitted, so the host
        prepares, reads and emits under a running call.  A slot's
        position, its remaining count and its page table advance at
        DISPATCH; ``tokens_generated`` and ``decode_steps`` count at
        emit.  Three spans split the tick: ``serve_decode_prep``
        (numpy operands and their upload), ``serve_decode`` (under it
        ``serve_decode_dispatch``, the launch of call t+1, then
        ``serve_decode_wait``, the wait for call t's vector),
        ``serve_emit`` (the per-slot loop over call t's tokens).

        The call in flight is SETTLED first (read and emitted, nothing
        dispatched ahead of it) where a row of it ends in it by length
        -- then the tick hands back, the caller refills the slot, and
        the next tick admits, prefills and dispatches with the new row
        in the call: a refilled slot misses no call and no prefill
        queues behind a decode call dispatched ahead of it -- and
        where the next call's bucket is another (its ``prev`` has the
        other bucket's shape).  An end the host cannot foresee (an
        EOS) is found when its call is read, one call late: the row's
        token of the call already in flight is dropped.  Every
        ``serve_decode`` span that did not dispatch ahead says why
        (``reason``, one of :data:`SETTLE_REASONS`)."""
        pend = self._inflight
        if pend is not None and pend.ends:
            self._settle('end', clock)
            return
        rec = _telemetry.live()
        operands, attrs = self._decode_prep(rec, pend)
        if pend is not None and (operands is None
                                 or operands[-1] is None):
            # occupancy crossed a decode edge (or every row was shed)
            self._settle('drained' if operands is None else 'bucket',
                         clock)
            pend = None
            operands, attrs = self._decode_prep(rec, None)
        if operands is None:
            return
        rows, held, k, bucket, exe, args = operands
        if _chaos._active is not None:
            _chaos.on_serve_slow(
                self.param_version != self._boot_version)
        t0 = clock()
        # one span a launched call: it carries ``ran_ahead`` of the
        # call it dispatches and every other attribute of the call
        # whose vector it READS (a tick later; a settle's span reads
        # and dispatches nothing, the span after it the reverse)
        with (self._decode_span(rec, pend) if rec is not None
              else NULL_SPAN) as span:
            with (rec.span('serve_decode_dispatch', kind='serve',
                           step=self._step_index)
                  if rec is not None else NULL_SPAN) as go:
                if rec is not None:
                    self._launching(
                        rec, go.t0,
                        'admission' if self._admitting
                        else 'steady' if pend is not None
                        else self._primes_after)
                    self._admitting = False
                toks, cache = exe(self.params, self._cache, *args)
                # rebound BEFORE the wait (here and at every call of
                # the tick): the donated cache is a husk per layer,
                # and they die while the device runs, not after it
                self._cache = cache
                toks.copy_to_host_async()
                # the host's handles on the uploaded operands go here,
                # under the dispatch, not at the tick's return
                del operands, args
            if rec is not None:
                self._last_call = toks
            last = [False] * bucket
            for i, slot in enumerate(held):
                if slot is not None:
                    slot.position += 1
                    slot.remaining -= 1
                    last[i] = slot.remaining == 0
            self._inflight = _Flight(toks, rows, held, last, k, bucket,
                                     attrs)
            self.decode_calls += 1
            if pend is not None:
                self.decode_calls_ahead += 1
                read = self._read(pend, span, rec)
            else:
                self.settles['prime'] += 1
        if pend is not None:
            self._emit(pend, read, t0, clock)

    def _decode_prep(self, rec, pend):
        """``serve_decode_prep``: :meth:`_decode_operands` and, with a
        recorder live, what the call's span will say its rows attend
        (taken before the slots advance; the instrument's own work,
        booked with the host's)."""
        with (self._phase(rec, 'serve_decode_prep') if rec is not None
              else NULL_SPAN) as prep:
            paging = self._paging() if rec is not None else None
            operands = self._decode_operands(pend, rec)
            if rec is not None:
                self._set_paging(prep, paging)
            attrs = None
            if rec is not None and operands is not None \
                    and operands[-1] is not None:
                self._tick_gauges(rec)['active_slots'].set(
                    operands[2])
                attrs = self._attended(
                    [slot.position + 1 for slot in operands[1]
                     if slot is not None], operands[3])
        return operands, attrs

    def _settle(self, reason, clock=time.monotonic):
        """Read the call in flight, if there is one, and emit its
        tokens: after it nothing of the scheduler's is on the device
        and every slot's newest token is the host's.  ``reason`` (of
        :data:`SETTLE_REASONS`) is why nothing was dispatched ahead of
        it."""
        with self._lock:
            pend, self._inflight = self._inflight, None
        if pend is None:
            return
        self.settles[reason] += 1
        rec = _telemetry.live()
        t0 = clock()
        with (self._decode_span(rec, None, reason)
              if rec is not None else NULL_SPAN) as span:
            read = self._read(pend, span, rec)
        if rec is not None:
            self._primes_after = ('end' if reason in ('end', 'bucket')
                                  else 'other')
        self._emit(pend, read, t0, clock)

    def _decode_span(self, rec, pend, reason='prime'):
        """The ``serve_decode`` span of a call that dispatches behind
        ``pend`` (ahead of its read), of one that primes the pipeline
        (``pend`` None) or, with a ``reason`` given, of a settle."""
        attrs = {}
        if reason == 'prime':
            attrs['ran_ahead'] = int(pend is not None)
        if pend is None:
            attrs['reason'] = reason
        return self._phase(rec, 'serve_decode',
                           iteration=self._step_index,
                           n_slots=self.n_slots,
                           queue_depth=self._last_queue_depth, **attrs)

    def _read(self, pend, span, rec):
        """Wait for a dispatched call's vector (its copy to the host
        began at dispatch) and split it; ``span`` gets what its
        readers take of a decode call.  The wait is
        ``serve_decode_wait``: from asking for the vector to having
        it."""
        with (rec.span('serve_decode_wait', kind='serve',
                       step=self._step_index)
              if rec is not None else NULL_SPAN) as wait:
            out = np.asarray(pend.toks)
        if rec is not None:
            self._waited(rec, wait, pend.toks)
        toks, counters = self._split_sampled(out)
        span.set(active_slots=pend.k, bucket=pend.bucket, **counters,
                 **(pend.attrs or {}))
        return toks

    def _emit(self, pend, toks, t0, clock):
        """A read call's tokens to their requests; finished rows
        resolve and free their slots (refilled at the NEXT step).  A
        row whose slot is no longer the one it was dispatched for is
        dropped: its request is dead (expired, shed) or ended a call
        earlier.  All of it is the ``serve_emit`` span."""
        rec = _telemetry.live()
        with (self._phase(rec, 'serve_emit') if rec is not None
              else NULL_SPAN):
            reg = _telemetry.registry()
            ident = self._ident()
            now = clock()
            now_tele = rec.now() if rec is not None else None
            itl = (reg.histogram('serve_intertoken_seconds',
                                 help='per-sequence gap between '
                                      'consecutive tokens (s)')
                   if reg is not None else None)
            emitted = 0
            for i, sid in enumerate(pend.rows):
                slot = pend.slots[i]
                if slot is None or self._slots.get(sid) is not slot:
                    continue   # a pad row, or a token nobody is owed
                tok = int(toks[i])
                slot.generated.append(tok)
                slot.request.notify_tokens([tok])
                emitted += 1
                if itl is not None:
                    itl.observe(now - slot.t_last_token)
                slot.t_last_token = now
                if rec is not None:
                    # one decode stage per live slot per tick, starting at
                    # the request's previous stage end: the span absorbs
                    # any scheduler wait between ticks (a neighbor's slow
                    # prefill IS latency this request paid), which is
                    # exactly what makes the stage budgets sum to the
                    # end-to-end latency
                    t_prev = slot.t_stage_end
                    if t_prev is None:
                        t_prev = now_tele - (now - t0)
                    rec.child_span(slot.request.request_id, 'decode',
                                   t_prev, now_tele, slot=sid,
                                   step=self._step_index,
                                   token_index=len(slot.generated) - 1,
                                   **ident)
                    slot.t_stage_end = now_tele
                if pend.last[i] or (self.eos_id is not None
                                    and tok == self.eos_id):
                    slot.request.set_result(slot.generated)
                    if rec is not None:
                        rec.event('complete', kind='request',
                                  request_id=slot.request.request_id,
                                  tokens=len(slot.generated), slot=sid,
                                  **ident)
                    # after an EOS the row is in the call in flight
                    # too, which writes these pages: safe because
                    # whoever gets them next writes them in a LATER
                    # call, ordered behind it on the one device stream
                    self._release_pages(slot.pages, slot.ring,
                                        slot.state_row)
                    del self._slots[sid]
                    self._free.append(sid)
            self.decode_steps += 1
            self.tokens_generated += emitted
            if reg is not None:
                reg.histogram('serve_decode_seconds',
                              help='per-decode-step wall time (s)'
                              ).observe(now - t0)
                reg.counter('serve_tokens_total',
                            help='generated tokens').inc(emitted)

    def _attended(self, live, n_rows):
        """Positions a decode call's rows attend, by layer kind, from
        the live rows' lengths: every live position in a full layer,
        at most the window in a window layer (what the kernels'
        roofline shares count bytes from)."""
        out = dict(self._kv_lanes, kv_positions=sum(live))
        if self._window is not None:
            out['kv_window_positions'] = sum(
                min(n, self._window) for n in live)
        if self.paged:
            # how the paged decode kernel's grid engaged: the pages its
            # copies fetched over the steps it took (a pad row attends
            # position 0 of the scratch page), every layer's call
            tp = (self.plan.mesh.shape[self.plan.model_axis]
                  if self.plan is not None else 1)
            lengths = live + [1] * (n_rows - len(live))
            out['kv_pages_read'], out['kv_grid_steps'] = (
                self.model.decode_paged_grid(
                    self._cache_struct, lengths, self.pages_per_seq,
                    self._ring, tp=tp))
        return out

    def _spec_once(self, clock):
        """One SPECULATIVE tick over every active slot: ``spec_tokens``
        draft-decode steps propose a window, ONE target verify
        executable scores all of it, and each slot commits the longest
        prefix where draft and target argmax agree PLUS the target's
        own next token (the correction at the first divergence, the
        bonus on full acceptance) -- so every tick emits 1..k tokens
        for one expensive target pass, and a rejection at draft
        position 0 degenerates to exactly the plain decode step.

        Rollback is a position rewind: rejected window positions'
        K/V (and int8 scales) in BOTH caches stay as garbage masked
        by the live length -- the reused-slot contract -- and in
        paged mode the page-table tail past the accepted boundary is
        released back to the pool so refcounts track committed tokens
        only."""
        # a verify's acceptance decides the next draft: data the host
        # must read, so this tick stays synchronous
        self._settle('spec', clock)
        kk = self.spec_tokens
        if self.paged:
            # grow page tables to cover the WHOLE window [position,
            # position + k) before dispatch; overhang past the cache
            # depth is clamped (those rows write scratch, never commit)
            for sid in sorted(self._slots):
                slot = self._slots[sid]
                last = min(slot.position + kk - 1, self.max_len - 1)
                need = last // self.page_size
                while len(slot.pages) <= need:
                    page = self._alloc_page()
                    if page is None:
                        del self._slots[sid]
                        self._shed_paged(slot.request, slot.pages,
                                         'decode')
                        self._free.append(sid)
                        break
                    slot.pages.append(page)
            if not self._slots:
                return
        active = sorted(self._slots)
        k = len(active)
        bucket = bucket_of(k, self.decode_edges)
        if self.paged:
            rows = active + [None] * (bucket - k)
        elif bucket == self.n_slots:
            rows = list(range(self.n_slots))
        else:
            rows = active + self._free[:bucket - k]
        base_tok = np.asarray(
            [self._slots[s].generated[-1] if s in self._slots else 0
             for s in rows], np.int32)
        base_pos = np.asarray(
            [self._slots[s].position if s in self._slots else 0
             for s in rows], np.int32)
        tables = None
        if self.paged:
            tables = np.zeros((bucket, self.pages_per_seq), np.int32)
            for i, sid in enumerate(rows):
                if sid is not None:
                    pages = self._slots[sid].pages
                    tables[i, :len(pages)] = pages
        rec = _telemetry.live()
        reg = _telemetry.registry()
        ident = self._ident()
        if reg is not None:
            reg.gauge('active_slots',
                      help='live sequences at this decode step'
                      ).set(k)
        if _chaos._active is not None:
            _chaos.on_serve_slow(
                self.param_version != self._boot_version)
        t0 = clock()

        def operand_args(tok, pos):
            if self.paged:
                return (jnp.asarray(tok), jnp.asarray(pos),
                        jnp.asarray(tables))
            if bucket == self.n_slots:
                return (jnp.asarray(tok), jnp.asarray(pos))
            return (jnp.asarray(tok),
                    jnp.asarray(np.asarray(rows, np.int32)),
                    jnp.asarray(pos))

        # -- draft loop: k cheap steps propose the window -------------
        d_exe = self._get_draft_decode(bucket)
        proposals = np.zeros((bucket, kk), np.int32)
        cur = base_tok
        with (self._phase(rec, 'serve_draft', stage='decode',
                          iteration=self._step_index, active_slots=k,
                          bucket=bucket, window=kk)
              if rec is not None else NULL_SPAN) as span:
            if rec is not None:
                self._launching(rec, span.t0, 'other')
            for j in range(kk):
                # clamp overhang past the cache depth: the write lands
                # on a not-yet-committed row, the proposal is garbage,
                # and garbage past the boundary is never committed
                pos = np.minimum(base_pos + j,
                                 self.max_len - 1).astype(np.int32)
                args = operand_args(cur, pos)
                self._guard_call(self._draft_cache_sig, args)
                toks, dcache = d_exe(self._draft_params,
                                     self._draft_cache, *args)
                self._draft_cache = dcache
                if rec is not None:
                    self._last_call = toks
                cur = np.asarray(jax.block_until_ready(toks))
                proposals[:, j] = cur
                self.draft_steps += 1
        # window row: [last committed token, draft_1 .. draft_{k-1}];
        # the k-th draft proposal is never verified -- its draft step
        # exists to keep the draft cache covering every position the
        # window can commit
        win = np.zeros((bucket, kk), np.int32)
        win[:, 0] = base_tok
        win[:, 1:] = proposals[:, :kk - 1]
        # -- the ONE target pass --------------------------------------
        v_exe = self._get_verify(bucket)
        vargs = operand_args(win, base_pos)
        self._guard_call(self._cache_sig, vargs)
        with (self._phase(rec, 'serve_verify',
                          iteration=self._step_index, active_slots=k,
                          bucket=bucket, window=kk,
                          n_slots=self.n_slots,
                          queue_depth=self._last_queue_depth)
              if rec is not None else NULL_SPAN) as span:
            if rec is not None:
                self._launching(rec, span.t0, 'other')
            tgt, cache = v_exe(self.params, self._cache, *vargs)
            self._cache = cache
            if rec is not None:
                self._last_call = tgt
            tgt = np.asarray(jax.block_until_ready(tgt))
        self.verify_steps += 1
        now = clock()
        now_tele = rec.now() if rec is not None else None
        itl = (reg.histogram('serve_intertoken_seconds',
                             help='per-sequence gap between '
                                  'consecutive tokens (s)')
               if reg is not None else None)
        proposed_tick = accepted_tick = emitted_total = 0
        # -- host-side accept-prefix + commit/rollback ----------------
        for i, sid in enumerate(rows):
            slot = self._slots.get(sid)
            if slot is None:
                continue   # pad row (or inactive full-bucket row)
            drafts = win[i, 1:]       # the k-1 verified proposals
            targets = tgt[i]          # target argmax after win[i, j]
            m = 0
            while m < kk - 1 and drafts[m] == targets[m]:
                m += 1
            proposed_tick += kk - 1
            accepted_tick += m
            emitted = ([int(x) for x in drafts[:m]]
                       + [int(targets[m])])
            # clip to the request's budget (a window near the end
            # proposes more than max_new_tokens allows)
            emitted = emitted[:min(len(emitted), slot.remaining)]
            if self.eos_id is not None and self.eos_id in emitted:
                # EOS inside the accepted prefix ends the request
                # exactly where the oracle loop would have stopped
                emitted = emitted[:emitted.index(self.eos_id) + 1]
            c = len(emitted)
            slot.generated.extend(emitted)
            slot.request.notify_tokens(emitted)
            slot.position += c
            slot.remaining -= c
            emitted_total += c
            if itl is not None:
                gap = (now - slot.t_last_token) / c
                for _ in range(c):
                    itl.observe(gap)
            slot.t_last_token = now
            if rec is not None:
                t_prev = slot.t_stage_end
                if t_prev is None:
                    t_prev = now_tele - (now - t0)
                rec.child_span(slot.request.request_id, 'decode',
                               t_prev, now_tele, slot=sid,
                               step=self._step_index,
                               token_index=len(slot.generated) - 1,
                               tokens=c, accepted=m, **ident)
                slot.t_stage_end = now_tele
            if slot.remaining == 0 or (self.eos_id is not None
                                       and emitted[-1] == self.eos_id):
                slot.request.set_result(slot.generated)
                if rec is not None:
                    rec.event('complete', kind='request',
                              request_id=slot.request.request_id,
                              tokens=len(slot.generated), slot=sid,
                              **ident)
                self._release_pages(slot.pages)
                del self._slots[sid]
                self._free.append(sid)
            elif self.paged:
                # rollback the page-table tail to the accepted
                # boundary: pages grown for rejected window positions
                # go back to the pool NOW (refcounts track committed
                # tokens, not speculation)
                keep = (slot.position - 1) // self.page_size + 1
                while len(slot.pages) > keep:
                    self.pool.release(slot.pages.pop())
        self.draft_proposed += proposed_tick
        self.draft_accepted += accepted_tick
        self.decode_steps += 1
        self.tokens_generated += emitted_total
        if reg is not None:
            reg.histogram('serve_decode_seconds',
                          help='per-decode-step wall time (s)'
                          ).observe(now - t0)
            reg.counter('serve_tokens_total',
                        help='generated tokens').inc(emitted_total)
            reg.counter(
                'serve_draft_proposed_total',
                help='draft tokens submitted to target verify'
            ).inc(proposed_tick)
            reg.counter(
                'serve_draft_accepted_total',
                help='draft tokens whose target argmax agreed'
            ).inc(accepted_tick)
        if rec is not None:
            rec.event('serve_spec', kind='serve',
                      iteration=self._step_index,
                      proposed=proposed_tick, accepted=accepted_tick,
                      tokens=emitted_total, **ident)

    def _flight_table(self):
        """The in-flight request table embedded in every flight dump
        (:attr:`Recorder.flight_sources`): which requests were alive,
        in which slot, at which stage, with how many tokens emitted --
        so a crash mid-generation names which requests died where."""
        active = []
        for sid in sorted(self._prefilling):
            try:
                st = self._prefilling[sid]
            except KeyError:
                continue   # racing refill on the dying process
            active.append({'slot': sid,
                           'request_id': st.request.request_id,
                           'stage': 'prefill',
                           'tokens': 0,
                           'position': st.pos,
                           'remaining': st.request.max_new_tokens})
        for sid in sorted(self._slots):
            try:
                slot = self._slots[sid]
            except KeyError:
                continue   # racing refill on the dying process
            active.append({'slot': sid,
                           'request_id': slot.request.request_id,
                           'stage': 'decode',
                           'tokens': len(slot.generated),
                           'position': slot.position,
                           'remaining': slot.remaining})
        return {'active': active,
                'free_slots': list(self._free),
                'step_index': self._step_index,
                'queue_depth': self._last_queue_depth}

    def step(self, queue, clock=time.monotonic):
        """One scheduler tick: expire -> admit (slot refill) -> one
        decode step.  Returns True when any work happened.

        With telemetry on, queue pressure is sampled EVERY tick --
        ``serve_queue_depth`` (waiting requests, all still needing
        prefill) and the backlog split ``serve_prefill_backlog`` /
        ``serve_decode_backlog`` (live slots still generating) -- so
        pressure ONSET is visible in captures, not just its latency
        consequences; the engine's in-flight request table is also
        registered as a flight-dump source.  The tick is one
        ``serve_tick`` span whose children TILE it, in this order:
        ``serve_expire``, ``serve_admit``, per prefilled sequence
        ``serve_prefill_prep`` / ``serve_prefill`` / ``serve_emit``
        (``first=1``), then ``serve_decode_prep``, ``serve_decode``,
        ``serve_emit`` (a speculative engine: ``serve_draft``,
        ``serve_verify``); every child's end, and the tick's two, is a
        boundary of the starved-device probe (:meth:`_launching`)."""
        rec = _telemetry.live()
        if rec is None:
            if self._last_call is not None:
                # calls launched from here on go unseen: forget
                self._last_call = self._idle_since = None
            return self._tick(queue, clock, None)
        with rec.span('serve_tick', kind='serve',
                      step=self._step_index, **self._ident()) as tick:
            self._probe(rec, 'client')
            prefills, admissions = self.prefills, self.admissions
            worked = self._tick(queue, clock, rec)
            tick.set(queue_depth=self._last_queue_depth,
                     prefills=self.prefills - prefills,
                     active_slots=len(self._slots))
            if self.admissions > admissions:
                # on admitting ticks only: a reader's mean over the
                # spans that have it is requests an admitting tick
                tick.set(admitted=self.admissions - admissions)
            if self._ring:
                tick.set(full_pages_in_use=self.pool.in_use(),
                         window_pages_in_use=self.window_pool.in_use())
            if self._page_counter:
                pages = self.pool.in_use()
                tick.set(cache_bytes_in_use=pages * self._cache_bytes[0],
                         **{self._page_counter: pages})
            if self.state_pool is not None:
                # what the sequences' cache is made of: state held by
                # the row, K/V by the page (a model that holds window
                # pages too gives their bytes third)
                page_bytes, row_bytes, *ring_bytes = self._cache_bytes
                rows = self.state_pool.in_use()
                held = rows * row_bytes + self.pool.in_use() * page_bytes
                if ring_bytes:
                    held += self.window_pool.in_use() * ring_bytes[0]
                tick.set(state_rows_in_use=rows,
                         state_bytes_in_use=rows * row_bytes,
                         cache_bytes_in_use=held)
            self._probe(rec, 'serve_tick')
        return worked

    def _tick_gauges(self, rec):
        """The gauges a tick sets, by name, looked up in ``rec``'s
        registry once and held (a new recorder: once more)."""
        held = self._gauges
        if held is None or held[0] is not rec:
            helps = {
                'serve_queue_depth': 'requests waiting in the '
                                     'generation queue at the '
                                     'scheduler tick',
                'serve_prefill_backlog': 'queued requests still needing '
                                         'their prefill pass (queued + '
                                         'mid-prefill)',
                'serve_decode_backlog': 'live slots still generating at '
                                        'the scheduler tick',
                'active_slots': 'live sequences at this decode step'}
            if self.paged:
                helps.update({
                    'serve_kv_pages_in_use': 'allocated KV pages (live '
                                             'sequences + banked '
                                             'prefixes) at the tick',
                    'serve_kv_pages_free': 'free KV pages at the tick'})
            if self._prefix_index is not None:
                helps['prefix_evictions'] = (
                    'banked pages the prefix index has dropped for a '
                    'dry pool, up to the tick')
            held = self._gauges = (rec, {
                name: rec.registry.gauge(name, help=text)
                for name, text in helps.items()})
        return held[1]

    def _tick(self, queue, clock, rec):
        with (self._phase(rec, 'serve_expire') if rec is not None
              else NULL_SPAN):
            depth = queue.depth()
            self._last_queue_depth = depth
            if rec is not None:
                if rec.flight_sources.get('serve_requests') \
                        != self._flight_table:
                    rec.flight_sources['serve_requests'] = \
                        self._flight_table
                gauges = self._tick_gauges(rec)
                gauges['serve_queue_depth'].set(depth)
                gauges['serve_prefill_backlog'].set(
                    depth + len(self._prefilling))
                gauges['serve_decode_backlog'].set(len(self._slots))
                if self.paged:
                    gauges['serve_kv_pages_in_use'].set(
                        self.pool.in_use())
                    gauges['serve_kv_pages_free'].set(
                        self.pool.available())
                if self._prefix_index is not None:
                    gauges['prefix_evictions'].set(
                        self._prefix_index.evictions)
            now = clock()
            force = (_chaos.on_serve_cancel()
                     if _chaos._active is not None else 0)
            self._expire(now, force=force)
        self._admit(queue, now, clock)
        worked = False
        if self.paged and self._prefilling:
            worked = self._prefill_tick(clock)
        if self._slots:
            if _chaos._active is not None:
                # replica_kill counts DECODE ticks (slots live), so a
                # fired site always dies with generations in flight --
                # the unplanned-death scenario the fleet front's
                # journal replay must recover
                _chaos.on_replica_kill()
            if self.speculative:
                self._spec_once(clock)
            else:
                self._decode_once(clock)
            worked = True
        elif self._inflight is not None:
            # every row of the call in flight has gone since (expired,
            # shed, ended on an EOS found a call late): nothing is owed
            self._settle('drained', clock)
            worked = True
        if not worked:
            return False
        self._step_index += 1
        return True

    def run(self, queue, stop=None, idle_sleep=0.002):
        """Scheduler loop: tick until ``stop`` is set AND the queue
        and slot table are drained (the loadgen worker loop)."""
        while True:
            worked = self.step(queue)
            if not worked:
                if stop is not None and stop.is_set() \
                        and queue.depth() == 0 and not self._slots \
                        and not self._prefilling:
                    return
                time.sleep(idle_sleep)

    def stats(self):
        paged = {}
        if self.paged:
            paged = {
                'paged': True,
                'page_size': self.page_size,
                'n_pages': self.n_pages,
                'pages_per_seq': self.pages_per_seq,
                'pages_in_use': self.pool.in_use(),
                'pages_free': self.pool.available(),
                'peak_pages_in_use': self.pool.peak_in_use,
                'prefill_chunk': self.prefill_chunk,
                'prefill_chunks': self.prefill_chunks,
                'cow_copies': self.cow_copies,
                'copy_trace_count': self.copy_trace_count,
                'prefilling': len(self._prefilling),
                'full_pages_in_use': self.pool.in_use(),
                'peak_full_pages_in_use': self.pool.peak_in_use,
                'window_ring': self._ring,
                'window_pages_in_use': (
                    self.window_pool.in_use() if self._ring else 0),
                'peak_window_pages_in_use': (
                    self.window_pool.peak_in_use if self._ring else 0),
                'state_rows_in_use': (
                    self.state_pool.in_use()
                    if self.state_pool is not None else 0),
                'peak_state_rows_in_use': (
                    self.state_pool.peak_in_use
                    if self.state_pool is not None else 0),
            }
            if self._prefix_index is not None:
                paged.update(
                    prefix_lookups=self._prefix_index.lookups,
                    prefix_hits=self._prefix_index.hits,
                    prefix_hit_rate=self._prefix_index.hit_rate(),
                    prefix_evictions=self._prefix_index.evictions,
                    prefix_tokens_reused=(
                        self._prefix_index.tokens_reused))
        base = {
            'prefill_buckets': sorted(self._prefill),
            'decode_buckets': sorted(self._decode),
            'label': self.label,
            'param_version': self.param_version,
            'prefill_edges': list(self.prefill_edges),
            'decode_edges': list(self.decode_edges),
            'n_slots': self.n_slots,
            'aot': {'prefill': {b: a for b, (_, a)
                                in sorted(self._prefill.items())},
                    'decode': {b: a for b, (_, a)
                               in sorted(self._decode.items())}},
            'aot_requested': self.aot_requested,
            'cache_dir': self.cache_dir,
            'quantized': self.quantized,
            'int8_kv': self.int8_kv,
            'prefill_trace_count': self.prefill_trace_count,
            'decode_trace_count': self.decode_trace_count,
            'compile_count': self.compile_count,
            'prefills': self.prefills,
            'decode_steps': self.decode_steps,
            # of the decode calls dispatched, the share that went out
            # before their predecessor's tokens were read (0 to 1)
            'decode_runahead_share': (
                self.decode_calls_ahead / max(self.decode_calls, 1)),
            # why the others did not: the call in flight settled for a
            # foreseen end, a change of bucket, a drained table, a
            # speculative tick or a swap, and the calls that primed
            # the pipeline again (= decode calls not dispatched ahead)
            'settles': dict(self.settles),
            'admissions': self.admissions,
            'tokens_generated': self.tokens_generated,
            'cancelled': self.cancelled,
            'active_slots': len(self._slots),
        }
        base.update(paged)
        if self.speculative:
            rate = (self.draft_accepted / self.draft_proposed
                    if self.draft_proposed else None)
            base['speculative'] = {
                'spec_tokens': self.spec_tokens,
                'draft_steps': self.draft_steps,
                'verify_steps': self.verify_steps,
                'draft_proposed': self.draft_proposed,
                'draft_accepted': self.draft_accepted,
                'accepted_draft_rate': rate,
                'draft_trace_count': self.draft_trace_count,
                'verify_trace_count': self.verify_trace_count,
                'draft_decode_buckets': sorted(self._draft_decode),
                'verify_buckets': sorted(self._verify),
                'aot': {
                    'draft_prefill': {
                        b: a for b, (_, a)
                        in sorted(self._draft_prefill.items())},
                    'draft_decode': {
                        b: a for b, (_, a)
                        in sorted(self._draft_decode.items())},
                    'verify': {b: a for b, (_, a)
                               in sorted(self._verify.items())},
                },
            }
        else:
            base['speculative'] = False
        return base

    # -- constructors --------------------------------------------------
    @classmethod
    def from_checkpoint(cls, path, model, params_template, **kw):
        """Engine loaded from an elastic-resume training checkpoint
        (the :func:`chainermn_tpu.serving.load_params` contract)."""
        from chainermn_tpu.serving.engine import load_params
        return cls(model, load_params(path, params_template), **kw)
