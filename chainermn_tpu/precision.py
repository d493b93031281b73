"""Mixed-precision policy subsystem.

The r5 bench verdict (PERF.md) is that the ResNet-50 headline is
HBM-bound: the BN/relu interludes between convs are pure HBM traffic,
and every collective moves gradient bytes proportional to dtype width.
Running activations, the backward pass and the gradient reduction in
bfloat16 roughly halves the bytes behind both, while float32 master
weights keep the optimizer trajectory stable -- the recipe ChainerMN's
lineage proved at scale (Akiba et al. 2017 trained the 15-minute
ResNet-50 in half precision with f32 master weights; PyTorch DDP ships
gradient-reduction dtype as a first-class knob, Li et al. VLDB 2020).

A :class:`Policy` names four dtypes (jmp-style) plus an optional loss
scale:

- ``param_dtype``   -- the MASTER weights the optimizer updates (f32);
- ``compute_dtype`` -- forward/backward activations and weights as the
  model sees them (bf16 on TPU);
- ``reduce_dtype``  -- the dtype gradients cross the wire in
  (cast-before-reduce, upcast-after; ``None`` reduces in the
  gradient's own dtype);
- ``output_dtype``  -- model outputs handed back to the caller
  (``None`` keeps the compute dtype).

The cast points live in the training stack, not the model:
``StandardUpdater(..., policy=Policy.bf16())`` casts master params to
compute dtype INSIDE the differentiated loss (so the
``convert_element_type`` transpose upcasts gradient cotangents back to
the master dtype for free), imposes ``reduce_dtype`` on the
communicator's ``allreduce_grad`` (every strategy inherits the
cast/upcast plumbing from ``CommunicatorBase``), keeps BatchNorm
statistics and metric averages in f32, and casts batches to compute
dtype on the HOST so H2D traffic is halved too: ``concat_examples(
dtype=...)`` allocates each floating column at compute dtype and the
cast happens in the assignment that writes an example into its row --
one pass, no float32 batch in between, bit for bit what ``astype`` of
one would give.

bf16 shares f32's exponent range, so ``Policy.bf16()`` needs no loss
scaling.  ``Policy.f16()`` pairs the narrow-exponent float16 with
:class:`DynamicLossScale`: the loss is multiplied by the scale before
the backward pass, gradients are unscaled before the optimizer, and a
step whose unscaled gradients are non-finite is SKIPPED (params and
optimizer state kept) while the scale backs off -- the standard
GradScaler recipe.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


def cast_floating(tree, dtype):
    """Cast every floating-point leaf of ``tree`` to ``dtype``
    (integer/bool leaves -- labels, counters -- pass through;
    ``dtype=None`` is the identity)."""
    if dtype is None:
        return tree
    dt = jnp.dtype(dtype)

    def cast(x):
        x_dt = jnp.result_type(x)
        if jnp.issubdtype(x_dt, jnp.floating) and x_dt != dt:
            return jnp.asarray(x, dt)
        return x

    return jax.tree_util.tree_map(cast, tree)


def all_finite(tree):
    """Scalar bool: every element of every floating leaf is finite."""
    checks = [jnp.all(jnp.isfinite(x))
              for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.result_type(x), jnp.floating)]
    if not checks:
        return jnp.asarray(True)
    return functools.reduce(jnp.logical_and, checks)


def tree_select(pred, on_true, on_false):
    """Leafwise ``where(pred, a, b)`` over two same-structure trees --
    the skip-on-nonfinite primitive (params/optimizer state keep their
    old values when a scaled step overflowed)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false)


class LossScaleState(NamedTuple):
    """Carried loss-scale state: ``scale`` (f32 scalar) and
    ``growth_count`` (int32 consecutive-finite-step counter)."""
    scale: jnp.ndarray
    growth_count: jnp.ndarray


class StaticLossScale:
    """Fixed loss scale: ``adjust`` is the identity.  Useful when the
    gradient magnitude profile is known; :class:`DynamicLossScale` is
    the default for f16."""

    def __init__(self, scale):
        if scale <= 0:
            raise ValueError('loss scale must be positive')
        self.initial_scale = float(scale)

    def init(self):
        return LossScaleState(
            scale=jnp.asarray(self.initial_scale, jnp.float32),
            growth_count=jnp.zeros((), jnp.int32))

    def scale(self, tree, state):
        return jax.tree_util.tree_map(
            lambda x: x * state.scale.astype(x.dtype), tree)

    def unscale(self, tree, state):
        inv = 1.0 / state.scale
        return jax.tree_util.tree_map(
            lambda x: x * inv.astype(x.dtype), tree)

    def adjust(self, state, grads_finite):
        del grads_finite
        return state


class DynamicLossScale(StaticLossScale):
    """GradScaler-style dynamic loss scaling.

    Every step with finite unscaled gradients increments a counter;
    after ``growth_interval`` consecutive finite steps the scale
    multiplies by ``growth_factor``.  A non-finite step multiplies the
    scale by ``backoff_factor`` (floored at ``min_scale``) and resets
    the counter -- the caller is responsible for SKIPPING that step's
    update (:func:`tree_select`; ``StandardUpdater`` does this).
    Scales are powers of two by construction, so scaling/unscaling is
    exact in every binary float dtype.
    """

    def __init__(self, initial_scale=2.0 ** 15, growth_interval=2000,
                 growth_factor=2.0, backoff_factor=0.5, min_scale=1.0):
        super().__init__(initial_scale)
        if growth_interval < 1:
            raise ValueError('growth_interval must be >= 1')
        if not 0.0 < backoff_factor < 1.0:
            raise ValueError('backoff_factor must be in (0, 1)')
        if growth_factor <= 1.0:
            raise ValueError('growth_factor must be > 1')
        self.growth_interval = int(growth_interval)
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.min_scale = float(min_scale)

    def adjust(self, state, grads_finite):
        grown = state.growth_count + 1
        should_grow = grown >= self.growth_interval
        fin_scale = jnp.where(should_grow,
                              state.scale * self.growth_factor,
                              state.scale)
        fin_count = jnp.where(should_grow, 0, grown)
        new_scale = jnp.where(
            grads_finite, fin_scale,
            jnp.maximum(state.scale * self.backoff_factor,
                        self.min_scale))
        new_count = jnp.where(grads_finite, fin_count, 0)
        return LossScaleState(scale=new_scale.astype(jnp.float32),
                              growth_count=new_count.astype(jnp.int32))


class QuantizedLeaf(NamedTuple):
    """One int8-quantized weight: ``q`` (int8, the original shape) and
    ``scale`` (f32, broadcastable on the last axis -- per-output-
    channel symmetric scales).  A pytree node, so quantized trees
    flow through ``device_put``/``jit`` unchanged; tree walks that
    must treat it atomically pass ``is_leaf=is_quantized``."""
    q: jnp.ndarray
    scale: jnp.ndarray


def is_quantized(x):
    return isinstance(x, QuantizedLeaf)


#: leaves smaller than this stay in float: biases and norm scales are
#: a rounding error of the weight bytes, and quantizing them costs
#: accuracy for no memory win
QUANT_MIN_ELEMS = 1024


def quantize_int8(tree, min_elems=QUANT_MIN_ELEMS):
    """Per-channel symmetric int8 quantization of a weight tree.

    Floating leaves with ``ndim >= 2`` and at least ``min_elems``
    elements (the Dense/conv kernels) become :class:`QuantizedLeaf`:
    ``scale = max|w| / 127`` reduced over every axis except the LAST
    (the output-feature axis of both Dense ``(in, out)`` and conv
    ``HWIO`` kernels), ``q = round(w / scale)`` clipped to ±127.
    Symmetric (no zero point), so dequantization is a single
    per-channel multiply and the matmul form
    (:func:`chainermn_tpu.ops.int8_matmul.dequant_matmul`) is exact.
    Everything else -- biases, norms, embeddings under the size floor,
    integer leaves -- passes through untouched.

    Runs at LOAD time on the host or device; the result is what the
    serving engine places and closes over (``docs/serving.md``).
    """
    def one(w):
        dt = jnp.result_type(w)
        if (not jnp.issubdtype(dt, jnp.floating) or w.ndim < 2
                or w.size < min_elems):
            return w
        wf = jnp.asarray(w, jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=tuple(range(w.ndim - 1)),
                       keepdims=False)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
        return QuantizedLeaf(q=q, scale=scale.astype(jnp.float32))

    return jax.tree_util.tree_map(one, tree)


def dequantize_int8(tree, dtype=jnp.float32):
    """Inverse of :func:`quantize_int8` (up to rounding): every
    :class:`QuantizedLeaf` becomes a plain ``dtype`` array, other
    floating leaves are cast to ``dtype``.  Called INSIDE the jitted
    forward, the per-leaf convert+multiply fuses into each consumer
    matmul (see :mod:`chainermn_tpu.ops.int8_matmul`)."""
    from chainermn_tpu.ops.int8_matmul import dequant

    def one(x):
        if is_quantized(x):
            return dequant(x.q, x.scale, dtype)
        if jnp.issubdtype(jnp.result_type(x), jnp.floating):
            return jnp.asarray(x, dtype)
        return x

    return jax.tree_util.tree_map(one, tree, is_leaf=is_quantized)


def quantize_kv(x):
    """Per-vector symmetric int8 quantization over the LAST axis --
    the KV-cache member of the :func:`quantize_int8` family.

    Where weight quantization reduces over every axis but the output
    channel (static content, computed once at load), a KV cache is
    written one token at a time and each (position, head) vector's
    dynamic range is its own: ``scale = max|x| / 127`` over the head
    dim, ``q = round(x / scale)`` clipped to +-127.  Returns
    ``(q int8 of x.shape, scale f32 of x.shape[:-1])`` -- what
    :func:`chainermn_tpu.ops.flash_attention_decode` consumes as
    ``k_scale``/``v_scale`` and dequantizes per tile in VMEM, so the
    HBM bytes the decode step streams are the int8 ones
    (``docs/serving.md``)."""
    xf = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (up to rounding): a per-vector
    multiply that XLA/Pallas fuses into the consumer's operand read."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def quantization_error(tree, qtree):
    """Worst relative Frobenius error over quantized leaves --
    the load-time sanity number the engine logs (int8 per-channel
    symmetric lands around 1e-2 for well-scaled weights)."""
    errs = []

    def one(w, qw):
        if is_quantized(qw):
            deq = dequantize_int8(qw, jnp.float32)
            num = jnp.linalg.norm(jnp.asarray(w, jnp.float32) - deq)
            den = jnp.maximum(jnp.linalg.norm(
                jnp.asarray(w, jnp.float32)), 1e-12)
            errs.append(float(num / den))

    jax.tree_util.tree_map(one, tree, qtree, is_leaf=is_quantized)
    return max(errs) if errs else 0.0


class Policy:
    """Dtype policy for one training run (see module docstring).

    Deliberately NOT a pytree: instances are trace-time configuration
    closed over by the jitted step, never traced values.
    """

    def __init__(self, param_dtype=jnp.float32,
                 compute_dtype=jnp.float32, reduce_dtype=None,
                 output_dtype=None, loss_scale=None):
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.reduce_dtype = (jnp.dtype(reduce_dtype)
                             if reduce_dtype is not None else None)
        self.output_dtype = (jnp.dtype(output_dtype)
                             if output_dtype is not None else None)
        self.loss_scale = loss_scale

    # -- casts ----------------------------------------------------------
    def cast_to_compute(self, tree):
        return cast_floating(tree, self.compute_dtype)

    def cast_to_param(self, tree):
        return cast_floating(tree, self.param_dtype)

    def cast_to_output(self, tree):
        return cast_floating(tree, self.output_dtype
                             or self.compute_dtype)

    def cast_to_reduce(self, tree):
        return cast_floating(tree, self.reduce_dtype)

    def upcast_from_reduce(self, tree, like):
        """Restore each reduced leaf to its pre-reduction dtype."""
        if self.reduce_dtype is None:
            return tree
        return jax.tree_util.tree_map(
            lambda r, g: r.astype(jnp.result_type(g)), tree, like)

    # -- introspection --------------------------------------------------
    def declared_dtypes(self):
        """Dtype names this policy DECLARES reductions/compute may
        narrow to -- consumed by shardlint SL004 (a reduction narrowed
        to a declared dtype is the policy working, not a lint error)."""
        out = {str(self.compute_dtype)}
        if self.reduce_dtype is not None:
            out.add(str(self.reduce_dtype))
        return out

    # -- registry -------------------------------------------------------
    @classmethod
    def f32(cls):
        """Full precision (the identity policy)."""
        return cls()

    @classmethod
    def bf16(cls):
        """The TPU-native policy: bf16 compute and reduce, f32 master
        weights, f32 outputs.  bf16 keeps f32's exponent, so no loss
        scaling is needed."""
        return cls(param_dtype=jnp.float32,
                   compute_dtype=jnp.bfloat16,
                   reduce_dtype=jnp.bfloat16,
                   output_dtype=jnp.float32)

    @classmethod
    def f16(cls, loss_scale=None):
        """float16 compute/reduce with f32 masters and dynamic loss
        scaling (f16's 5-bit exponent underflows gradients without
        it)."""
        return cls(param_dtype=jnp.float32,
                   compute_dtype=jnp.float16,
                   reduce_dtype=jnp.float16,
                   output_dtype=jnp.float32,
                   loss_scale=(loss_scale if loss_scale is not None
                               else DynamicLossScale()))

    @classmethod
    def from_string(cls, name):
        """``'f32'|'float32'``, ``'bf16'|'bfloat16'``,
        ``'f16'|'float16'`` -> the matching policy (CLI surface for
        bench.py and the shardlint sweep)."""
        table = {'f32': cls.f32, 'float32': cls.f32,
                 'bf16': cls.bf16, 'bfloat16': cls.bf16,
                 'f16': cls.f16, 'float16': cls.f16}
        try:
            return table[name.lower()]()
        except KeyError:
            raise ValueError(
                'unknown precision policy %r (choose from %s)'
                % (name, ', '.join(sorted(table))))

    def __repr__(self):
        return ('Policy(param=%s, compute=%s, reduce=%s, output=%s, '
                'loss_scale=%s)'
                % (self.param_dtype, self.compute_dtype,
                   self.reduce_dtype, self.output_dtype,
                   type(self.loss_scale).__name__
                   if self.loss_scale is not None else None))

    def __eq__(self, other):
        return (isinstance(other, Policy)
                and self.param_dtype == other.param_dtype
                and self.compute_dtype == other.compute_dtype
                and self.reduce_dtype == other.reduce_dtype
                and self.output_dtype == other.output_dtype
                and self.loss_scale is other.loss_scale)

    def __hash__(self):
        return hash((self.param_dtype, self.compute_dtype,
                     self.reduce_dtype, self.output_dtype,
                     id(self.loss_scale)))


class Int8Policy(Policy):
    """Int8-WEIGHT inference policy (forward-only; raising it at a
    training updater is a usage error and the updater's policy
    plumbing never sees one).

    Weights are stored int8 with per-channel symmetric f32 scales
    (:func:`quantize_int8`, computed once at load), activations run in
    ``compute_dtype`` (f32 by default, bf16 on TPU), and
    dequantization happens IN the compiled forward
    (:func:`dequantize_int8` -- a per-channel multiply XLA fuses into
    each consumer matmul, so no wide weight tensor is materialized in
    HBM; :mod:`chainermn_tpu.ops.int8_matmul`).  4x weight-HBM
    saving over f32, parity-pinned against the f32 oracle at
    rtol <= 5e-2 on logits (``tests/test_serving.py``).

    ``min_elems`` is the quantization size floor (small leaves --
    biases, norms -- stay float; :data:`QUANT_MIN_ELEMS`)."""

    def __init__(self, compute_dtype=jnp.float32, output_dtype=None,
                 min_elems=QUANT_MIN_ELEMS):
        super().__init__(param_dtype=jnp.int8,
                         compute_dtype=compute_dtype,
                         output_dtype=output_dtype)
        self.min_elems = int(min_elems)

    #: introspection flag the serving engine keys its quantized
    #: params path on (and updaters could reject on)
    is_inference_only = True

    def quantize(self, params):
        """The load-time transform: float weight tree ->
        mixed tree of :class:`QuantizedLeaf` and passthrough leaves."""
        return quantize_int8(params, min_elems=self.min_elems)

    def dequantize(self, qparams):
        """The in-graph inverse at this policy's compute dtype."""
        return dequantize_int8(qparams, self.compute_dtype)

    @classmethod
    def bf16(cls):
        """bf16 activations over int8 weights -- the TPU serving
        configuration."""
        return cls(compute_dtype=jnp.bfloat16,
                   output_dtype=jnp.float32)

    @classmethod
    def from_string(cls, name):
        """``'int8'`` (f32 activations) or ``'int8_bf16'`` -- the
        serving CLI surface (``bench.py --serve --int8``)."""
        table = {'int8': cls, 'int8_f32': cls, 'int8_bf16': cls.bf16}
        try:
            return table[name.lower()]()
        except KeyError:
            raise ValueError(
                'unknown int8 policy %r (choose from %s)'
                % (name, ', '.join(sorted(table))))
