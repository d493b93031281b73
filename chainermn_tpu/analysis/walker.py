"""Generic jaxpr traversal for shardlint.

One walker for every rule: :func:`iter_eqns` yields each equation of a
(closed) jaxpr depth-first, recursing into EVERY sub-jaxpr an equation
carries in its params -- ``jit``'s ``jaxpr``, ``shard_map``'s
``jaxpr``, ``scan``'s ``jaxpr``, ``cond``'s ``branches``,
``while``'s ``cond_jaxpr``/``body_jaxpr``, ``custom_*_call``'s
``call_jaxpr``/``fun_jaxpr``, remat, ...  Discovery is structural
(anything in ``eqn.params`` that IS a jaxpr participates), so a new
higher-order primitive in a future JAX is walked without a code
change here.
"""

import jax
from jax._src import source_info_util as _src_info

#: collectives that REDUCE values across an axis (the topology rule's
#: subjects).  ``pmean``/``psum_scatter`` trace to psum/reduce_scatter.
REDUCE_PRIMS = ('psum', 'pmax', 'pmin', 'reduce_scatter',
                'psum_scatter')
#: collectives that MOVE/regather values without reducing
MOVE_PRIMS = ('all_gather', 'ppermute', 'pbroadcast', 'all_to_all')
COLLECTIVE_PRIMS = REDUCE_PRIMS + MOVE_PRIMS
#: primitives that round-trip through the host at run time
CALLBACK_PRIMS = ('pure_callback', 'debug_callback', 'debug_print',
                  'io_callback', 'callback')


def raw_jaxpr(j):
    """The underlying ``Jaxpr`` of a ``ClosedJaxpr`` (identity on a
    raw ``Jaxpr``)."""
    return getattr(j, 'jaxpr', j)


def _is_jaxpr(v):
    return hasattr(v, 'eqns') or hasattr(getattr(v, 'jaxpr', None),
                                         'eqns')


def subjaxprs(eqn):
    """Every sub-jaxpr carried in ``eqn.params`` (order-stable)."""
    for key in sorted(eqn.params):
        val = eqn.params[key]
        if _is_jaxpr(val):
            yield raw_jaxpr(val)
        elif isinstance(val, (tuple, list)):
            for item in val:
                if _is_jaxpr(item):
                    yield raw_jaxpr(item)


def iter_eqns(jaxpr, _path=()):
    """Yield ``(eqn, path)`` for every equation, depth-first; ``path``
    is the tuple of enclosing higher-order primitive names."""
    for eqn in raw_jaxpr(jaxpr).eqns:
        yield eqn, _path
        for sub in subjaxprs(eqn):
            for item in iter_eqns(sub, _path + (eqn.primitive.name,)):
                yield item


def eqn_axes(eqn):
    """Named mesh axes an equation's collective acts over, as a tuple
    of strings (positional/int axes are dropped -- they are array
    dims, not mesh axes)."""
    params = eqn.params
    axes = params.get('axes', params.get('axis_name', ()))
    if isinstance(axes, str):
        axes = (axes,)
    elif not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def eqn_source(eqn):
    """``"file.py:line"`` of the user frame that emitted ``eqn``, or
    ``None`` when source info is unavailable."""
    info = getattr(eqn, 'source_info', None)
    if info is None:
        return None
    frame = _src_info.user_frame(info.traceback)
    if frame is None:
        return None
    return '%s:%d' % (frame.file_name, frame.start_line)


def producer_map(jaxpr):
    """``{outvar: eqn}`` for one (non-recursive) jaxpr level -- the
    chain rules use this to look at what computed a collective's
    operand."""
    out = {}
    for eqn in raw_jaxpr(jaxpr).eqns:
        for var in eqn.outvars:
            out[var] = eqn
    return out


def iter_jaxprs(jaxpr, _path=()):
    """Yield ``(jaxpr_level, path)`` for the top jaxpr and every
    sub-jaxpr -- rules that reason about def-use chains run once per
    level (chains cannot cross a sub-jaxpr boundary structurally)."""
    j = raw_jaxpr(jaxpr)
    yield j, _path
    for eqn in j.eqns:
        for sub in subjaxprs(eqn):
            for item in iter_jaxprs(sub, _path + (eqn.primitive.name,)):
                yield item


def abstract_signature(args):
    """Hashable (shape, dtype, weak_type) signature of a flattened
    argument pytree -- what jit keys its compile cache on.  Two
    synthetic steps whose signatures differ would recompile every
    iteration at run time."""
    leaves = jax.tree_util.tree_leaves(args)
    sig = []
    for leaf in leaves:
        aval = jax.api_util.shaped_abstractify(leaf)
        sig.append((tuple(aval.shape), str(aval.dtype),
                    bool(getattr(aval, 'weak_type', False))))
    return tuple(sig)
