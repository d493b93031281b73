"""Test harness: 8 virtual CPU devices.

The reference exercises real multi-process behavior by running the whole
suite under ``mpiexec -n {1,2,3}`` on one CPU host (``.travis.yml:55``).
The TPU-native analogue is XLA's forced host-platform device count: one
process, 8 virtual CPU devices, real mesh/collective code paths.
"""

import os

# The suite is written for the CPU: run it with JAX_PLATFORMS=cpu (the
# tier-1 command and ci/run_matrix.sh set it) and this file adds the
# eight virtual devices.  With the variable unset JAX picks the
# platform itself -- on a machine with a TPU that is the chip, which is
# how tests/test_tpu_mosaic.py is run there.
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (
        _flags + ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update('jax_default_matmul_precision', 'highest')

# CPU compiles dominate the suite's wall time and many tests build the
# same tiny programs: with the persistent cache on (and no minimum
# compile time) each distinct program is compiled once per run, or
# once per checkout.
from chainermn_tpu.utils.platform import (  # noqa: E402
    enable_compilation_cache)

enable_compilation_cache()
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)


def hlo_collective_counts(fn, mesh, in_specs, out_specs, ops, *args):
    """Count collective-op mentions in the StableHLO a shard_mapped
    ``fn`` lowers to -- the shared primitive behind the
    lowering-signature pin tests (single place to patch if a JAX
    upgrade changes lowering text)."""
    import re

    import jax

    txt = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)).lower(*args).as_text()
    return {k: len(re.findall(k, txt)) for k in ops}


def stablehlo_case_branches(txt):
    """``(outside, [branch, ...])`` of a StableHLO text that holds ONE
    top-level ``stablehlo.case`` (what ``lax.cond`` lowers to; branch
    0 is the FALSE function): the text around the op and the text of
    each of its regions, so a test can say WHERE a collective sits.
    Keyed on the printer's indentation: a region's own ops (a nested
    ``case`` too) are indented deeper than the op that holds them."""
    lines = txt.splitlines()
    start = next(i for i, line in enumerate(lines)
                 if '"stablehlo.case"' in line)
    pad = lines[start][:len(lines[start]) - len(lines[start].lstrip())]
    branches, cur = [], []
    for end in range(start + 1, len(lines)):
        if lines[end].startswith(pad + '}, {'):
            branches.append('\n'.join(cur))
            cur = []
        elif lines[end].startswith(pad + '}) :'):
            branches.append('\n'.join(cur))
            break
        else:
            cur.append(lines[end])
    return '\n'.join(lines[:start] + lines[end + 1:]), branches


def pytest_addoption(parser):
    parser.addoption(
        '--runslow', action='store_true', default=False,
        help='include @pytest.mark.slow tests (the full-coverage '
             'pass; ci/run_matrix.sh runs it once)')


def pytest_collection_modifyitems(config, items):
    """Default run stays under ~5 minutes (VERDICT r3 item 7): the
    slow tail is opt-in via --runslow; ci/run_matrix.sh runs the fast
    set per device count and the FULL set once, so coverage is not
    lost -- only moved out of the edit-test loop."""
    if config.getoption('--runslow'):
        return
    import pytest
    skip = pytest.mark.skip(reason='slow: run with --runslow')
    for item in items:
        if 'slow' in item.keywords:
            item.add_marker(skip)


def flat_params(updater):
    """Concatenate an updater's device params into one host vector
    (shared by the ZeRO trajectory suites)."""
    import numpy as np

    return np.concatenate([
        np.asarray(leaf).ravel() for leaf in
        jax.tree_util.tree_leaves(jax.device_get(updater.params))])


def mlp_setup(n_units=16, n_in=48, n_out=10, seed=0):
    """A seeded MLP for the serving suites: ``(model, params, apply_fn,
    one zero example)``."""
    import numpy as np

    from chainermn_tpu.models import MLP
    model = MLP(n_units=n_units, n_out=n_out)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, n_in)))['params']

    def apply_fn(p, x):
        return model.apply({'params': p}, x)

    return model, params, apply_fn, np.zeros((n_in,), np.float32)


def tiny_lm(dtype=jnp.float32, n_layers=1, max_len=64, d_model=32,
            n_heads=4):
    """A seeded one-layer ``TransformerLM`` for the generation suites:
    ``(model, params)``."""
    from chainermn_tpu.models import TransformerLM
    model = TransformerLM(vocab_size=32, d_model=d_model,
                          n_heads=n_heads, n_layers=n_layers, d_ff=32,
                          max_len=max_len, dtype=dtype)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))['params']
    return model, params
