"""Platform/backend setup helpers.

The multi-device CPU simulation the test harness and examples use
(the TPU-native analogue of the reference's ``mpiexec -n N`` CPU
matrix, ``.travis.yml:55``).
"""

import os
import re

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_host_device_flag(n=8):
    """Append ``--xla_force_host_platform_device_count=n`` to
    ``XLA_FLAGS`` unless some value for it is already present.  Safe
    on any platform (only affects the host backend); must run before
    first backend use to have an effect."""
    flags = os.environ.get('XLA_FLAGS', '')
    m = re.search(r'--xla_force_host_platform_device_count=(\d+)', flags)
    if m is None:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=%d' % n
        ).strip()
    return m


def enable_compilation_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory.  The place is decided OUTSIDE the program: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is configured here; otherwise the one fixed path
    ``<checkout>/.jax_compile_cache`` (git-ignored) -- the path is
    part of the cache key, so a directory that moves never hits."""
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if path:
        return path
    path = os.path.join(_CHECKOUT, '.jax_compile_cache')
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update('jax_compilation_cache_dir', path)
        # the cache object is built once, at the first compile; a
        # directory configured after that is otherwise never used
        from jax.experimental.compilation_cache import (
            compilation_cache)
        compilation_cache.reset_cache()
    return path


def force_host_devices(n=8, require=False):
    """Switch this process to the CPU backend with ``n`` virtual
    devices and return the live CPU device count.

    Must run before first backend use.  An already-present
    ``--xla_force_host_platform_device_count`` flag is respected (it
    may be a deliberate smaller CI-matrix setting).  With
    ``require=True`` a RuntimeError is raised when fewer than ``n``
    devices actually materialize -- either the pre-existing flag asked
    for fewer, or the backend was initialized before this call could
    take effect.
    """
    m = ensure_host_device_flag(n)
    jax.config.update('jax_platforms', 'cpu')
    devices = jax.devices()
    if devices[0].platform != 'cpu':
        # config update is a no-op once backends are live: the one job
        # of this function failed, never continue silently on real
        # hardware
        raise RuntimeError(
            'could not force the CPU backend: jax already initialized '
            'platform %r before force_host_devices ran'
            % devices[0].platform)
    count = len(devices)
    if require and count < n:
        raise RuntimeError(
            'asked for %d virtual CPU devices but the backend exposes '
            '%d (pre-existing flag: %s); set XLA_FLAGS='
            '--xla_force_host_platform_device_count=%d before first '
            'jax use' % (n, count, m.group(1) if m else 'unset', n))
    return count
