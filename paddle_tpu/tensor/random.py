"""Random ops over a global stateful PRNG.

TPU-native design: the reference's per-device ``phi::Generator``
(/root/reference/paddle/phi/core/generator.h) becomes a process-global JAX PRNG
key chain — stateful at the Python level (paddle API compat) but every sample
is a pure function of a split key, so the same ops remain usable under jit
(the nn.functional dropout path threads keys explicitly; see
paddle_tpu/nn/functional/common.py).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtypes
from ..core.tensor import Tensor


class Generator:
    """Key-chain generator (reference: phi::Generator)."""

    def __init__(self, seed=0):
        self._key = jax.random.key(seed)
        self._seed = seed

    def manual_seed(self, seed):
        self._key = jax.random.key(seed)
        self._seed = seed
        return self

    def initial_seed(self):
        return self._seed

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def get_state(self):
        return jax.random.key_data(self._key)

    def set_state(self, state):
        self._key = jax.random.wrap_key_data(state._data if isinstance(state, Tensor) else state)


# The default generator's key is the package's first touch of the device:
# where nothing touched it before the import, the backend starts here
# (seconds on a TPU).  Stamped so that the package's own import can be told
# from it (``paddle_tpu/__init__`` keeps ``setup.first_device_touch``).
_T0_NS = time.perf_counter_ns()
_DEFAULT_GEN = Generator(np.random.randint(0, 2**31 - 1))
FIRST_TOUCH_NS = (_T0_NS, time.perf_counter_ns())


def default_generator():
    return _DEFAULT_GEN


def seed(value):
    _DEFAULT_GEN.manual_seed(int(value))
    return _DEFAULT_GEN


def get_rng_state():
    return [Tensor._wrap(_DEFAULT_GEN.get_state())]


def set_rng_state(state):
    _DEFAULT_GEN.set_state(state[0] if isinstance(state, (list, tuple)) else state)


class _TraceKeyChain:
    """Functional key chain used while tracing a compiled train step: the
    root key is a traced input, so every compiled step gets fresh randomness
    (the analogue of the reference's RNG-state offset threading,
    fleet/layers/mpu/random.py RNGStatesTracker)."""

    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub


_TRACE_CHAIN = [None]


def _next_key(recording_ok=False):
    """Draw the next PRNG key.

    ``recording_ok=True`` marks callers that thread the key INTO the op as an
    argument (e.g. functional dropout), so a recorded static Program replays
    them with fresh per-run keys.  All other callers sample at dispatch time:
    under ``program_guard`` that sample is frozen into the Program and every
    ``Executor.run`` replays the identical values — warn so the silent
    determinism is at least visible."""
    if _TRACE_CHAIN[0] is not None:
        return _TRACE_CHAIN[0].next()
    if not recording_ok:
        from ..core.state import STATE
        if STATE.recording_program is not None:
            import warnings
            warnings.warn(
                "dispatch-time randomness recorded under program_guard: the "
                "sampled values are frozen into the Program and will replay "
                "identically on every Executor.run (only key-threaded ops "
                "like nn.functional.dropout re-randomize per run)",
                RuntimeWarning, stacklevel=3)
    return _DEFAULT_GEN.next_key()


def _dt(dtype, default=jnp.float32):
    d = dtypes.convert_dtype(dtype)
    return default if d is None else d


def _shape(shape):
    from .creation import _shape as s
    return s(shape)


def rand(shape, dtype=None, name=None):
    return Tensor._wrap(jax.random.uniform(_next_key(), _shape(shape),
                                           _dt(dtype)))


def randn(shape, dtype=None, name=None):
    return Tensor._wrap(jax.random.normal(_next_key(), _shape(shape),
                                          _dt(dtype)))


def standard_normal(shape, dtype=None, name=None):
    return randn(shape, dtype)


def normal(mean=0.0, std=1.0, shape=None, name=None):
    if isinstance(mean, Tensor) or isinstance(std, Tensor):
        m = mean._data if isinstance(mean, Tensor) else mean
        s = std._data if isinstance(std, Tensor) else std
        shp = np.broadcast_shapes(np.shape(m), np.shape(s))
        return Tensor._wrap(m + s * jax.random.normal(_next_key(), shp))
    shp = _shape(shape) if shape is not None else ()
    return Tensor._wrap(mean + std * jax.random.normal(_next_key(), shp))


def gaussian(shape, mean=0.0, std=1.0, seed=0, dtype=None, name=None):
    return Tensor._wrap(mean + std * jax.random.normal(_next_key(),
                                                       _shape(shape),
                                                       _dt(dtype)))


def uniform(shape, dtype=None, min=-1.0, max=1.0, seed=0, name=None):
    return Tensor._wrap(jax.random.uniform(_next_key(), _shape(shape),
                                           _dt(dtype), minval=min, maxval=max))


def uniform_(x, min=-1.0, max=1.0, seed=0, name=None):
    x._data = jax.random.uniform(_next_key(), x._data.shape, x._data.dtype,
                                 minval=min, maxval=max)
    return x


def randint(low=0, high=None, shape=[1], dtype=None, name=None):
    if high is None:
        low, high = 0, low
    return Tensor._wrap(jax.random.randint(_next_key(), _shape(shape), low,
                                           high, _dt(dtype, jnp.int64)))


def randint_like(x, low=0, high=None, dtype=None, name=None):
    if high is None:
        low, high = 0, low
    return Tensor._wrap(jax.random.randint(_next_key(), x._data.shape, low,
                                           high,
                                           _dt(dtype, x.dtype)))


def randperm(n, dtype="int64", name=None):
    return Tensor._wrap(jax.random.permutation(_next_key(), n).astype(
        _dt(dtype, jnp.int64)))


def shuffle(x, name=None):
    perm = jax.random.permutation(_next_key(), x._data.shape[0])
    return Tensor._wrap(x._data[perm])


def multinomial(x, num_samples=1, replacement=False, name=None):
    d = x._data
    logits = jnp.log(jnp.maximum(d, 1e-30))
    if replacement:
        out = jax.random.categorical(_next_key(), logits,
                                     shape=d.shape[:-1] + (num_samples,))
    else:
        g = jax.random.gumbel(_next_key(), d.shape)
        _, out = jax.lax.top_k(logits + g, num_samples)
    return Tensor._wrap(out.astype(jnp.int64))


def bernoulli(x, name=None):
    return Tensor._wrap(
        jax.random.bernoulli(_next_key(), x._data).astype(x.dtype))


def bernoulli_(x, p=0.5, name=None):
    x._data = jax.random.bernoulli(_next_key(), p, x._data.shape).astype(x.dtype)
    return x


def poisson(x, name=None):
    return Tensor._wrap(jax.random.poisson(_next_key(), x._data).astype(x.dtype))


def binomial(count, prob, name=None):
    c = count._data if isinstance(count, Tensor) else count
    p = prob._data if isinstance(prob, Tensor) else prob
    return Tensor._wrap(jax.random.binomial(_next_key(), c, p).astype(jnp.int64))


def log_normal(mean=1.0, std=2.0, shape=None, name=None):
    shp = _shape(shape) if shape is not None else ()
    return Tensor._wrap(jnp.exp(mean + std * jax.random.normal(_next_key(), shp)))


def normal_(x, mean=0.0, std=1.0, name=None):
    x._data = (mean + std * jax.random.normal(_next_key(), x._data.shape)
               ).astype(x.dtype)
    return x


def exponential_(x, lam=1.0, name=None):
    x._data = (jax.random.exponential(_next_key(), x._data.shape) / lam).astype(
        x.dtype)
    return x


def cauchy_(x, loc=0, scale=1, name=None):
    """In-place Cauchy fill (reference: tensor/random.py cauchy_ ->
    inverse-CDF over uniform)."""
    import jax

    u = jax.random.uniform(_next_key(), tuple(x.shape),
                           minval=1e-7, maxval=1.0 - 1e-7)
    vals = loc + scale * jnp.tan(jnp.pi * (u - 0.5))
    x._data = vals.astype(x._data.dtype)
    return x


def geometric_(x, probs, name=None):
    """In-place Geometric(probs) fill (number of Bernoulli trials until
    first success; reference: tensor/random.py geometric_)."""
    import jax

    p = probs._data if hasattr(probs, "_data") else probs
    u = jax.random.uniform(_next_key(), tuple(x.shape),
                           minval=1e-7, maxval=1.0 - 1e-7)
    vals = jnp.ceil(jnp.log(u) / jnp.log1p(-p))
    x._data = vals.astype(x._data.dtype)
    return x
