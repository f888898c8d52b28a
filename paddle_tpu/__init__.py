"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle (reference: /root/reference, snapshot 2025-03-21),
re-designed from scratch on JAX/XLA/Pallas.

Architecture (vs SURVEY.md layer map):
- L0-L2 (common/device/kernels): ``paddle_tpu.core`` — Tensor over jax.Array,
  op dispatch over jnp/lax/Pallas, flags; XLA owns device memory.
- L3 (op codegen): ``core.dispatch.OPS`` registry (single Python tier — XLA is
  the kernel compiler).
- L4a (eager autograd): ``core.autograd`` tape over jax.vjp.
- L4b/L4c (PIR+CINN): ``paddle_tpu.jit`` — whole-program jax.jit tracing.
- L5-L7 (distributed): ``paddle_tpu.distributed`` — jax.sharding Mesh +
  GSPMD; fleet-style hybrid parallel (dp/tp/pp/sharding/sep/ep).
- L6 (user API): this namespace mirrors ``paddle.*``.
"""

from __future__ import annotations

import time as _time

_IMPORT_T0_NS = _time.perf_counter_ns()  # the ``setup.import`` span begins

import warnings as _warnings  # noqa: E402

_warnings.filterwarnings(
    "ignore", message="Explicitly requested dtype.*truncated")

__version__ = "0.1.0"

# core first
from .core import dtype as _dtype_mod
from .core.dtype import (bfloat16, bool_ as bool, complex64, complex128,  # noqa: F401
                         float8_e4m3fn, float8_e5m2, float16, float32,
                         float64, int8, int16, int32, int64, uint8)
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401

# op surface
from .tensor import *  # noqa: F401,F403
from .tensor import add_n, einsum  # noqa: F401
from .tensor.random import (bernoulli, binomial, get_rng_state, multinomial,  # noqa: F401
                            normal, poisson, rand, randint, randint_like,
                            randn, randperm, seed, set_rng_state,
                            standard_normal, uniform)

# subsystems
from . import amp  # noqa: F401
from . import analysis  # noqa: F401
from . import audio  # noqa: F401
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distributed  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import inference  # noqa: F401
from . import framework  # noqa: F401
from . import geometric  # noqa: F401
from . import hapi  # noqa: F401
from . import incubate  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import linalg  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import onnx  # noqa: F401
from . import quantization  # noqa: F401
from . import resilience  # noqa: F401
from . import serving  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import vision  # noqa: F401
from .autograd import PyLayer, enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401
from .core.selected_rows import SelectedRows  # noqa: F401
from .tensor.extras import (  # noqa: F401
    as_complex, as_real, cast, cdist, check_shape, frexp, mv, pdist,
    reduce_as, renorm, renorm_, sgn, standard_gamma, tensordot, tolist,
    vander)
from .tensor.scatter_views import (  # noqa: F401
    combinations, diagonal_scatter, masked_scatter, masked_scatter_,
    select_scatter, slice_scatter, unfold)
from .tensor.inplace import *  # noqa: F401,F403
from .framework import (  # noqa: F401
    LazyGuard, batch, create_parameter, disable_signal_handler, finfo,
    get_cuda_rng_state, iinfo, set_cuda_rng_state, set_printoptions)
from .tensor.manipulation import flip as reverse  # noqa: F401
from .tensor.creation import create_tensor  # noqa: F401
from .tensor.linalg import ormqr, svd_lowrank  # noqa: F401
from .tensor.search import top_p_sampling  # noqa: F401
from .tensor.random import cauchy_, geometric_  # noqa: F401
from .device import CUDAPinnedPlace  # noqa: F401
from .nn.functional.init_utils import ParamAttr  # noqa: F401
import numpy as _np
dtype = _np.dtype  # paddle.dtype: dtype objects are numpy/ml_dtypes dtypes
from .device import (CPUPlace, CUDAPlace, TPUPlace, XPUPlace, get_device,  # noqa: F401
                     is_compiled_with_cinn, is_compiled_with_cuda,
                     is_compiled_with_distribute, is_compiled_with_rocm,
                     is_compiled_with_tpu, is_compiled_with_xpu, set_device)
from .framework import (get_default_dtype, in_dynamic_mode,  # noqa: F401
                        in_dynamic_or_pir_mode, in_pir_mode, load, save,
                        set_default_dtype)
from .hapi import Model, flops, summary  # noqa: F401
from .jit import disable_static, enable_static  # noqa: F401
from .nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401

DataParallel = None  # bound by paddle_tpu.distributed at import end


def _late_bind():
    global DataParallel
    from .distributed.parallel import DataParallel as DP
    DataParallel = DP


_late_bind()

# paddle compat alias for scaler
from .amp import GradScaler  # noqa: F401,E402

# last: the compile layer starts to listen (jax.monitoring), and the import
# that ends here is kept as a lifecycle span of the process, with the first
# touch of the device inside it (the backend's start, where it falls here)
from .core import compile_cache as _compile_cache  # noqa: F401,E402
from .profiler import host_tracer as _host_tracer  # noqa: E402
from .tensor import random as _random  # noqa: E402
_host_tracer.lifecycle_since("setup.first_device_touch",
                             *_random.FIRST_TOUCH_NS)
_host_tracer.lifecycle_since("setup.import", _IMPORT_T0_NS)
