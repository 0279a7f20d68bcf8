"""paddle_tpu — a TPU-native deep learning framework.

Brand-new design on JAX/XLA/Pallas idioms with the capability surface of
PaddlePaddle (blueprint: SURVEY.md; reference mounted at /root/reference).
The public namespace mirrors `import paddle` (ref:
python/paddle/__init__.py) so reference users find what they expect, while
everything below is TPU-first: XLA is the kernel library and fuser, GSPMD
the parallelizer, Pallas the escape hatch for fused attention/normalization.
"""
from __future__ import annotations

import time as _time

_import_start_ns = _time.time_ns()  # `runtime.import` opens here

import os as _os  # noqa: E402

# Multi-process bring-up MUST precede any XLA backend touch (jax raises
# otherwise), so when the launcher's env contract is present the
# coordination-service rendezvous happens here, at import — the analogue
# of the reference doing TCPStore + ncclCommInitRank inside
# init_parallel_env (distributed/parallel.py:978), shifted to import time
# because jax owns backend initialization. Opt out with
# PADDLE_DISABLE_AUTO_DIST=1.
if (
    _os.environ.get("PADDLE_MASTER")
    and int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1
    and _os.environ.get("PADDLE_DISABLE_AUTO_DIST") != "1"
    # PID-stamped: a bare inherited "1" would make spawned workers skip
    # their own jax.distributed.initialize
    and _os.environ.get("PADDLE_TPU_DIST_INITED") != str(_os.getpid())
):
    import jax as _jax

    _jax.distributed.initialize(
        coordinator_address=_os.environ["PADDLE_MASTER"],
        num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
        process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")),
    )
    _os.environ["PADDLE_TPU_DIST_INITED"] = str(_os.getpid())

from .core import autograd as _autograd_mod
from .core import dtype as _dtype_mod
from .core.autograd import enable_grad, is_grad_enabled, no_grad, set_grad_enabled
from .core.device import (
    CPUPlace,
    Place,
    TPUPlace,
    device_count,
    get_device,
    is_compiled_with_tpu,
    set_device,
)
from .core import errors  # typed error registry (enforce.h analogue)
from .core.dtype import (
    bfloat16,
    bool_,
    complex64,
    complex128,
    finfo,
    float16,
    float32,
    float64,
    iinfo,
    int8,
    int16,
    int32,
    int64,
    uint8,
    promote_types,
)
from .core.flags import get_flags, set_flags
from .core.random import get_rng_state, seed, set_rng_state
from .core.aux_tensors import (
    StringTensor,
    TensorArray,
    array_length,
    array_read,
    array_write,
    create_array,
)
from .core.tensor import Tensor, to_tensor
from .ops import *  # noqa: F401,F403
from .ops import api as _ops_api
from .ops import tensor_patch as _tensor_patch

_tensor_patch.patch()

from .autograd import grad  # noqa: E402  (needs patched Tensor)
from . import amp  # noqa: E402
from . import audio  # noqa: E402
from . import text  # noqa: E402
from . import utils  # noqa: E402
from . import inference  # noqa: E402
from . import autograd  # noqa: E402
from . import framework  # noqa: E402
from . import device  # noqa: E402
from . import observability  # noqa: E402  (metrics/spans/flight recorder)
from . import resilience  # noqa: E402  (fault injection + retry policy)
from . import analysis  # noqa: E402  (trace-safety linter / jaxpr analyzer)
from . import distributed  # noqa: E402
from . import distribution  # noqa: E402

# `fft` is both a generated op (bound by the ops glob above) and a
# namespace module; `from . import fft` would resolve to the existing
# function attribute without importing the submodule, so import it
# explicitly — paddle.fft is the MODULE (reference parity), the function
# stays reachable as paddle.fft.fft / ops.fft
import importlib as _importlib  # noqa: E402

fft = _importlib.import_module(__name__ + ".fft")
from . import geometric  # noqa: E402
from . import hapi  # noqa: E402
from . import incubate  # noqa: E402
from .hapi import Model  # noqa: E402
from . import metric  # noqa: E402
from . import profiler  # noqa: E402
from . import io  # noqa: E402
from . import jit  # noqa: E402
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import quantization  # noqa: E402
from . import regularizer  # noqa: E402
from . import serving  # noqa: E402
from . import signal  # noqa: E402
from . import sparse  # noqa: E402
from . import static  # noqa: E402
from . import vision  # noqa: E402
from .framework.io_api import load, save  # noqa: E402
from .nn.parameter import ParamAttr  # noqa: E402

# `bool` dtype under its paddle name (shadows builtin only inside namespace)
bool = bool_

__version__ = "0.1.0"


def disable_static(place=None):
    """Dygraph is the default and only eager mode; kept for API parity."""
    return None


def in_dynamic_mode() -> bool:
    return True


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


# the package and everything it pulled in (JAX too, unless the caller had
# it already) is one span of the ring: the first of a start-up's time line
from .observability import spans as _spans  # noqa: E402

_spans.record("runtime.import", _import_start_ns, _time.time_ns())
