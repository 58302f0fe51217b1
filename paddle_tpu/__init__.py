"""paddle_tpu — a TPU-native deep learning framework.

Ground-up jax/XLA/pallas/pjit re-design with the capabilities of the
reference PaddlePaddle snapshot (see SURVEY.md).  Eager-first tensor/autograd
runtime whose "static mode" is trace-and-compile (jax.jit / pjit), a
registry-driven op corpus lowering to XLA with Pallas kernels for the hot
paths, and a Fleet-style distributed stack over jax.sharding meshes.
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import core
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    Parameter,
    Tensor,
    enable_grad,
    get_device,
    is_compiled_with_tpu,
    is_tensor,
    no_grad,
    set_device,
    to_tensor,
)
from .core.dtype import (  # noqa: F401
    bfloat16,
    bool_ as bool8,
    complex64,
    complex128,
    dtype,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401

from . import ops
from .ops import *  # noqa: F401,F403

from . import autograd  # noqa: F401
from .core.autograd import grad  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import fft  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import utils  # noqa: F401
from . import incubate  # noqa: F401
from . import static  # noqa: F401
from . import contrib  # noqa: F401
from . import device  # noqa: F401
from . import vision  # noqa: F401
from . import inference  # noqa: F401
from . import signal  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from .framework_io import load, save  # noqa: F401

# numpy-style creation with tensor return
from .ops.creation import tensor_ctor as _tensor_ctor


def _patch_tensor_methods():
    """Attach the op corpus as Tensor methods (reference:
    python/paddle/fluid/dygraph/varbase_patch_methods.py + math_op_patch.py)."""
    import functools

    method_names = [
        "abs", "acos", "add", "all", "allclose", "amax", "amin", "any",
        "argmax", "argmin", "argsort", "asin", "atan", "bmm",
        "broadcast_to", "cast", "ceil", "cholesky", "chunk", "clip",
        "concat", "cos", "cosh", "cross", "cumprod", "cumsum", "diff",
        "digamma", "dist", "divide", "dot", "equal", "equal_all", "erf",
        "exp", "expand", "expand_as", "expm1", "flatten", "flip", "floor",
        "floor_divide", "gather", "gather_nd", "greater_equal",
        "greater_than", "index_select", "inner", "inverse", "isclose",
        "isfinite", "isinf", "isnan", "kron", "kthvalue", "less_equal",
        "less_than", "lgamma", "log", "log10", "log1p", "log2",
        "logical_and", "logical_not", "logical_or", "logical_xor",
        "logsumexp", "masked_select", "matmul", "max", "maximum", "mean",
        "median", "min", "minimum", "mm", "multiply", "mv",
        "nonzero", "norm", "not_equal", "outer", "pow", "prod",
        "reciprocal", "remainder", "reshape", "roll", "round", "rsqrt",
        "scale", "scatter", "sigmoid", "sign", "sin", "sinh", "softmax",
        "sort", "split", "sqrt", "square", "squeeze", "stack", "std",
        "subtract", "sum", "t", "tanh", "tile", "topk", "transpose",
        "tril", "triu", "trunc", "unbind", "unique", "unsqueeze", "unstack",
        "var", "where",
    ]
    import sys

    mod = sys.modules[__name__]
    for name in method_names:
        fn = getattr(mod, name, None) or getattr(ops, name, None)
        if fn is None:
            continue
        if hasattr(Tensor, name) and name not in ("reshape",):
            # don't clobber core dunder-backed methods
            if name in Tensor.__dict__:
                continue
        setattr(Tensor, name, fn)
    # trace is a python builtin-ish name collision in ops; map explicitly
    Tensor.trace = ops.linalg.trace


_patch_tensor_methods()
del _patch_tensor_methods

# paddle-parity callable: paddle_tpu.tensor(...) like paddle.to_tensor
tensor = _tensor_ctor

from .profiler.timer import Benchmark  # noqa: F401,E402
from . import profiler  # noqa: F401,E402
from . import observability  # noqa: F401,E402
from . import resilience  # noqa: F401,E402

# distributed is imported lazily (it builds meshes); expose the module path
from . import distributed  # noqa: F401,E402
from . import hapi  # noqa: F401,E402
from .hapi import Model  # noqa: F401,E402
from . import serving  # noqa: F401,E402
