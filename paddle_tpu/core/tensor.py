"""Eager Tensor.

TPU-native analog of the reference's user-facing tensor
(paddle/phi/api/include/tensor.h:83 ``paddle::experimental::Tensor`` over
phi::DenseTensor, dense_tensor.h:38) fused with its eager AutogradMeta
(paddle/fluid/eager/autograd_meta.h:68).

Design: a Tensor is a thin mutable wrapper over an immutable ``jax.Array``
(``.data``) plus autograd metadata (``stop_gradient``, ``.grad``, producing
``TapeNode``).  Storage/layout/placement are XLA's problem; this class owns
API surface and tape wiring only.  Most numeric methods are monkey-patched
from the ops corpus at package import (the reference does the same via
varbase_patch_methods.py / math_op_patch.py).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from .place import _current_place, Place

# set True inside forked DataLoader worker processes (io/multiprocess.py)
_IN_DATALOADER_WORKER = False

__all__ = ["Tensor", "Parameter", "to_tensor", "is_tensor"]


class Tensor:
    __slots__ = ("data", "stop_gradient", "grad", "_node", "name",
                 "persistable", "_retain_grads", "_grad_hooks",
                 "__weakref__")

    def __init__(self, data, stop_gradient=True, name=None, place=None):
        if _IN_DATALOADER_WORKER:
            # a device-put through the forked, thread-less PJRT client
            # hangs; fail loudly instead (io/multiprocess.py sets this)
            raise RuntimeError(
                "Tensor construction inside a DataLoader worker process: "
                "return numpy arrays from __getitem__/collate_fn (the "
                "parent wraps them), or pass use_thread_workers=True.")
        if isinstance(data, Tensor):
            data = data.data
        if not isinstance(data, jax.Array):
            data = _to_jax(data, place=place)
        elif place is not None:
            data = jax.device_put(data, place.jax_device())
        self.data = data
        self.stop_gradient = stop_gradient
        self.grad = None
        self._node = None
        self.name = name
        self.persistable = False
        self._retain_grads = False
        self._grad_hooks = ()    # shared empty tuple: no alloc on hot path

    # ------------------------------------------------------------------ meta
    @property
    def shape(self):
        return list(self.data.shape)

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def size(self):
        return int(self.data.size)

    @property
    def place(self) -> Place:
        d = self.data.devices() if hasattr(self.data, "devices") else None
        if d:
            dev = next(iter(d))
            return Place(dev.platform, dev.id)
        return _current_place()

    @property
    def T(self):
        from .. import ops

        return ops.transpose_last2(self)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.data.shape[0]

    def __repr__(self):
        grad_flag = f", stop_gradient={self.stop_gradient}"
        return (
            f"Tensor(shape={self.shape}, dtype={self.data.dtype.name}"
            f"{grad_flag})\n{np.asarray(self.data)}"
        )

    # ------------------------------------------------------------- transfers
    def numpy(self):
        return np.asarray(self.data)

    def item(self):
        return self.data.item()

    def tolist(self):
        return np.asarray(self.data).tolist()

    def cpu(self):
        return Tensor(jax.device_put(self.data, jax.devices("cpu")[0]),
                      stop_gradient=self.stop_gradient)

    def to(self, place_or_dtype):
        if isinstance(place_or_dtype, Place):
            return Tensor(jax.device_put(self.data, place_or_dtype.jax_device()),
                          stop_gradient=self.stop_gradient)
        return self.astype(place_or_dtype)

    def astype(self, dt):
        from .. import ops

        return ops.cast(self, dt)

    # ------------------------------------------------------------- autograd
    def backward(self, grad_tensor=None, retain_graph=False):
        from .autograd import run_backward

        run_backward(self, grad=grad_tensor, retain_graph=retain_graph)

    def retain_grads(self):
        self._retain_grads = True

    def register_hook(self, hook):
        """Register ``hook(grad) -> grad | None`` fired when this
        tensor's gradient is finalized during backward (parity:
        Tensor.register_hook over egr::GradNodeBase hooks,
        grad_node_info.h:90).  Returns a handle with ``.remove()``."""
        self._grad_hooks = tuple(self._grad_hooks) + (hook,)
        return _HookHandle(self, hook)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):  # paddle alias
        self.grad = None

    def detach(self):
        t = Tensor(self.data, stop_gradient=True, name=self.name)
        return t

    def clone(self):
        from .. import ops

        return ops.assign(self)

    def _accum_grad(self, g):
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True)
        else:
            self.grad = Tensor(self.grad.data + g, stop_gradient=True)

    # ---------------------------------------------------------- mutation ops
    def set_value(self, value):
        """In-place value replacement (keeps autograd identity as a leaf)."""
        arr = value.data if isinstance(value, Tensor) else _to_jax(value)
        if tuple(arr.shape) != tuple(self.data.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self.data.shape}")
        self.data = arr.astype(self.data.dtype)
        self._node = None

    def copy_(self, other):
        self.set_value(other)
        return self

    # ------------------------------------------------------------- indexing
    def __getitem__(self, idx):
        from .. import ops

        return ops.getitem(self, idx)

    def __setitem__(self, idx, value):
        arr = value.data if isinstance(value, Tensor) else jnp.asarray(value)
        self.data = self.data.at[idx].set(arr.astype(self.data.dtype))
        self._node = None

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ----------------------------------------------------------- arithmetic
    # (rich numeric API is monkey-patched in paddle_tpu/__init__.py; dunders
    #  here delegate so `a + b` works before patching too)
    def _binop(self, other, opname, reverse=False):
        from .. import ops

        fn = getattr(ops, opname)
        return fn(other, self) if reverse else fn(self, other)

    def __add__(self, o):
        return self._binop(o, "add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "subtract")

    def __rsub__(self, o):
        return self._binop(o, "subtract", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "multiply")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, "divide", reverse=True)

    def __floordiv__(self, o):
        return self._binop(o, "floor_divide")

    def __mod__(self, o):
        return self._binop(o, "remainder")

    def __pow__(self, o):
        return self._binop(o, "pow")

    def __rpow__(self, o):
        return self._binop(o, "pow", reverse=True)

    def __matmul__(self, o):
        return self._binop(o, "matmul")

    def __neg__(self):
        from .. import ops

        return ops.scale(self, -1.0)

    def __abs__(self):
        from .. import ops

        return ops.abs(self)

    def __lt__(self, o):
        return self._binop(o, "less_than")

    def __le__(self, o):
        return self._binop(o, "less_equal")

    def __gt__(self, o):
        return self._binop(o, "greater_than")

    def __ge__(self, o):
        return self._binop(o, "greater_equal")

    def __eq__(self, o):
        from .. import ops

        return ops.equal(self, o)

    def __ne__(self, o):
        from .. import ops

        return ops.not_equal(self, o)

    def __hash__(self):
        return id(self)

    def __invert__(self):
        from .. import ops

        return ops.logical_not(self)

    def __bool__(self):
        if self.data.size != 1:
            raise ValueError("truth value of multi-element Tensor is ambiguous")
        import jax

        if isinstance(self.data, jax.core.Tracer):
            # dy2static guard (reference: program_translator's AST pass
            # rewrites `if tensor:`; we trace instead, so branching on a
            # traced value must fail loudly with the supported alternative)
            raise RuntimeError(
                "Python control flow on a traced Tensor: under jit/"
                "to_static the value is not concrete. Use "
                "paddle_tpu.static.nn.cond / while_loop (or jax.lax.cond) "
                "for tensor-dependent branches, or move the branch out of "
                "the compiled function.")
        return bool(self.data)

    def __float__(self):
        return float(self.data)

    def __int__(self):
        return int(self.data)

    def __array__(self, dtype=None):
        a = np.asarray(self.data)
        return a.astype(dtype) if dtype is not None else a

    # jax pytree-friendly: allow jnp.asarray(tensor)
    def __jax_array__(self):
        return self.data


class _HookHandle:
    __slots__ = ("_ref", "_hook")

    def __init__(self, tensor, hook):
        import weakref

        self._ref = weakref.ref(tensor)
        self._hook = hook

    def remove(self):
        t = self._ref()
        if t is not None:
            t._grad_hooks = tuple(h for h in t._grad_hooks
                                  if h is not self._hook)
        self._hook = None


class Parameter(Tensor):
    """Trainable leaf tensor (reference: framework.py ``Parameter``)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "is_distributed")

    def __init__(self, data, trainable=True, name=None):
        super().__init__(data, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def _to_jax(data, dtype=None, place=None):
    if isinstance(data, Tensor):
        arr = data.data
    elif isinstance(data, jax.Array):
        arr = data
    else:
        if isinstance(data, np.ndarray) and data.dtype == np.float64 and dtype is None:
            data = data.astype(np.float32)
        if isinstance(data, float) and dtype is None:
            dtype = dtypes.get_default_dtype()
        arr = jnp.asarray(data, dtype=dtypes.convert_dtype(dtype))
    if dtype is not None:
        arr = arr.astype(dtypes.convert_dtype(dtype))
    if place is not None:
        arr = jax.device_put(arr, place.jax_device())
    return arr


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity."""
    arr = _to_jax(data, dtype=dtype, place=place)
    return Tensor(arr, stop_gradient=stop_gradient)


def is_tensor(x):
    return isinstance(x, Tensor)


# Register Tensor as a jax pytree so Tensors can cross jit boundaries when
# needed (data is the leaf; autograd metadata is aux and dropped on rebuild).
def _tensor_flatten(t):
    return (t.data,), (t.stop_gradient, t.name)


def _tensor_unflatten(aux, children):
    t = Tensor.__new__(Tensor)
    t.data = children[0]
    t.stop_gradient, t.name = aux
    t.grad = None
    t._node = None
    t.persistable = False
    t._retain_grads = False
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)


def _param_flatten(p):
    return (p.data,), (p.stop_gradient, p.name)


def _param_unflatten(aux, children):
    p = Parameter.__new__(Parameter)
    p.data = children[0]
    p.stop_gradient, p.name = aux
    p.grad = None
    p._node = None
    p.persistable = True
    p._retain_grads = False
    p.trainable = not p.stop_gradient
    p.optimize_attr = {"learning_rate": 1.0}
    p.regularizer = None
    p.is_distributed = False
    return p


jax.tree_util.register_pytree_node(Parameter, _param_flatten, _param_unflatten)
