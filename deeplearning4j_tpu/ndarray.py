"""Eager ndarray façade — the ``INDArray`` / ``Nd4j`` equivalent.

Reference: ``org.nd4j.linalg.api.ndarray.INDArray`` (~700 methods) and the
``org.nd4j.linalg.factory.Nd4j`` static factory. Here the heavy lifting is
``jax.Array`` + XLA: every method is a thin call into ``jax.numpy``, which
jit-caches compiled kernels per shape/dtype, so eager UX costs O(cache
lookup) instead of a JNI crossing per op (reference call stack SURVEY §3.2).

Design notes (TPU-first):
 - No strides/views/TAD machinery — XLA owns layout. ``i``-suffixed
   "in-place" methods from the reference (``addi``, ``subi``…) exist for
   API parity but are functional underneath (they rebind the wrapped
   buffer; jax.Array is immutable).
 - dtype promotion follows jnp; default float dtype from ``dtypes``.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import dtypes

#: process-wide open-workspace count (hint only — the authoritative
#: scope lookup in utils.workspace is thread-local): the hot eager path
#: pays one int check when no workspace is open anywhere
_WS_DEPTH = 0
import threading as _threading  # noqa: E402
_WS_HINT_LOCK = _threading.Lock()


def _unwrap(x):
    return x.jax() if isinstance(x, NDArray) else x


class NDArray:
    """Thin eager wrapper over a ``jax.Array``.

    Reference parity: org.nd4j.linalg.api.ndarray.BaseNDArray.
    """

    __slots__ = ("_a", "__weakref__")
    __array_priority__ = 100  # beat numpy in mixed expressions

    def __init__(self, value, dtype=None):
        if isinstance(value, NDArray):
            value = value._a
        if dtype is not None:
            self._a = jnp.asarray(value, dtype=dtypes.resolve(dtype))
        else:
            self._a = jnp.asarray(value)
        if _WS_DEPTH:                    # workspace tracking (utils.workspace)
            from deeplearning4j_tpu.utils.workspace import \
                register_allocation
            register_allocation(self)

    # -- interop ----------------------------------------------------------
    def jax(self) -> jax.Array:
        return self._a

    def numpy(self) -> np.ndarray:
        return np.asarray(self._a)

    def __jax_array__(self):
        return self._a

    def item(self):
        return self._a.item()

    # -- properties -------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._a.shape)

    @property
    def dtype(self):
        return self._a.dtype

    @property
    def ndim(self) -> int:
        return self._a.ndim

    def rank(self) -> int:
        return self._a.ndim

    def length(self) -> int:
        return int(self._a.size)

    @property
    def size(self) -> int:
        return int(self._a.size)

    def size_at(self, dim: int) -> int:
        return self._a.shape[dim]

    def is_scalar(self) -> bool:
        return self._a.ndim == 0

    def is_vector(self) -> bool:
        return self._a.ndim == 1

    def is_matrix(self) -> bool:
        return self._a.ndim == 2

    # -- shape ops --------------------------------------------------------
    def reshape(self, *shape) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return NDArray(jnp.reshape(self._a, shape))

    def ravel(self) -> "NDArray":
        return NDArray(jnp.ravel(self._a))

    def transpose(self, *axes) -> "NDArray":
        if not axes:
            return NDArray(jnp.transpose(self._a))
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return NDArray(jnp.transpose(self._a, axes))

    def permute(self, *axes) -> "NDArray":
        return self.transpose(*axes)

    @property
    def T(self) -> "NDArray":
        return NDArray(self._a.T)

    def swap_axes(self, a: int, b: int) -> "NDArray":
        return NDArray(jnp.swapaxes(self._a, a, b))

    def broadcast_to(self, shape) -> "NDArray":
        return NDArray(jnp.broadcast_to(self._a, tuple(shape)))

    def expand_dims(self, axis: int) -> "NDArray":
        return NDArray(jnp.expand_dims(self._a, axis))

    def squeeze(self, axis=None) -> "NDArray":
        return NDArray(jnp.squeeze(self._a, axis))

    def repeat(self, repeats, axis=None) -> "NDArray":
        return NDArray(jnp.repeat(self._a, repeats, axis))

    def tile(self, reps) -> "NDArray":
        return NDArray(jnp.tile(self._a, reps))

    def dup(self) -> "NDArray":
        """Reference: INDArray.dup(). jax.Array is immutable; copy is free."""
        return NDArray(self._a)

    def cast(self, dtype) -> "NDArray":
        return NDArray(self._a.astype(dtypes.resolve(dtype)))

    astype = cast

    # -- indexing ---------------------------------------------------------
    def __getitem__(self, idx) -> "NDArray":
        return NDArray(self._a[_unwrap(idx) if not isinstance(idx, tuple)
                               else tuple(_unwrap(i) for i in idx)])

    def put(self, idx, value) -> "NDArray":
        """Functional scatter (reference putScalar/put are mutating)."""
        if isinstance(idx, tuple):
            idx = tuple(_unwrap(i) for i in idx)
        else:
            idx = _unwrap(idx)
        return NDArray(self._a.at[idx].set(_unwrap(value)))

    def get_scalar(self, *idx):
        return self._a[tuple(idx)].item()

    def slice_along(self, i: int, axis: int = 0) -> "NDArray":
        return NDArray(jnp.take(self._a, i, axis=axis))

    # -- arithmetic (functional + reference "i"-parity names) -------------
    def _binop(self, other, fn) -> "NDArray":
        return NDArray(fn(self._a, _unwrap(other)))

    def add(self, o): return self._binop(o, jnp.add)
    def sub(self, o): return self._binop(o, jnp.subtract)
    def mul(self, o): return self._binop(o, jnp.multiply)
    def div(self, o): return self._binop(o, jnp.divide)
    def rsub(self, o): return NDArray(jnp.subtract(_unwrap(o), self._a))
    def rdiv(self, o): return NDArray(jnp.divide(_unwrap(o), self._a))
    def pow(self, o): return self._binop(o, jnp.power)
    def fmod(self, o): return self._binop(o, jnp.fmod)

    # In-place spellings rebind the buffer (functional underneath).
    def addi(self, o): self._a = jnp.add(self._a, _unwrap(o)); return self
    def subi(self, o): self._a = jnp.subtract(self._a, _unwrap(o)); return self
    def muli(self, o): self._a = jnp.multiply(self._a, _unwrap(o)); return self
    def divi(self, o): self._a = jnp.divide(self._a, _unwrap(o)); return self
    def assign(self, o):
        self._a = jnp.broadcast_to(jnp.asarray(_unwrap(o), self._a.dtype),
                                   self._a.shape)
        return self

    __add__ = add
    __radd__ = add
    __sub__ = sub
    def __rsub__(self, o): return self.rsub(o)
    __mul__ = mul
    __rmul__ = mul
    __truediv__ = div
    def __rtruediv__(self, o): return self.rdiv(o)
    __pow__ = pow
    def __neg__(self): return NDArray(-self._a)
    def __abs__(self): return NDArray(jnp.abs(self._a))
    def __matmul__(self, o): return self.mmul(o)

    # -- comparisons ------------------------------------------------------
    def __lt__(self, o): return self._binop(o, jnp.less)
    def __le__(self, o): return self._binop(o, jnp.less_equal)
    def __gt__(self, o): return self._binop(o, jnp.greater)
    def __ge__(self, o): return self._binop(o, jnp.greater_equal)
    def eq(self, o): return self._binop(o, jnp.equal)
    def neq(self, o): return self._binop(o, jnp.not_equal)

    def __eq__(self, o):
        """Elementwise equality (numpy semantics — safe under jit
        tracing). For the reference's INDArray.equals whole-array
        boolean, use :meth:`equals`."""
        if isinstance(o, (NDArray, jax.Array, np.ndarray, int, float,
                          bool)):
            return self._binop(o, jnp.equal)
        return NotImplemented

    def equals(self, o) -> bool:
        """Whole-array value equality (reference INDArray.equals).
        Eager-only: do not call inside jit."""
        a, b = self._a, _unwrap(o)
        return a.shape == b.shape and bool(jnp.all(a == b))

    # Elementwise __eq__ ⇒ unhashable, same stance as np.ndarray.
    __hash__ = None

    # -- linalg -----------------------------------------------------------
    def mmul(self, other) -> "NDArray":
        return NDArray(jnp.matmul(self._a, _unwrap(other)))

    def dot(self, other) -> "NDArray":
        return NDArray(jnp.dot(self._a, _unwrap(other)))

    def tensordot(self, other, axes) -> "NDArray":
        return NDArray(jnp.tensordot(self._a, _unwrap(other), axes))

    # -- reductions -------------------------------------------------------
    def _reduce(self, fn, axis, keepdims=False) -> "NDArray":
        return NDArray(fn(self._a, axis=axis, keepdims=keepdims))

    def sum(self, axis=None, keepdims=False):
        return self._reduce(jnp.sum, axis, keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._reduce(jnp.mean, axis, keepdims)

    def std(self, axis=None, keepdims=False, ddof=1):
        return NDArray(jnp.std(self._a, axis=axis, keepdims=keepdims,
                               ddof=ddof))

    def var(self, axis=None, keepdims=False, ddof=1):
        return NDArray(jnp.var(self._a, axis=axis, keepdims=keepdims,
                               ddof=ddof))

    def max(self, axis=None, keepdims=False):
        return self._reduce(jnp.max, axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce(jnp.min, axis, keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._reduce(jnp.prod, axis, keepdims)

    def argmax(self, axis=None):
        return NDArray(jnp.argmax(self._a, axis=axis))

    def argmin(self, axis=None):
        return NDArray(jnp.argmin(self._a, axis=axis))

    def cumsum(self, axis=None):
        return NDArray(jnp.cumsum(self._a, axis=axis))

    def norm1(self, axis=None):
        return NDArray(jnp.sum(jnp.abs(self._a), axis=axis))

    def norm2(self, axis=None):
        return NDArray(jnp.sqrt(jnp.sum(jnp.square(self._a), axis=axis)))

    def norm_max(self, axis=None):
        return NDArray(jnp.max(jnp.abs(self._a), axis=axis))

    def any(self): return bool(jnp.any(self._a))
    def all(self): return bool(jnp.all(self._a))

    # -- elementwise math (reference Transforms.*) ------------------------
    def _map(self, fn) -> "NDArray":
        return NDArray(fn(self._a))

    def abs(self): return self._map(jnp.abs)
    def neg(self): return self._map(jnp.negative)
    def exp(self): return self._map(jnp.exp)
    def log(self): return self._map(jnp.log)
    def sqrt(self): return self._map(jnp.sqrt)
    def square(self): return self._map(jnp.square)
    def sin(self): return self._map(jnp.sin)
    def cos(self): return self._map(jnp.cos)
    def tanh(self): return self._map(jnp.tanh)
    def sigmoid(self): return self._map(jax.nn.sigmoid)
    def relu(self): return self._map(jax.nn.relu)
    def softmax(self, axis=-1):
        return NDArray(jax.nn.softmax(self._a, axis=axis))
    def floor(self): return self._map(jnp.floor)
    def ceil(self): return self._map(jnp.ceil)
    def round(self): return self._map(jnp.round)
    def sign(self): return self._map(jnp.sign)
    def clip(self, lo, hi): return NDArray(jnp.clip(self._a, lo, hi))

    # -- misc -------------------------------------------------------------
    def isnan(self): return self._map(jnp.isnan)
    def isinf(self): return self._map(jnp.isinf)

    def __len__(self):
        return self._a.shape[0]

    def __repr__(self):
        return f"NDArray({np.asarray(self._a)!r})"

    def __format__(self, spec):
        return format(np.asarray(self._a), spec)



    # -- row/column vector broadcasting (reference addRowVector etc.) ---
    def _rowvec(self, other, op):
        o = jnp.asarray(_unwrap(other)).reshape(1, -1)
        return NDArray(op(self._a, o))

    def _colvec(self, other, op):
        o = jnp.asarray(_unwrap(other)).reshape(-1, 1)
        return NDArray(op(self._a, o))

    def add_row_vector(self, v):
        return self._rowvec(v, jnp.add)

    def sub_row_vector(self, v):
        return self._rowvec(v, jnp.subtract)

    def mul_row_vector(self, v):
        return self._rowvec(v, jnp.multiply)

    def div_row_vector(self, v):
        return self._rowvec(v, jnp.divide)

    def add_column_vector(self, v):
        return self._colvec(v, jnp.add)

    def sub_column_vector(self, v):
        return self._colvec(v, jnp.subtract)

    def mul_column_vector(self, v):
        return self._colvec(v, jnp.multiply)

    def div_column_vector(self, v):
        return self._colvec(v, jnp.divide)

    # -- row/column access (reference getRow/putRow/getColumn…) ---------
    def get_row(self, i):
        return NDArray(self._a[i])

    def get_rows(self, *idx):
        return NDArray(self._a[jnp.asarray(idx)])

    def get_column(self, i):
        return NDArray(self._a[:, i])

    def get_columns(self, *idx):
        return NDArray(self._a[:, jnp.asarray(idx)])

    def put_row(self, i, v):
        self._a = self._a.at[i].set(jnp.asarray(_unwrap(v)))
        return self

    def put_column(self, i, v):
        self._a = self._a.at[:, i].set(jnp.asarray(_unwrap(v)))
        return self

    def put_scalar(self, idx, value):
        if isinstance(idx, int):
            idx = (idx,)
        self._a = self._a.at[tuple(idx)].set(value)
        return self

    def get_double(self, *idx):
        return float(self._a[tuple(idx)])

    def get_int(self, *idx):
        return int(self._a[tuple(idx)])

    # -- number-returning reductions (reference sumNumber() etc.) -------
    def sum_number(self):
        return float(jnp.sum(self._a))

    def mean_number(self):
        return float(jnp.mean(self._a))

    def max_number(self):
        return float(jnp.max(self._a))

    def min_number(self):
        return float(jnp.min(self._a))

    def std_number(self):
        # Bessel-corrected like std() and the reference stdNumber()
        return float(jnp.std(self._a, ddof=1))

    def amax(self, axis=None, keepdims=False):
        return NDArray(jnp.max(jnp.abs(self._a), axis=axis,
                               keepdims=keepdims))

    def amin(self, axis=None, keepdims=False):
        return NDArray(jnp.min(jnp.abs(self._a), axis=axis,
                               keepdims=keepdims))

    def amean(self, axis=None, keepdims=False):
        return NDArray(jnp.mean(jnp.abs(self._a), axis=axis,
                                keepdims=keepdims))

    # -- named comparisons (reference gt/lt/gte/lte return masks) -------
    def gt(self, o):
        return NDArray(self._a > jnp.asarray(_unwrap(o)))

    def gte(self, o):
        return NDArray(self._a >= jnp.asarray(_unwrap(o)))

    def lt(self, o):
        return NDArray(self._a < jnp.asarray(_unwrap(o)))

    def lte(self, o):
        return NDArray(self._a <= jnp.asarray(_unwrap(o)))

    # -- distances (reference distance1/distance2/cosineSim) ------------
    def distance1(self, o):
        return float(jnp.sum(jnp.abs(self._a - _unwrap(o))))

    def distance2(self, o):
        return float(jnp.sqrt(jnp.sum(jnp.square(
            self._a - _unwrap(o)))))

    def cosine_sim(self, o):
        b = jnp.asarray(_unwrap(o))
        return float(jnp.sum(self._a * b)
                     / (jnp.linalg.norm(self._a)
                        * jnp.linalg.norm(b) + 1e-12))

    # -- NDArrayIndex DSL (reference get(INDArrayIndex...)/put) ----------
    def get(self, *indices) -> "NDArray":
        """arr.get(NDArrayIndex.point(0), NDArrayIndex.interval(1, 3))
        (reference INDArray.get with the indexing DSL)."""
        from deeplearning4j_tpu.ndarray_index import resolve_indices
        return NDArray(self._a[resolve_indices(indices)])

    def put_indices(self, indices, value) -> "NDArray":
        """Functional put at DSL indices (reference INDArray.put(
        INDArrayIndex[], INDArray)) — returns the updated array."""
        from deeplearning4j_tpu.ndarray_index import resolve_indices
        return NDArray(self._a.at[resolve_indices(tuple(indices))]
                       .set(jnp.asarray(_unwrap(value))))

    # -- shape predicates / host exports (reference INDArray) ------------
    def rows(self) -> int:
        return int(self._a.shape[0])

    def columns(self) -> int:
        return int(self._a.shape[1])

    def is_row_vector(self) -> bool:
        return self._a.ndim == 1 or (self._a.ndim == 2
                                     and self._a.shape[0] == 1)

    def is_column_vector(self) -> bool:
        return self._a.ndim == 2 and self._a.shape[1] == 1

    def is_square(self) -> bool:
        return (self._a.ndim == 2
                and self._a.shape[0] == self._a.shape[1])

    def to_int_vector(self):
        return [int(v) for v in np.asarray(self._a).ravel()]

    def to_double_vector(self):
        return [float(v) for v in np.asarray(self._a).ravel()]

    def to_float_matrix(self):
        return np.asarray(self._a, np.float32).tolist()

    # -- number reductions missing from the commit-fae4081 set -----------
    def median_number(self) -> float:
        return float(jnp.median(self._a))

    def percentile_number(self, q) -> float:
        return float(jnp.percentile(self._a, q))

    def entropy_number(self) -> float:
        import jax.scipy.special as jsp
        return float(-jnp.sum(jsp.xlogy(self._a, self._a)))

    def var_number(self) -> float:
        return float(jnp.var(self._a))

    def prod_number(self) -> float:
        return float(jnp.prod(self._a))

    # -- conditional replace (reference replaceWhere/getWhere/cond) ------
    def replace_where(self, replacement, condition) -> "NDArray":
        """Elements matching ``condition`` replaced from ``replacement``
        (reference BooleanIndexing.replaceWhere)."""
        m = condition(self._a) if callable(condition) else condition
        return NDArray(jnp.where(jnp.asarray(_unwrap(m)),
                                 jnp.asarray(_unwrap(replacement)),
                                 self._a))

    def get_where(self, comp, condition):
        """Eager boolean select (reference getWhere) — returns the
        matching elements as a flat NDArray."""
        m = condition(self._a) if callable(condition) else condition
        return NDArray(self._a[jnp.asarray(_unwrap(m))])

    def cond(self, condition) -> "NDArray":
        """Boolean mask of elements matching condition (reference
        MatchConditionTransform)."""
        m = condition(self._a) if callable(condition) else condition
        return NDArray(jnp.asarray(_unwrap(m)).astype(self._a.dtype))

    # -- tensor-along-dimension (reference TAD API) ----------------------
    def tensors_along_dimension(self, *dims) -> int:
        n = self._a.size
        for d in dims:
            n //= self._a.shape[d]
        return int(n)

    def tensor_along_dimension(self, index, *dims) -> "NDArray":
        """The index-th sub-tensor spanning ``dims`` (reference
        tensorAlongDimension): iterate the remaining axes C-order."""
        other = [d for d in range(self._a.ndim) if d not in dims]
        moved = jnp.moveaxis(self._a, other,
                             list(range(len(other))))
        lead = 1
        for d in other:
            lead *= self._a.shape[d]
        flat = moved.reshape((lead,) + moved.shape[len(other):])
        return NDArray(flat[index])

    def vector_along_dimension(self, index, dim) -> "NDArray":
        return self.tensor_along_dimension(index, dim)

    def vectors_along_dimension(self, dim) -> int:
        return self.tensors_along_dimension(dim)


def _ndarray_unflatten(_, children):
    # Rebind the leaf directly: transforms (eval_shape, jit tracing) pass
    # tracer/ShapeDtypeStruct leaves that jnp.asarray would reject.
    obj = object.__new__(NDArray)
    obj._a = children[0]
    return obj


jax.tree_util.register_pytree_node(
    NDArray,
    lambda x: ((x._a,), None),
    _ndarray_unflatten,
)


class Nd4j:
    """Static factory — reference: ``org.nd4j.linalg.factory.Nd4j``."""

    @staticmethod
    def exec(op_name: str, *args, **kwargs):
        """Run any registered declarable op eagerly on NDArrays
        (reference ``Nd4j.exec(DynamicCustomOp)`` — name + args into the
        op registry instead of a JNI dispatch). Returns NDArray(s)."""
        from deeplearning4j_tpu.autodiff.ops_registry import get_op
        from deeplearning4j_tpu.utils.profiler import OpProfiler
        fn = get_op(op_name)
        prof = OpProfiler.get_instance()
        if prof.verbose or prof.enabled:
            prof.op_executed(op_name, args, kwargs)
        out = fn(*[_unwrap(a) for a in args], **kwargs)
        if isinstance(out, tuple):
            return tuple(NDArray(o) if hasattr(o, "dtype") else o
                         for o in out)
        return NDArray(out) if hasattr(out, "dtype") else out

    @staticmethod
    def create(data=None, shape=None, dtype=None) -> NDArray:
        if data is None:
            return Nd4j.zeros(shape, dtype)
        arr = NDArray(data, dtype=dtype or dtypes.default_dtype())
        if shape is not None:
            arr = arr.reshape(shape)
        return arr

    @staticmethod
    def zeros(shape, dtype=None) -> NDArray:
        return NDArray(jnp.zeros(_shape(shape), dtypes.resolve(dtype)))

    @staticmethod
    def ones(shape, dtype=None) -> NDArray:
        return NDArray(jnp.ones(_shape(shape), dtypes.resolve(dtype)))

    @staticmethod
    def full(shape, value, dtype=None) -> NDArray:
        return NDArray(jnp.full(_shape(shape), value, dtypes.resolve(dtype)))

    value_array_of = full

    @staticmethod
    def eye(n, dtype=None) -> NDArray:
        return NDArray(jnp.eye(n, dtype=dtypes.resolve(dtype)))

    @staticmethod
    def arange(*args, dtype=None) -> NDArray:
        return NDArray(jnp.arange(*args, dtype=dtype and dtypes.resolve(dtype)))

    @staticmethod
    def linspace(lo, hi, num, dtype=None) -> NDArray:
        return NDArray(jnp.linspace(lo, hi, num,
                                    dtype=dtypes.resolve(dtype)))

    @staticmethod
    def rand(shape, seed: Optional[int] = None) -> NDArray:
        """Uniform [0,1). Without ``seed``, draws from an advancing
        global stream (reference Nd4j.rand semantics — successive calls
        differ); with ``seed``, deterministic."""
        return NDArray(jax.random.uniform(_next_key(seed), _shape(shape),
                                          dtypes.default_dtype()))

    @staticmethod
    def randn(shape, seed: Optional[int] = None) -> NDArray:
        return NDArray(jax.random.normal(_next_key(seed), _shape(shape),
                                         dtypes.default_dtype()))

    @staticmethod
    def set_random_seed(seed: int) -> None:
        """Reset the global stream (reference Nd4j.getRandom().setSeed)."""
        _GLOBAL_KEY[0] = jax.random.PRNGKey(seed)

    @staticmethod
    def concat(axis: int, *arrays) -> NDArray:
        return NDArray(jnp.concatenate([_unwrap(a) for a in arrays],
                                       axis=axis))

    @staticmethod
    def stack(axis: int, *arrays) -> NDArray:
        return NDArray(jnp.stack([_unwrap(a) for a in arrays], axis=axis))

    @staticmethod
    def hstack(*arrays) -> NDArray:
        return NDArray(jnp.hstack([_unwrap(a) for a in arrays]))

    @staticmethod
    def vstack(*arrays) -> NDArray:
        return NDArray(jnp.vstack([_unwrap(a) for a in arrays]))

    @staticmethod
    def where(cond, x, y) -> NDArray:
        return NDArray(jnp.where(_unwrap(cond), _unwrap(x), _unwrap(y)))

    @staticmethod
    def sort(arr, axis=-1, descending=False) -> NDArray:
        out = jnp.sort(_unwrap(arr), axis=axis)
        if descending:
            out = jnp.flip(out, axis=axis)
        return NDArray(out)


    @staticmethod
    def zeros_like(a):
        return NDArray(jnp.zeros_like(_unwrap(a)))

    @staticmethod
    def ones_like(a):
        return NDArray(jnp.ones_like(_unwrap(a)))

    @staticmethod
    def scalar(value):
        return NDArray(jnp.asarray(value))

    @staticmethod
    def empty(dtype=None):
        return NDArray(jnp.zeros(
            (0,), dtypes.resolve(dtype) if dtype is not None
            else dtypes.default_dtype()))

    @staticmethod
    def diag(v):
        return NDArray(jnp.diag(jnp.asarray(_unwrap(v))))

    @staticmethod
    def pile(*arrs):
        """Stack along a new leading axis (reference Nd4j.pile)."""
        return Nd4j.stack(0, *arrs)

    @staticmethod
    def rot90(a, k: int = 1):
        return NDArray(jnp.rot90(jnp.asarray(_unwrap(a)), k))

    @staticmethod
    def pad(a, pad_width, mode="constant", value=0.0):
        kw = {"constant_values": value} if mode == "constant" else {}
        return NDArray(jnp.pad(jnp.asarray(_unwrap(a)), pad_width,
                               mode=mode, **kw))

    @staticmethod
    def shuffle(a, seed=None):
        """Permute rows (reference Nd4j.shuffle; functional here)."""
        arr = jnp.asarray(_unwrap(a))
        perm = jax.random.permutation(_next_key(seed), arr.shape[0])
        return NDArray(arr[perm])

    @staticmethod
    def argsort(a, axis=-1):
        return NDArray(jnp.argsort(jnp.asarray(_unwrap(a)), axis=axis))

    @staticmethod
    def to_flattened(*arrs):
        """Concatenate raveled arrays (reference Nd4j.toFlattened)."""
        return NDArray(jnp.concatenate(
            [jnp.ravel(jnp.asarray(_unwrap(a))) for a in arrs]))


class Transforms:
    """Reference ``org.nd4j.linalg.ops.transforms.Transforms`` — the
    eager math-helper namespace users reach for first."""

    @staticmethod
    def _wrap1(fn, a):
        return NDArray(fn(jnp.asarray(_unwrap(a))))

    sigmoid = staticmethod(lambda a: Transforms._wrap1(jax.nn.sigmoid, a))
    tanh = staticmethod(lambda a: Transforms._wrap1(jnp.tanh, a))
    relu = staticmethod(lambda a: Transforms._wrap1(jax.nn.relu, a))
    leaky_relu = staticmethod(
        lambda a, alpha=0.01: NDArray(jax.nn.leaky_relu(
            jnp.asarray(_unwrap(a)), alpha)))
    softmax = staticmethod(
        lambda a, axis=-1: NDArray(jax.nn.softmax(
            jnp.asarray(_unwrap(a)), axis=axis)))
    exp = staticmethod(lambda a: Transforms._wrap1(jnp.exp, a))
    log = staticmethod(lambda a: Transforms._wrap1(jnp.log, a))
    sqrt = staticmethod(lambda a: Transforms._wrap1(jnp.sqrt, a))
    abs = staticmethod(lambda a: Transforms._wrap1(jnp.abs, a))
    sign = staticmethod(lambda a: Transforms._wrap1(jnp.sign, a))
    floor = staticmethod(lambda a: Transforms._wrap1(jnp.floor, a))
    ceil = staticmethod(lambda a: Transforms._wrap1(jnp.ceil, a))
    round = staticmethod(lambda a: Transforms._wrap1(jnp.round, a))
    sin = staticmethod(lambda a: Transforms._wrap1(jnp.sin, a))
    cos = staticmethod(lambda a: Transforms._wrap1(jnp.cos, a))
    asin = staticmethod(lambda a: Transforms._wrap1(jnp.arcsin, a))
    acos = staticmethod(lambda a: Transforms._wrap1(jnp.arccos, a))
    atan = staticmethod(lambda a: Transforms._wrap1(jnp.arctan, a))
    hard_tanh = staticmethod(
        lambda a: NDArray(jnp.clip(jnp.asarray(_unwrap(a)), -1, 1)))
    soft_plus = staticmethod(
        lambda a: Transforms._wrap1(jax.nn.softplus, a))
    elu = staticmethod(lambda a: Transforms._wrap1(jax.nn.elu, a))

    @staticmethod
    def pow(a, p):
        return NDArray(jnp.power(jnp.asarray(_unwrap(a)), _unwrap(p)))

    @staticmethod
    def max(a, b):
        return NDArray(jnp.maximum(jnp.asarray(_unwrap(a)),
                                   jnp.asarray(_unwrap(b))))

    @staticmethod
    def min(a, b):
        return NDArray(jnp.minimum(jnp.asarray(_unwrap(a)),
                                   jnp.asarray(_unwrap(b))))

    @staticmethod
    def unit_vec(a):
        arr = jnp.asarray(_unwrap(a))
        return NDArray(arr / (jnp.linalg.norm(arr) + 1e-12))

    @staticmethod
    def normalize_zero_mean_and_unit_variance(a):
        arr = jnp.asarray(_unwrap(a))
        return NDArray((arr - jnp.mean(arr, 0)) / (jnp.std(arr, 0)
                                                   + 1e-12))

    @staticmethod
    def cosine_sim(a, b):
        x = jnp.asarray(_unwrap(a)).ravel()
        y = jnp.asarray(_unwrap(b)).ravel()
        return float(jnp.dot(x, y) / (jnp.linalg.norm(x)
                                      * jnp.linalg.norm(y) + 1e-12))

    @staticmethod
    def euclidean_distance(a, b):
        return float(jnp.linalg.norm(jnp.asarray(_unwrap(a)).ravel()
                                     - jnp.asarray(_unwrap(b)).ravel()))

    @staticmethod
    def manhattan_distance(a, b):
        return float(jnp.sum(jnp.abs(
            jnp.asarray(_unwrap(a)).ravel()
            - jnp.asarray(_unwrap(b)).ravel())))

    @staticmethod
    def all_cosine_similarities(a, b):
        """Pairwise cosine similarities between rows of a and b
        (reference allCosineSimilarities)."""
        x = jnp.asarray(_unwrap(a))
        y = jnp.asarray(_unwrap(b))
        xn = x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)
        yn = y / (jnp.linalg.norm(y, axis=1, keepdims=True) + 1e-12)
        # analytics helper, not a hot path: full-precision matmul (the
        # TPU default bf16 MXU precision is visible at 1e-4 here)
        return NDArray(jnp.matmul(xn, yn.T,
                                  precision=jax.lax.Precision.HIGHEST))


def _shape(shape) -> tuple:
    if shape is None:
        raise ValueError("shape required")
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


# lazily seeded: creating a PRNGKey materialises a device array, and
# importing the library must NEVER initialise a backend (a chip
# belongs to one process: an import that takes it locks out the
# process meant to run on it)
_GLOBAL_KEY = [None]


def _next_key(seed: Optional[int] = None):
    if seed is not None:
        return jax.random.PRNGKey(seed)
    if _GLOBAL_KEY[0] is None:
        _GLOBAL_KEY[0] = jax.random.PRNGKey(0)
    _GLOBAL_KEY[0], sub = jax.random.split(_GLOBAL_KEY[0])
    return sub
