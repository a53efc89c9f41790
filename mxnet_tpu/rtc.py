"""Runtime kernel compilation: user-supplied Pallas kernels.

TPU-native re-design of the reference's RTC subsystem
(``src/common/rtc.cc:31-94`` — NVRTC compiles CUDA-C strings to PTX at
runtime; Python surface ``python/mxnet/rtc.py`` ``CudaModule``/
``get_kernel``/``launch``). On TPU the runtime-kernel substrate is Pallas:
a :class:`PallasModule` takes kernel SOURCE (a Python string defining
Pallas kernel functions over ``pl``/``jnp``), compiles it lazily through
XLA's Mosaic pipeline at first launch, and launches over a grid — same
workflow, same signature-driven input/output convention (``const`` marks
inputs, non-const pointers are outputs, exactly like the reference's
signature strings).

Kernels fall back to Pallas interpret mode off-TPU so user code is testable
on CPU.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, np_dtype
from .ndarray.ndarray import NDArray
from .ops.pallas_kernels import _interpret  # THE platform decision, one copy

__all__ = ["PallasModule", "Kernel", "CudaModule"]


class Kernel(object):
    """One launchable kernel (reference rtc.py:CudaKernel).

    The wrapped function is a Pallas kernel taking ``(*in_refs, *out_refs)``
    in the order declared by the signature.
    """

    def __init__(self, fn, name: str, spec: List[Tuple[str, object, bool]]):
        self._fn = fn
        self._name = name
        self._spec = spec  # (arg_name, dtype, is_output)

    def launch(self, args: Sequence, ctx=None,
               grid_dims: Tuple[int, int, int] = (1, 1, 1),
               block_dims: Tuple[int, int, int] = (1, 1, 1),
               shared_mem: int = 0):
        """Launch over a grid (reference rtc.py:CudaKernel.launch).

        ``args`` pairs with the signature; output args are NDArrays whose
        contents are REPLACED by the kernel result (the CUDA out-pointer
        idiom, realized functionally). ``block_dims``/``shared_mem`` are
        accepted for API parity — Pallas blocks are expressed by the
        kernel's own BlockSpecs/refs, and scratch memory by its allocations.
        """
        del ctx, block_dims, shared_mem
        if len(args) != len(self._spec):
            raise MXNetError("kernel %s: %d args for %d-parameter signature"
                             % (self._name, len(args), len(self._spec)))
        from jax.experimental import pallas as pl

        ins, outs, out_refs = [], [], []
        for a, (_, dt, is_out) in zip(args, self._spec):
            if is_out:
                if not isinstance(a, NDArray):
                    raise MXNetError("kernel %s: output args must be NDArrays"
                                     % self._name)
                outs.append(jax.ShapeDtypeStruct(a.shape, dt))
                out_refs.append(a)
            else:
                data = a._data if isinstance(a, NDArray) else jnp.asarray(a)
                ins.append(data.astype(dt) if data.dtype != dt else data)
        # Preserve grid RANK: a kernel written against grid (1, 8, 1) reads
        # pl.program_id(1) for its real axis — dropping interior 1-dims would
        # silently renumber its axes. Only trailing 1s are safe to strip.
        grid = tuple(int(g) for g in grid_dims) or (1,)
        if any(g < 1 for g in grid):
            # CUDA rejects a zero gridDim launch; silently running zero
            # grid steps would return an unwritten output buffer
            raise MXNetError("kernel %s: invalid grid_dims %r (all dims "
                             "must be >= 1)" % (self._name, grid_dims))
        while len(grid) > 1 and grid[-1] == 1:
            grid = grid[:-1]
        result = pl.pallas_call(
            self._fn,
            out_shape=outs if len(outs) > 1 else outs[0],
            grid=grid,
            interpret=_interpret(),
        )(*ins)
        results = result if isinstance(result, (tuple, list)) else (result,)
        for ref, res in zip(out_refs, results):
            ref._data = res
        return out_refs[0] if len(out_refs) == 1 else out_refs


_SIG_RE = re.compile(
    r"^\s*(?P<const>const\s+)?(?P<type>\w+)\s*(?P<ptr>\*)?\s*(?P<name>\w+)\s*$")

_CTYPE_DT = {"float": np.float32, "double": np.float64, "int": np.int32,  # tpulint: disable=dtype-drift -- C ABI signature table, host-side
             "long": np.int64, "half": np.float16, "bfloat16": jnp.bfloat16,
             "uint8": np.uint8, "int8": np.int8}


def _parse_signature(sig: str):
    spec = []
    for part in sig.split(","):
        m = _SIG_RE.match(part)
        if not m:
            raise MXNetError("cannot parse signature fragment %r" % part)
        base = m.group("type")
        dt = _CTYPE_DT.get(base)
        if dt is None:
            dt = np_dtype(base)
        is_out = bool(m.group("ptr")) and not m.group("const")
        spec.append((m.group("name"), np.dtype(dt) if dt is not jnp.bfloat16
                     else jnp.bfloat16, is_out))
    return spec


class PallasModule(object):
    """Compile Pallas kernel source at runtime (reference rtc.py:CudaModule).

    ``source`` is Python code with ``pl``, ``jnp``, ``jax`` and ``np`` in
    scope, defining one function per kernel; ``exports`` lists the kernel
    names retrievable with :meth:`get_kernel`.

    Example::

        mod = mx.rtc.PallasModule('''
        def axpy(a_ref, x_ref, y_ref, out_ref):
            out_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]
        ''', exports=["axpy"])
        k = mod.get_kernel("axpy", "const float *a, const float *x, "
                                   "const float *y, float *out")
        k.launch((a, x, y, out))
    """

    def __init__(self, source: str, options: Sequence[str] = (),
                 exports: Sequence[str] = ()):
        del options  # NVRTC flags have no Mosaic equivalent; kept for parity
        from jax.experimental import pallas as pl

        self._namespace: Dict[str, object] = {
            "pl": pl, "jnp": jnp, "jax": jax, "np": np}
        try:
            exec(compile(source, "<mxnet_tpu.rtc>", "exec"), self._namespace)
        except SyntaxError as exc:
            raise MXNetError("PallasModule: kernel source does not compile: %s"
                             % exc) from exc
        self._exports = tuple(exports) or tuple(
            n for n, v in self._namespace.items()
            if callable(v) and not n.startswith("_") and n not in
            ("pl", "jnp", "jax", "np"))
        for name in self._exports:
            if name not in self._namespace:
                raise MXNetError("PallasModule: exported kernel %r not "
                                 "defined by source" % name)

    def get_kernel(self, name: str, signature: str) -> Kernel:
        """Bind a kernel by name + C-style signature (reference
        rtc.py:CudaModule.get_kernel)."""
        if name not in self._exports:
            raise MXNetError("kernel %r not exported (exports: %s)"
                             % (name, list(self._exports)))
        return Kernel(self._namespace[name], name, _parse_signature(signature))


#: Reference-compatible alias: code written against ``mx.rtc.CudaModule``
#: gets the Pallas substrate transparently.
CudaModule = PallasModule
