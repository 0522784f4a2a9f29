"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up re-design of PaddlePaddle's capabilities (reference:
/root/reference, efreading/Paddle ~v2.0) for TPU: JAX/XLA is the compiled
execution engine (replacing the reference's C++ Executor + CUDA kernel
registry), Pallas provides custom TPU kernels, and jax.sharding meshes
replace NCCL ring-id collectives. The public API mirrors paddle 2.x so a
reference user can switch with minimal changes.

Layer map vs the reference (SURVEY.md §1):
- layers 0-3 (platform/memory/framework/operators) -> core/ + tensor/ over
  XLA; HBM is runtime-managed, kernels are jnp/lax/Pallas lowerings.
- layer 4 (imperative) -> core/autograd eager tape.
- layers 5/9 (distributed) -> distributed/ (mesh + collectives + fleet).
- layers 7-8 (python api) -> this package's nn/optimizer/amp/io/jit/...
- layer 10 (hapi) -> hapi/Model. layer 11 (inference) -> jit.save + export.
"""
from __future__ import annotations

__version__ = "0.1.0"
full_version = __version__
# reference paddle.version exports a build commit id; stamped at package
# build in the reference, a constant here
commit = "unknown"

import warnings as _warnings

# int64/float64 silently canonicalize to 32-bit unless JAX x64 is enabled;
# that is the intended TPU behavior (int32/bf16-native), so hide the noise.
_warnings.filterwarnings(
    "ignore", message=".*requested in astype is not available.*")
_warnings.filterwarnings(
    "ignore", message=".*Explicitly requested dtype.*is not available.*")

from .core.tensor import Parameter, Tensor, to_tensor, is_tensor  # noqa: F401
from .core.autograd import (no_grad, enable_grad, set_grad_enabled,  # noqa: F401
                            is_grad_enabled, grad)
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core.dtype import (  # noqa: F401
    set_default_dtype, get_default_dtype,
    bool_, uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64, complex64, complex128,
)
from .core.flags import set_flags, get_flags  # noqa: F401

from .tensor import *  # noqa: F401,F403
from .tensor import tensor_methods as _tensor_methods  # noqa: F401  (patch Tensor)

from . import tensor  # noqa: F401
# `from .tensor import *` leaks tensor's submodule objects (math, linalg,
# ...) into this namespace because tensor/__init__ has no __all__; the
# public paddle.linalg namespace must be the dedicated module. NB a plain
# `from . import linalg` would return the leaked attribute, not import.
import importlib as _importlib
linalg = _importlib.import_module(".linalg", __name__)
from . import device  # noqa: F401
from .device import (CPUPlace, CUDAPlace, TPUPlace, CUDAPinnedPlace,  # noqa: F401
                     XPUPlace, get_device, set_device,
                     is_compiled_with_cuda, is_compiled_with_xpu)

# the reference's dygraph VarBase role is played by Tensor directly
VarBase = Tensor


def get_cudnn_version():
    """Reference paddle.get_cudnn_version — no cuDNN on TPU."""
    return None


def get_cuda_rng_state():
    """Reference CUDA rng-state accessors map onto the single JAX key
    state (there is no separate device generator)."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)

# Subpackages imported lazily to keep import light and avoid cycles.
_LAZY_MODULES = (
    "nn", "optimizer", "io", "metric", "amp", "jit", "static",
    "distributed", "vision", "text", "hapi", "callbacks", "profiler",
    "framework", "regularizer", "linalg", "distribution", "incubate",
    "utils", "models", "autograd", "extension", "onnx", "observability",
)


def __getattr__(name):
    if name in _LAZY_MODULES:
        try:
            mod = _importlib.import_module(f".{name}", __name__)
        except ModuleNotFoundError as e:
            # hasattr()/getattr() probing must see AttributeError for a
            # MISSING submodule — but a transitive dep failure (e.g. a
            # broken jax install) must surface as the real import error
            if e.name != f"{__name__}.{name}":
                raise
            raise AttributeError(
                f"module 'paddle_tpu' has no attribute {name!r}") from e
        globals()[name] = mod
        return mod
    if name == "save":
        from .framework.io import save as _save
        return _save
    if name == "load":
        from .framework.io import load as _load
        return _load
    if name == "in_static_mode":
        from .static import in_static_mode
        return in_static_mode
    if name == "summary":
        from .hapi.model_summary import summary as _summary
        return _summary
    if name == "Model":
        from .hapi.model import Model as _Model
        return _Model
    if name == "DataParallel":
        from .distributed.parallel import DataParallel as _DP
        return _DP
    if name == "flops":
        from .hapi.model_summary import flops as _flops
        return _flops
    if name == "ParamAttr":
        from .nn.layer_base import ParamAttr as _PA
        return _PA
    if name == "create_parameter":
        from .static import create_parameter as _cp
        return _cp
    if name == "py_func":
        from .extension import py_func as _pf
        return _pf
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def monkey_patch_math_varbase():
    """reference fluid/dygraph/math_op_patch.py entry point: binds the
    op library onto Tensor. Runs at import here; calling it re-binds
    (idempotent) so late-registered ops become methods too."""
    _tensor_methods._bind()


def monkey_patch_variable():
    """reference fluid/layers/math_op_patch.py: operator overloads on
    static Variables — built into static/program.py Variable here."""
    return None


def in_dygraph_mode():
    """Reference paddle.in_dygraph_mode (alias of in_dynamic_mode)."""
    return in_dynamic_mode()


def enable_dygraph(place=None):
    """Reference paddle.enable_dygraph == leaving static mode."""
    return disable_static(place)


def disable_dygraph():
    """Reference paddle.disable_dygraph == entering static mode."""
    return enable_static()


def in_dynamic_mode():
    """True when executing eagerly (reference paddle.in_dynamic_mode):
    False inside jit tracing AND while static-graph mode is enabled."""
    from .static import in_static_mode
    if in_static_mode():
        return False
    try:
        from .jit.api import in_tracing
        return not in_tracing()
    except ImportError:
        return True


def disable_static(place=None):
    """Leave static-graph mode (reference paddle.disable_static)."""
    from .static import disable_static as _ds
    return _ds()


def enable_static():
    """Enter static-graph mode: paddle.static.data declares symbolic
    inputs, ops record onto the default Program, and
    paddle.static.Executor runs the captured graph (see
    static/program.py)."""
    from .static import enable_static as _es
    return _es()
