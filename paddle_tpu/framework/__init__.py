"""Core framework: dtypes, places, flags, RNG, Tensor, dispatch, autograd."""
import jax as _jax

# Full dtype coverage (float64/int64 like the reference) — XLA still computes
# in 32-bit unless explicitly asked for 64-bit values.
_jax.config.update("jax_enable_x64", True)

from . import dtypes  # noqa: E402,F401
from .dtypes import (bfloat16, bool_, complex64, complex128,  # noqa: E402,F401
                     convert_dtype, float16, float32, float64,
                     get_default_dtype, int8, int16, int32, int64,
                     set_default_dtype, uint8)
from .enforce import (EnforceNotMet, InvalidArgumentError,  # noqa: E402,F401
                      enforce)
from .flags import define_flag, get_flags, set_flags  # noqa: E402,F401
from .place import (CPUPlace, CUDAPinnedPlace, CUDAPlace, NPUPlace,  # noqa: E402,F401
                    Place, TPUPlace,
                    current_place, get_device, is_compiled_with_tpu,
                    set_device)
from .random import (default_generator, rng_guard, seed)  # noqa: E402,F401
from .tensor import (GradNode, Parameter, Tensor,  # noqa: E402,F401
                     is_grad_enabled, no_grad, no_grad_guard, run_backward)
from .dispatch import call_op  # noqa: E402,F401

# persistent XLA compilation cache: armed for the whole process at
# import unless FLAGS_compile_cache=0 (directory rule: compile_cache.py)
from . import compile_cache  # noqa: E402,F401
compile_cache.maybe_enable()
