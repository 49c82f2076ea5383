"""TPU Pallas kernels for NL-DPE compute hot-spots.

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle).  Kernels target TPU; on a
CPU-only host they run under the Pallas interpreter.

Every entry point takes ``interpret=None`` and resolves it through
``resolve_interpret``: interpret only when the default JAX backend is CPU,
compile for real on TPU/GPU.  Pass an explicit bool to override.
"""
import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> interpret iff the default backend is CPU; bools pass through."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def divisor_block(n: int, target: int) -> int:
    """Largest block size <= target that divides n (attention wrappers shrink
    blocks instead of zero-padding K/V, which would leak into softmax)."""
    for b in range(min(target, n), 0, -1):
        if n % b == 0:
            return b
    return 1


from .acam_activation.ops import acam_apply
from .crossbar_vmm.ops import crossbar_matmul
from .dual_compute.ops import (fused_crossbar_acam, fused_linear_acam,
                               logdomain_flash_attention)
from .flash_attention.ops import flash_attention
from .nldpe_qmatmul.ops import nldpe_matmul_int8
from .paged_attention.ops import paged_attention
