from repro.kernels.flash_attention import ops, ref  # noqa: F401
from repro.kernels.flash_attention.kernel import flash_bwd, flash_fwd  # noqa: F401
from repro.kernels.flash_attention.ops import flash_attention  # noqa: F401
