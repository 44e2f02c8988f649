"""Per-kernel backend capability: where each Pallas kernel has a compiled
lowering, and therefore what ``interpret=None`` should resolve to.

The first three kernels are written against the generic Pallas API (no
``pltpu`` scratch shapes, no cross-grid-step state carry); ``moe_experts``
uses Mosaic's scalar prefetch and lowers on a TPU only. Their Mosaic
lowering is checked at real widths for a described TPU v5e by
``tests/test_tpu_compile.py``; the Triton (GPU) lowering is the same
generic Pallas but no test compiles it. Only the CPU backend has no
compiled lowering and runs the Python interpreter. The table is per kernel
so that a future kernel with a narrower lowering (e.g. Mosaic-only
constructs) can declare it here instead of silently interpreting
everywhere, which is the bug class RL005 lints against.
"""
from __future__ import annotations

import jax

# kernel name -> backends with a compiled lowering for its Pallas form
_LOWERS: dict[str, tuple[str, ...]] = {
    "deis_step": ("tpu", "gpu", "cuda", "rocm"),
    "flash_attention": ("tpu", "gpu", "cuda", "rocm"),
    "ssd_scan": ("tpu", "gpu", "cuda", "rocm"),
    # scalar-prefetched block indices: Mosaic only
    "moe_experts": ("tpu",),
}


def default_interpret(kernel: str = "deis_step") -> bool:
    """True when ``kernel`` has no compiled lowering on the active backend.

    This is what every kernel's ``interpret=None`` default resolves to at
    call time: compiled wherever a lowering exists, interpreter otherwise.
    (The old defaults -- ``interpret=True`` baked into jitted signatures,
    then a blanket "interpret off-TPU" -- silently ran kernels in interpret
    mode on backends that could compile them.)
    """
    try:
        lowers = _LOWERS[kernel]
    except KeyError:
        raise ValueError(
            f"unknown kernel {kernel!r}; known: {sorted(_LOWERS)}") from None
    return jax.default_backend() not in lowers
