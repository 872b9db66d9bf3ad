"""Where a Pallas kernel runs: compiled for the chip, or interpreted."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for a kernel launch, given the caller's request.

    On the TPU backend a kernel is always compiled by Mosaic: asking for
    ``interpret=True`` there is an error, so no result measured on the chip
    can come from the interpreter.  Interpret mode comes only from an
    explicit ``interpret=True`` off the TPU, or from the CPU backend, which
    has no Mosaic compiler (``None`` resolves to it there).  Any other
    backend compiles and fails loudly if the kernel cannot run on it.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError("Pallas kernels are never interpreted on the "
                             "TPU backend")
        return False
    if interpret is None:
        return backend == "cpu"
    return bool(interpret)
