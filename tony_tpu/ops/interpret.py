"""Whether Pallas kernels run compiled (the chip) or under the interpreter
(the CPU test suite). One switch, ``TONY_PALLAS_INTERPRET=1``, read when a
kernel is traced; it never silently turns a chip run into an emulated one."""

from __future__ import annotations

import os

import jax

ENV = "TONY_PALLAS_INTERPRET"


def interpret() -> bool:
    """True when the environment asks for interpreted kernels. On a TPU
    backend that is an error: an inherited variable would otherwise run every
    kernel emulated and say nothing."""
    if os.environ.get(ENV, "") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{ENV}=1 is set but the backend is a TPU: the Pallas kernels would "
            f"run interpreted instead of compiled. Unset {ENV} for chip runs."
        )
    return True
