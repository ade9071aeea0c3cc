"""Where a Pallas kernel runs: compiled by Mosaic on the TPU, interpreted
everywhere else — or, where the kernel brings one, replaced there by a jnp
twin of its algorithm.

The choice is made when the calling program is lowered, for the platform it
is lowered for (``jax.lax.platform_dependent``).  Importing a kernel module
decides nothing and queries no backend, and compiling for a described TPU
from a CPU host takes the TPU branch, so such a compile sees the real
kernel.
"""
from __future__ import annotations

import functools

import jax


def run_kernel(kernel, *args, otherwise=None, **kwargs):
    """``kernel(*args, **kwargs, interpret=...)`` with ``interpret`` False
    when lowered for the TPU and True on any other platform.  With
    ``otherwise``, any other platform runs ``otherwise(*args, **kwargs)``
    instead of the interpreter: a kernel on a hot path of the CPU tests
    brings a twin that computes the same algorithm in jnp."""
    default = (functools.partial(kernel, **kwargs, interpret=True)
               if otherwise is None else functools.partial(otherwise, **kwargs))
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, **kwargs, interpret=False),
        default=default,
    )
