"""Which form of an operator runs: the one place `impl` is resolved.

Every operator of `ray_tpu.ops` takes `impl`: "auto", "pallas" (the Pallas
kernels), "pallas_interpret" (the kernels under the interpreter: CPU tests)
or "reference" (the `jnp` form). What an operator's caller alone knows (a
mesh around it, a width that keeps `lax.top_k`) stays with that caller.
"""

import jax

IMPLS = ("pallas", "pallas_interpret", "reference")
LANES = 128


def resolve_impl(impl: str, op: str, *widths: int) -> str:
    """One of `IMPLS` for the operator `op` (named in the refusals).

    `widths`: the minor widths that the operator's kernels take only as
    whole 128-lane tiles on a TPU (none: any width). "auto" is the kernels
    on a TPU backend when the widths allow and the `jnp` form otherwise;
    "pallas" by name refuses other widths; a name that is none of these is
    refused as unknown."""
    tiled = not any(width % LANES for width in widths)
    if impl == "auto":
        return ("pallas" if tiled and jax.default_backend() == "tpu"
                else "reference")
    if impl == "pallas" and not tiled:
        raise ValueError(
            f"{op}: the kernels take widths of whole {LANES}-lane tiles on "
            f"a TPU, got {widths}: use impl='auto' or 'reference'")
    if impl not in IMPLS:
        raise ValueError(f"{op}: unknown impl {impl!r}, not 'auto' or one "
                         f"of {IMPLS}")
    return impl
