"""The lower-precision control: the program with its float32 dots at
``Precision.HIGH`` instead of the ``HIGHEST`` the configuration states.

Every matmul of the library passes ``precision=HIGHEST`` itself, and the
Pallas kernels pass it to their in-kernel ``dot_general``; a global
``jax_default_matmul_precision`` reaches none of them.  So the control
replaces ``lax.dot_general`` (both the public ``jax.lax`` name the kernels
call, the internal one that ``jnp.matmul``/``dot`` call, and the default
that ``jnp.einsum`` binds) with a
version that computes a HIGHEST float32 dot as XLA's ``HIGH`` does: three
bf16 passes (hi·hi + hi·lo + lo·hi, f32 accumulation).  Mosaic accepts no
``HIGH`` precision, but it accepts these three bf16 dots, so the kernels
are lowered exactly as the jnp code is — and on the CPU too, where XLA
ignores precision settings.

``lowered()`` switches it on and ``restore()`` off, before the reference
runs; each clears JAX's trace caches, so no trace made on the other side
is reused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax._src.lax import lax as _lax_impl
from jax._src.numpy import einsum as _einsum_mod

_ORIG = _lax_impl.dot_general
_HIGHEST = jax.lax.Precision.HIGHEST


def _is_highest(precision) -> bool:
    if precision is None:
        return False
    if isinstance(precision, (tuple, list)):
        return all(_is_highest(p) for p in precision)
    if isinstance(precision, str):
        return precision.lower() in ("highest", "float32", "fp32")
    return precision == _HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot_general_high(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None, **kw):
    """``lax.dot_general`` with HIGHEST float32 dots computed as HIGH."""
    if not (_is_highest(precision) and jnp.result_type(lhs) == jnp.float32
            and jnp.result_type(rhs) == jnp.float32):
        return _ORIG(lhs, rhs, dimension_numbers, precision=precision,
                     preferred_element_type=preferred_element_type, **kw)
    lh, ll = _split(lhs)
    rh, rl = _split(rhs)

    def one(a, b):
        return _ORIG(a, b, dimension_numbers,
                     precision=jax.lax.Precision.DEFAULT,
                     preferred_element_type=jnp.float32, **kw)

    out = one(lh, rh) + one(lh, rl) + one(ll, rh)
    if preferred_element_type is not None:
        out = out.astype(preferred_element_type)
    return out


def lowered() -> None:
    """Switch the control on, dropping every trace made before."""
    _set(dot_general_high)
    jax.clear_caches()


def restore() -> None:
    """Switch it off and drop every trace made while it was on."""
    _set(_ORIG)
    jax.clear_caches()


def _set(fn) -> None:
    _lax_impl.dot_general = fn
    jax.lax.dot_general = fn
    _einsum_mod.einsum.__kwdefaults__["_dot_general"] = fn
