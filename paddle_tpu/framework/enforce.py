"""Error-raising helpers.

Analog of ``PADDLE_ENFORCE*`` and the typed error hierarchy in
/root/reference/paddle/fluid/platform/enforce.h and
paddle/phi/core/errors.h. Python-level since all device-side failure comes
back through XLA as exceptions already carrying device context.
"""
from __future__ import annotations


class EnforceNotMet(RuntimeError):
    """Base error, mirrors platform::EnforceNotMet."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


def enforce(cond, msg="enforce failed", error_cls=InvalidArgumentError):
    if not cond:
        raise error_cls(msg)


def enforce_eq(a, b, msg=None, error_cls=InvalidArgumentError):
    if a != b:
        raise error_cls(msg or f"expected {a!r} == {b!r}")


def enforce_shape_rank(shape, rank, name="input"):
    if len(shape) != rank:
        raise InvalidArgumentError(
            f"{name} expected rank {rank}, got shape {tuple(shape)}")
