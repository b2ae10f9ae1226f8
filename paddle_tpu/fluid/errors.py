"""Error classes + enforce helpers (reference paddle/fluid/platform/
enforce.h + errors.h error codes, and operator.cc's exception re-wrap
that attaches the failing op to the message).

The reference throws EnforceNotMet carrying an error code enum; here each
code is a Python exception class (all subclass EnforceNotMet, which
subclasses RuntimeError so existing `except RuntimeError` sites keep
working). `wrap_op_error` is used by the executor/tracer to prepend
[operator < type >] context to kernel failures.
"""
from __future__ import annotations

__all__ = ["EnforceNotMet", "InvalidArgumentError", "NotFoundError",
           "OutOfRangeError", "AlreadyExistsError", "PermissionDeniedError",
           "ResourceExhaustedError", "PreconditionNotMetError",
           "UnimplementedError", "UnavailableError", "FatalError",
           "ExecutionTimeoutError", "enforce", "wrap_op_error"]


class EnforceNotMet(RuntimeError):
    """Base of all framework errors (reference EnforceNotMet)."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PermissionDeniedError(EnforceNotMet):
    pass


class ResourceExhaustedError(EnforceNotMet, MemoryError):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class FatalError(EnforceNotMet):
    pass


class ExecutionTimeoutError(EnforceNotMet, TimeoutError):
    pass


def enforce(cond, message="enforce failed", error_cls=InvalidArgumentError):
    """PADDLE_ENFORCE: raise a typed framework error when cond is false."""
    if not cond:
        raise error_cls(message)


def wrap_op_error(exc: BaseException, op_type: str, op_index: int = -1,
                  extra: str = ""):
    """Re-raise `exc` with operator context prepended (reference
    operator.cc:245 RunImpl catch-and-rethrow). Keeps the original type
    when it is already a framework/JAX error class; otherwise wraps into
    EnforceNotMet so callers get one catchable base."""
    loc = f"[operator < {op_type} > #{op_index}]" if op_index >= 0 \
        else f"[operator < {op_type} >]"
    msg = f"{loc} {extra + ' ' if extra else ''}{exc}"
    cls = type(exc) if isinstance(exc, EnforceNotMet) else EnforceNotMet
    new = cls(msg)
    new.__cause__ = exc
    return new
