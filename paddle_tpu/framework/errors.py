"""Structured error types + enforce checks.

Reference parity: paddle/fluid/platform/enforce.h:388-640 (PADDLE_ENFORCE*
macros, typed error codes from error_codes.proto) and platform/errors.cc.
TPU-native: plain python exceptions in the same classes; stack traces come
for free from python, XLA compile errors pass through annotated.
"""
from __future__ import annotations


class EnforceError(RuntimeError):
    code = "LEGACY"


class InvalidArgumentError(EnforceError, ValueError):
    code = "INVALID_ARGUMENT"


class NotFoundError(EnforceError):
    code = "NOT_FOUND"


class OutOfRangeError(EnforceError, IndexError):
    code = "OUT_OF_RANGE"


class AlreadyExistsError(EnforceError):
    code = "ALREADY_EXISTS"


class PermissionDeniedError(EnforceError):
    code = "PERMISSION_DENIED"


class UnimplementedError(EnforceError, NotImplementedError):
    code = "UNIMPLEMENTED"


class UnavailableError(EnforceError):
    code = "UNAVAILABLE"


class FatalError(EnforceError):
    code = "FATAL"


class ExecutionTimeoutError(EnforceError):
    code = "EXECUTION_TIMEOUT"


def enforce(cond: bool, msg: str = "", exc=EnforceError):
    if not cond:
        raise exc(msg or "Enforce check failed")


def enforce_eq(a, b, msg: str = ""):
    if a != b:
        raise InvalidArgumentError(f"{msg} (expected {a!r} == {b!r})")


def enforce_shape_match(shape_a, shape_b, msg: str = ""):
    if tuple(shape_a) != tuple(shape_b):
        raise InvalidArgumentError(
            f"{msg}: shape mismatch {tuple(shape_a)} vs {tuple(shape_b)}"
        )
