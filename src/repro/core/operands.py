"""Round-static device operands of the jitted round programs.

A jitted function that closes over a concrete array bakes it into the
executable as a constant.  For the round engine that array is the
training data — the whole client dataset, 188 MB at the paper's MNIST
scale — so closing over it inflates every compile, every compiled
program and every persistent-cache entry by that much.
:class:`RoundOperands` keeps those arrays out of the executable: the
round code reads them by name (``operands["x"]``), and
:meth:`RoundOperands.jit` passes them to the program as leading
arguments, binding the traced arguments in their place while it traces.
The returned callable keeps the round's own signature.
"""
from __future__ import annotations

from typing import Any, Callable

import jax


class RoundOperands:
    """Named device arrays a round program reads but never updates."""

    def __init__(self, arrays: dict[str, Any]):
        self._concrete = dict(arrays)
        self._bound = self._concrete

    def __getitem__(self, name: str):
        return self._bound[name]

    def jit(self, fn: Callable, *, donate_argnums: tuple = (),
            in_shardings=None, out_shardings=None,
            operand_shardings=None) -> Callable:
        """``jax.jit(fn)`` with these arrays as extra leading arguments.

        ``donate_argnums`` and ``in_shardings`` describe ``fn``'s own
        arguments; ``operand_shardings`` (a dict like the arrays, or
        None) places the operands.  The result is called and lowered
        with ``fn``'s arguments only.
        """
        def program(operands, *args):
            self._bound = operands
            try:
                return fn(*args)
            finally:
                self._bound = self._concrete

        kw: dict = {"donate_argnums": tuple(a + 1 for a in donate_argnums)}
        if in_shardings is not None:
            kw["in_shardings"] = (operand_shardings,) + tuple(in_shardings)
        if out_shardings is not None:
            kw["out_shardings"] = out_shardings
        jitted = jax.jit(program, **kw)

        def call(*args):
            return jitted(self._concrete, *args)

        call.lower = lambda *args: jitted.lower(self._concrete, *args)
        return call
