"""Named sections of the traced kernels (``px.*`` scopes).

The protocol steps are long straight-line functions whose sections are
marked by comments; a profile of the compiled program showed none of
them, only ``fusion.1177``. ``Sections`` gives each section a
``jax.named_scope`` of a constant name without re-indenting the body:
calling it closes the scope that is open and opens the next, and
leaving the ``with`` closes the last. Scopes are metadata only
(``op_name`` on every HLO instruction traced inside): the compiled
program's instructions are the same with or without them, which
tests/test_tick_phases.py pins.

Names are constants with the prefix ``px.`` so that a reduction of the
device trace groups by them after any refactor.
"""

from __future__ import annotations

import jax


class Sections:
    def __init__(self):
        self._open = None

    def __call__(self, name: str) -> None:
        self._close()
        self._open = jax.named_scope(name)
        self._open.__enter__()

    def _close(self) -> None:
        scope, self._open = self._open, None
        if scope is not None:
            scope.__exit__(None, None, None)

    def __enter__(self) -> "Sections":
        return self

    def __exit__(self, *exc) -> None:
        self._close()
