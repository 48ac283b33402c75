"""Faults planted under the timed path, to show that the check fails.

Each fault patches the program in this process only and returns a
function that removes the patch. These are the faults a read cell can
have:

- ``half_batch``: half of every batch left out (the second half of each
  read batch answered "absent", while every operation is acknowledged).
- ``answer_altered``: an answer altered where it is produced (the first
  read of every batch returns its value with the low bit flipped). This
  is the control: it breaks the guarantee that a read returns the value
  its key holds.
"""
from __future__ import annotations

from typing import Callable, List

_ACTIVE: List[Callable] = []


def _patch(owner, name: str, new, undo: List[Callable]):
    old = getattr(owner, name)
    setattr(owner, name, new(old))
    undo.append(lambda: setattr(owner, name, old))


def _search_half(orig):
    def search_batch(*a, **kw):
        found, vals = orig(*a, **kw)
        h = (found.shape[0] + 1) // 2
        return found.at[h:].set(False), vals
    return search_batch


def _search_altered(orig):
    def search_batch(*a, **kw):
        found, vals = orig(*a, **kw)
        return found, vals.at[0].set(vals[0] ^ 1)
    return search_batch


PATCHES = {"half_batch": _search_half, "answer_altered": _search_altered}
FAULTS = tuple(PATCHES)


def apply(name: str) -> Callable[[], None]:
    from repro.core import engine
    if name not in PATCHES:
        raise ValueError(f"unknown fault {name!r} (have {FAULTS})")
    undo: List[Callable] = []
    _patch(engine, "search_batch", PATCHES[name], undo)

    def remove():
        while undo:
            undo.pop()()
    _ACTIVE.append(remove)
    return remove


def clear():
    """Remove every fault still planted (after a run that raised)."""
    while _ACTIVE:
        _ACTIVE.pop()()
